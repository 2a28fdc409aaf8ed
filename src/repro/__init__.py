"""DynaFlow reproduction — programmable operator scheduling on JAX.

``repro.api.compile`` is the frontend: one call from a model (or arch
name, or raw traced Module) to a ``Program`` whose step builders route
through the plan IR, the persistent PlanStore and the tiered serve
runtime.  ``repro.core`` holds the substrate those builders compose.
"""
from . import api  # noqa: F401  (the facade is the public frontend)
