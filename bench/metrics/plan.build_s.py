"""Seconds the PlanStore spent lowering and specialising plans during
set-up (``lower_s`` + ``specialize_s``), on the host clock."""


def read(res):
    if res["kind"] != "serve":
        return None
    ps = res["stats0"]["plan_store"]
    return ps["lower_s"] + ps["specialize_s"]
