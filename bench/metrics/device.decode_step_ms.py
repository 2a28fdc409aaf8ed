"""Device milliseconds per decode step in the traced stretch: the device
seconds of the dense decode programs (``jit_decode_t{t}``, every tier)
over their executions, from the trace's "XLA Modules" line, averaged
over the chips.  None where no decode program ran in the stretch."""
import re


def read(res):
    tr = res.get("trace")
    if not tr:
        return None
    runs = [v for n, v in tr.get("modules", {}).items()
            if re.fullmatch(r"jit_decode_t\d+", n)]
    count = sum(v["count"] for v in runs)
    if count <= 0:
        return None
    return 1e3 * sum(v["seconds"] for v in runs) / count
