"""Share of the traced stretch of the window in which no operation ran
on the device, in percent: 1 - (union of device-op intervals) / window."""


def read(res):
    tr = res.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["n_ops"] == 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
