"""90th percentile of the time each request due in the window waited in
the engine's queue: ``admitted_s - submitted_s``, both stamped by the
program (a request is admitted when its prefill or first chunk is
dispatched).  One not admitted by the window's close counts
``t_close - submitted_s``.  None where requests carry no ``admitted_s``."""
from bench import common


def read(res):
    if res["kind"] != "serve":
        return None
    close = res["t_close"]
    waits = []
    for t in res["client"].all:
        if t.due >= res["seconds"]:
            continue
        r = t.req
        admitted = getattr(r, "admitted_s", None)
        if admitted is None:
            return None
        if not admitted or admitted > close:
            admitted = close
        waits.append(admitted - r.submitted_s)
    return common.quantile(waits, 0.9) if waits else None
