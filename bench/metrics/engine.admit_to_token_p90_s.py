"""90th percentile, over the requests due in the window that the engine
admitted by its close, of the time from admission to the first token:
``first_token_s - admitted_s``, both stamped by the program.  One with
no first token by the close counts ``t_close - admitted_s``.  None where
requests carry no ``admitted_s``."""
from bench import common


def read(res):
    if res["kind"] != "serve":
        return None
    close = res["t_close"]
    spans = []
    for t in res["client"].all:
        if t.due >= res["seconds"]:
            continue
        r = t.req
        admitted = getattr(r, "admitted_s", None)
        if admitted is None:
            return None
        if not admitted or admitted > close:
            continue
        first = r.first_token_s
        if not first or first > close:
            first = close
        spans.append(first - admitted)
    return common.quantile(spans, 0.9) if spans else None
