"""Rows per decode step over the window: the engine's ``decode_tokens``
over its ``decode_steps``, both counted where the work happens."""


def read(res):
    if res["kind"] != "serve":
        return None
    a, b = res["stats0"], res["stats1"]
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    return (b["decode_tokens"] - a["decode_tokens"]) / steps
