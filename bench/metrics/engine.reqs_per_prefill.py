"""Requests per batched prefill call over the window: the engine's
``prefill_reqs`` over its ``prefill_steps``."""


def read(res):
    if res["kind"] != "serve":
        return None
    a, b = res["stats0"], res["stats1"]
    steps = b["prefill_steps"] - a["prefill_steps"]
    if steps <= 0:
        return None
    return (b["prefill_reqs"] - a["prefill_reqs"]) / steps
