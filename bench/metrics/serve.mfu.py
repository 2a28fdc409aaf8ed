"""Model FLOPs the window's served work needs, over the window and the
bf16 peak of the cell's chips (one chip's times their count), in
percent.

The work: each request whose first token arrived in the window needed
its whole prompt's forward pass (causal attention at each position), and
each later output token that arrived in the window needed one decode
position attending to the prompt and the tokens before it.  Bucket and
tier padding do not count.
"""
from bench import flops, peaks


def read(res):
    if res["kind"] != "serve":
        return None
    m, w0, sec = res["m"], res["w0"], res["seconds"]
    w1 = w0 + sec
    total = 0.0
    for t in res["client"].all:
        p = t.plan.prompt_len
        for j, x in enumerate(t.tokens):
            if not w0 <= x < w1:
                continue
            total += (flops.prefill_flops(m, p) if j == 0
                      else flops.decode_flops(m, p + j))
    dev = res["device"]
    peak = peaks.peak(dev["kind"])["bf16_flops"] * dev["count"]
    return 100.0 * total / sec / peak if total else None
