"""The bytes the traced stretch's decode steps had to read, at the HBM
bandwidth of the cell's chips (one chip's times their count), over the
device's busy time in that stretch (averaged over the chips), in
percent.

Each decode step must read every weight once; each output token it
yields must read its row's K and V up to the token's position: the bytes
of the whole model, the sum over the chips that hold it.  Steps
are the engine's ``decode_steps`` between the trace's start and the
window's close; tokens are those that arrived between them.  The busy
time is the device trace's, so a slower or faster host loop does not
move this share; in a decode cell the few short prefills are part of
the busy time and hold the share down a little.
"""
from bench import flops, peaks


def read(res):
    tr = res.get("trace")
    if res["kind"] != "serve" or not tr or tr["busy_s"] <= 0 \
            or res.get("stats_trace") is None:
        return None
    m, t0, t1 = res["m"], res["t_trace"], res["t_close"]
    steps = res["stats1"]["decode_steps"] - res["stats_trace"]["decode_steps"]
    if steps <= 0:
        return None
    kv = 0
    for t in res["client"].all:
        p = t.plan.prompt_len
        for j, x in enumerate(t.tokens):
            if j and t0 <= x < t1:
                kv += flops.kv_bytes(m, p + j)
    need = steps * flops.weight_bytes(m) + kv
    dev = res["device"]
    bw = peaks.peak(dev["kind"])["hbm_bytes_per_s"] * dev["count"]
    return 100.0 * need / bw / tr["busy_s"]
