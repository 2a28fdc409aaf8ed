"""Forward and backward model FLOPs per token times the window's
training tokens per second, over the bf16 peak of the cell's chips (one
chip's times their count), in percent.  Rematerialisation does not
count."""
from bench import flops, peaks


def read(res):
    if res["kind"] != "train":
        return None
    per_tok = flops.train_flops_per_token(res["m"], res["mix"]["seq"])
    dev = res["device"]
    peak = peaks.peak(dev["kind"])["bf16_flops"] * dev["count"]
    return 100.0 * per_tok * res["numbers"]["train_tokens_per_s"] / peak
