"""Forward and backward model FLOPs per token times the window's
training tokens per second, over the chip's bf16 peak, in percent.
Rematerialisation does not count."""
from bench import flops, peaks


def read(res):
    if res["kind"] != "train":
        return None
    per_tok = flops.train_flops_per_token(res["m"], res["mix"]["seq"])
    peak = peaks.peak(res["device"]["kind"])["bf16_flops"]
    return 100.0 * per_tok * res["numbers"]["train_tokens_per_s"] / peak
