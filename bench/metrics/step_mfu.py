"""step_mfu (host clock and a count from shapes): the window's training
tokens per second times the model FLOPs per token of bench/flops.py, over
the chips' bf16 peak from bench/peaks.json, in percent."""

from bench import flops


def read(rec):
    if not rec["steps"] or rec.get("rehearsal"):
        return None
    rate = rec["tokens"] / rec["window_s"]
    peak = flops.peaks(rec["device_kind"])["bf16_flops_per_s"]
    f = flops.train_flops_per_token(rec["published"], rec["seq"])
    return 100.0 * rate * f / (rec["chips"] * peak)
