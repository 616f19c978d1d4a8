"""moe_step_mfu (host clock and a count from shapes): the window's
training tokens per second times the FLOPs per token of
bench/flops_mla_moe.py, with the held experts' pairs per token read from
the window's ``repro.moe.pairs_here`` counter, over the chips' bf16 peak
from bench/peaks.json, in percent.  None without the counter."""

from bench import flops, flops_mla_moe


def read(rec):
    pairs = rec.get("moe_window", {}).get("repro.moe.pairs_here")
    if not rec["steps"] or rec.get("rehearsal") or pairs is None:
        return None
    rate = rec["tokens"] / rec["window_s"]
    peak = flops.peaks(rec["device_kind"])["bf16_flops_per_s"]
    f = flops_mla_moe.train_flops_per_token(rec["published"], rec["seq"],
                                            pairs / rec["tokens"])
    return 100.0 * rate * f / (rec["chips"] * peak)
