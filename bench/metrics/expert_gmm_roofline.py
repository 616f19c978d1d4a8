"""expert_gmm_roofline (device trace): the routed experts' grouped
product against its roofline: the FLOPs of the window's pairs routed to
held experts (``repro.moe.pairs_here``, 2*3*D*Fe each, times 3 for
training) over the device time under the ``repro.moe.experts`` scope
(bench/scope_reduce.py), over the chips' bf16 peak, in percent.  Counted
from routed rows, not from the padded buffer; the time includes the
forward's recomputation under remat.  None without the counter or the
scope."""

from bench import flops, flops_mla_moe


def read(rec):
    pairs = rec.get("moe_window", {}).get("repro.moe.pairs_here")
    sc = rec.get("scopes")
    if not pairs or not sc or not sc["seconds"].get("repro.moe.experts"):
        return None
    f = flops_mla_moe.layer_flops_per_token(rec["published"], rec["seq"])
    work = 3 * pairs * f["expert_pair"]
    peak = flops.peaks(rec["device_kind"])["bf16_flops_per_s"]
    return 100.0 * work / (sc["seconds"]["repro.moe.experts"] * peak)
