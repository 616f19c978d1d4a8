"""mla_attention_ms (device trace): per window step, the device time
under the ``repro.mla`` scope (latent attention: projections, blockwise
softmax, forward, recomputation and backward), in milliseconds
(bench/scope_reduce.py).  None without the scope."""


def read(rec):
    sc = rec.get("scopes")
    if not sc or not rec["steps"] or not sc["seconds"].get("repro.mla"):
        return None
    return 1e3 * sc["seconds"]["repro.mla"] / rec["steps"]
