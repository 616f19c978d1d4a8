"""expert_load_imbalance (program counter): per window step, the largest
group of (token, choice) pairs that one held expert of one MoE layer got
(``repro.moe.max_expert_pairs``, summed over the window's steps), over
the mean held group (``repro.moe.pairs_here`` over the window's steps,
MoE layers and held experts).  1 is even routing over the held experts.
None without the counters."""


def read(rec):
    w = rec.get("moe_window", {})
    top, here = w.get("repro.moe.max_expert_pairs"), w.get(
        "repro.moe.pairs_here")
    if not top or not here:
        return None
    pub = rec["published"]
    groups = (pub["num_hidden_layers"] - pub["first_k_dense_replace"]) * \
        pub["n_routed_experts"]
    return top * groups / here
