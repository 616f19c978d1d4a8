"""Model FLOPs of a training step of a DeepSeek-V3-layer model on one
chip's share of its experts, from the configuration's published keys
(the top level of a ``bench/configs`` file of the ``train_mla_moe`` kind)
and the pairs routed to the experts held here.

``bench/flops.py`` reads ``num_experts`` and would count this model as
dense.  Per token, forward:

  every layer    latent attention projections
                   2*D*H*(dn + dr) + 2*D*(r + dr) + 2*r*H*(dn + dv)
                   + 2*H*dv*D
                 causal attention, q.k at dn + dr and a.v at dv, each query
                 seeing (S + 1) / 2 keys on average:
                   2*H*(dn + dr + dv)*(S + 1)/2
  dense layers   SwiGLU 2*3*D*F
  MoE layers     shared experts 2*3*D*Fe*n_shared, router 2*D*E (all E)
  held experts   2*3*D*Fe per (token, choice) pair routed to an expert
                 held here: ``pairs_per_token`` pairs summed over layers
  once           the head over the vocabulary slice, 2*D*V
  training       3 * forward (the backward pass is twice the forward)

Not counted: recomputation under remat, the masked half of the attention
square, norms, softmax, routing's sort and gathers, and the optimizer.
"""

from __future__ import annotations


def layer_flops_per_token(c: dict, seq: int) -> dict:
    """Forward FLOPs per token of each part, one layer of each kind."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    r, dn = c["kv_lora_rank"], c["qk_nope_head_dim"]
    dr, dv = c["qk_rope_head_dim"], c["v_head_dim"]
    Fe = c["moe_intermediate_size"]
    E = c["n_routed_experts"] * c["deployment"]["expert_parallel"]
    return {
        "mla_proj": 2 * D * H * (dn + dr) + 2 * D * (r + dr)
        + 2 * r * H * (dn + dv) + 2 * H * dv * D,
        "attention": 2 * H * (dn + dr + dv) * (seq + 1) / 2,
        "dense_mlp": 2 * 3 * D * c["intermediate_size"],
        "shared": 2 * 3 * D * Fe * c["n_shared_experts"],
        "router": 2 * D * E,
        "expert_pair": 2 * 3 * D * Fe,
        "head": 2 * D * c["vocab_size"],
    }


def forward_flops_per_token(c: dict, seq: int,
                            pairs_per_token: float) -> float:
    f = layer_flops_per_token(c, seq)
    L, K = c["num_hidden_layers"], c["first_k_dense_replace"]
    return (L * (f["mla_proj"] + f["attention"]) + K * f["dense_mlp"]
            + (L - K) * (f["shared"] + f["router"])
            + pairs_per_token * f["expert_pair"] + f["head"])


def train_flops_per_token(c: dict, seq: int,
                          pairs_per_token: float) -> float:
    return 3 * forward_flops_per_token(c, seq, pairs_per_token)
