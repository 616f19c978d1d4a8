"""Model FLOPs of a training step, and the chip's peaks.

``train_flops_per_token`` counts what the forward and backward passes of
a decoder-only transformer require per token, from the configuration's
published shapes (the keys of a ``bench/configs`` file):

  forward, per layer:
    attention projections   2 * D * (H + 2*KV + H) * hd
    causal attention        2 * 2 * H * hd * (S + 1) / 2   (QK^T and AV,
                            each query sees (S + 1) / 2 keys on average)
    dense SwiGLU MLP        2 * 3 * D * F
    or MoE                  2 * D * E (router) + top_k * 2 * 3 * D * Fe
                            (Fe: ``moe_intermediate_size``, else
                            ``intermediate_size`` as OLMoE names it)
  forward, once:            2 * D * V (output head)
  training = 3 * forward    (the backward pass is twice the forward)

Not counted: recomputation under remat, experts' capacity padding and
dropped tokens, the embedding gather, norms, softmax and the optimizer.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def forward_flops_per_token(c: dict, seq: int) -> float:
    D = c["hidden_size"]
    H = c["num_attention_heads"]
    KV = c["num_key_value_heads"]
    hd = c.get("head_dim") or D // H
    L = c["num_hidden_layers"]
    V = c["vocab_size"]
    proj = 2 * D * (2 * H + 2 * KV) * hd
    attn = 2 * 2 * H * hd * (seq + 1) / 2
    if c.get("num_experts"):
        Fe = c.get("moe_intermediate_size") or c["intermediate_size"]
        ffn = 2 * D * c["num_experts"] + \
            c["num_experts_per_tok"] * 2 * 3 * D * Fe
    else:
        ffn = 2 * 3 * D * c["intermediate_size"]
    return L * (proj + attn + ffn) + 2 * D * V


def train_flops_per_token(c: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(c, seq)


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
