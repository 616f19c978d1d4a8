"""Model zoo: unified decoder LMs, MoE, SSM (xLSTM), hybrid (RG-LRU),
encoder-decoder (Whisper), and VLM (LLaVA) — all explicit-SPMD
(Megatron-style tensor parallel over the 'model' axis, FSDP over 'data'),
with every collective routed through the policy dispatcher.
"""

from .config import ModelConfig
from .transformer import (decode_step, expert_choices, forward_logits,
                          init_params, loss_and_stats, loss_fn, prefill)

__all__ = ["ModelConfig", "decode_step", "expert_choices", "forward_logits",
           "init_params", "loss_and_stats", "loss_fn", "prefill"]
