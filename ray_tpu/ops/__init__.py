from ray_tpu.ops.attention import (attention_reference, flash_attention,
                                   repeat_kv)
from ray_tpu.ops.moe import moe_ffn, top_k_routing
from ray_tpu.ops.norms import apply_rope, rms_norm, rope_frequencies
from ray_tpu.ops.paged_kv import paged_decode_attention
from ray_tpu.ops.ring_attention import ring_attention

__all__ = ["attention_reference", "flash_attention",
           "paged_decode_attention", "repeat_kv", "moe_ffn",
           "top_k_routing", "apply_rope", "rms_norm", "rope_frequencies",
           "ring_attention"]
