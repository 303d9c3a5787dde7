"""TPU kernels (Pallas) with XLA fallbacks for CPU testing.

The hot ops of the transformer stack: fused flash attention, decode
attention over a paged KV pool, rmsnorm, rotary embeddings, and chunked
cross-entropy. Each op auto-selects the
Pallas TPU kernel on TPU backends and a mathematically identical jnp
implementation elsewhere, so the full test suite runs on the virtual CPU
mesh (SURVEY.md §4.2).
"""

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.paged_attention import paged_decode_attention
from ray_tpu.ops.rmsnorm import rmsnorm
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.ops.cross_entropy import softmax_cross_entropy

__all__ = [
    "flash_attention",
    "paged_decode_attention",
    "rmsnorm",
    "apply_rope",
    "rope_frequencies",
    "softmax_cross_entropy",
]
