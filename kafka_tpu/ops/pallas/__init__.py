"""Pallas TPU kernels — the framework's native tier.

The reference has no native code at all (SURVEY §2.3: its compute lived
behind remote gateways); these kernels are the TPU-native equivalent of the
CUDA kernels a GPU serving stack would carry.  Each kernel is validated
against the XLA reference formulation in ops/attention.py, which remains the
numerics ground truth and the portable fallback (CPU tests, non-TPU
platforms, and mesh layouts the per-shard kernel cannot express).

Selection is driven by `ModelConfig.attention_backend`:
  "auto"   — pallas for paged decode on single-device TPU AND on pure
             tp(/tq) meshes whose head split lines up per-shard
             (pallas_mesh_ok: shard_map runs the kernel per device);
             xla otherwise
  "pallas" — force the kernels (interpret mode off-TPU; tests use this)
  "xla"    — force the reference path
"""

from .flash_prefill import paged_prefill_attention
from .latent_prefill import latent_prefill_fold
from .selective_scan import selective_scan
from .paged_attention import (
    paged_decode_attention,
    paged_decode_attention_int8,
    paged_decode_attention_int8_sharded,
    paged_decode_attention_latent,
    paged_decode_attention_sharded,
    paged_decode_attention_window,
    paged_verify_attention,
    paged_verify_attention_sharded,
    pallas_mesh_ok,
)

__all__ = [
    "latent_prefill_fold",
    "paged_decode_attention",
    "paged_decode_attention_int8",
    "paged_decode_attention_int8_sharded",
    "paged_decode_attention_latent",
    "paged_decode_attention_sharded",
    "paged_decode_attention_window",
    "paged_prefill_attention",
    "paged_verify_attention",
    "paged_verify_attention_sharded",
    "pallas_mesh_ok",
    "selective_scan",
]
