"""Attention ops — XLA reference implementations.

These einsum formulations are the portable baseline: they run on CPU (tests)
and TPU, and XLA already fuses mask+softmax+matmul chains well on the MXU.
The Pallas kernels in ops/pallas/ override them on TPU for the flash
(prefill) and paged (decode) paths; this module is the numerics ground truth
those kernels are tested against.

Layout convention throughout the framework: activations are
[batch, seq, heads, head_dim] ("BSHD") — the layout that shards naturally
over a ("dp", "tp") mesh with heads on "tp".
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import jax


NEG_INF = -1e30  # large-negative mask value; -inf breaks softmax when a row is fully masked


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Expand KV heads for GQA: [B, S, Hkv, D] -> [B, S, Hkv*n_rep, D]."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d))
    return x.reshape(b, s, h * n_rep, d)


def causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    kv_valid: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Masked scaled-dot-product attention with GQA.

    q: [B, Sq, Hq, D]   k/v: [B, Skv, Hkv, D]
    q_positions: [B, Sq] absolute position of each query token
    kv_positions: [B, Skv] absolute position of each kv slot
    kv_valid: [B, Skv] bool — False for empty cache slots/padding
    Causality: a query at position p attends kv slots with position <= p.
    window (static): a sliding-window layer also drops positions <= p - window,
    so a query reads `window` keys, its own included (HF's sliding mask).
    Works for prefill (Sq == Skv), chunked prefill, and decode (Sq == 1)
    against a longer cache.
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    n_rep = hq // hkv
    if scale is None:
        scale = d**-0.5

    # Grouped GQA formulation: fold the repeat factor into the einsum batch
    # dims instead of materializing n_rep copies of K/V (repeat_kv would
    # stream the whole KV window through HBM n_rep times per layer).  The
    # matmuls take bf16 inputs with f32 accumulation (the MXU-native mode);
    # only the [.., Sq, Skv] score tensor is ever f32.
    qg = q.reshape(b, sq, hkv, n_rep, d)
    # [B, Hkv, G, Sq, Skv]
    logits = (
        jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32)
        * scale
    )

    mask = q_positions[:, None, None, :, None] >= kv_positions[:, None, None, None, :]
    if window is not None:
        mask = mask & (
            kv_positions[:, None, None, None, :]
            > q_positions[:, None, None, :, None] - window
        )
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, sq, hq, d).astype(q.dtype)
