"""Rotary position embeddings (RoPE), including Llama-3.x NTK-by-parts scaling
and YaRN, and one table per kind of layer for models whose windowed and
global layers rotate differently.

Frequencies are computed on the fly from integer position ids rather than
from a precomputed [max_context, dim] table: paged decoding addresses
positions per-sequence, and an on-the-fly gatherless formulation keeps the
decode step free of HBM table lookups (the cos/sin math fuses into the
surrounding elementwise ops under XLA).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax.numpy as jnp


def rope_frequencies(cfg) -> jnp.ndarray:
    """Per-pair inverse frequencies [head_dim//2], with Llama-3 scaling."""
    dim = cfg.head_dim
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    )
    if cfg.rope_scaling_factor is None:
        return inv_freq

    # Llama-3.x "NTK-by-parts": low-frequency components are slowed by
    # `factor`, high-frequency kept, mid-band interpolated smoothly.
    low_freq_wavelen = cfg.rope_original_max_position / cfg.rope_low_freq_factor
    high_freq_wavelen = cfg.rope_original_max_position / cfg.rope_high_freq_factor
    wavelen = 2.0 * math.pi / inv_freq
    scaled = inv_freq / cfg.rope_scaling_factor
    smooth = (cfg.rope_original_max_position / wavelen - cfg.rope_low_freq_factor) / (
        cfg.rope_high_freq_factor - cfg.rope_low_freq_factor
    )
    mid = (1.0 - smooth) * scaled + smooth * inv_freq
    out = jnp.where(wavelen > low_freq_wavelen, scaled, inv_freq)
    out = jnp.where(
        (wavelen <= low_freq_wavelen) & (wavelen >= high_freq_wavelen), mid, out
    )
    return out


def _plain_frequencies(theta: float, dim: int) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_frequencies(rp, dim: int) -> Tuple[jnp.ndarray, float]:
    """(inverse frequencies [dim//2], attention factor) of YaRN, as HF
    `_compute_yarn_parameters` computes them: pairs that turn more than
    `beta_fast` times over the original context keep their frequency
    (extrapolation), pairs that turn fewer than `beta_slow` times are slowed
    by `factor` (interpolation), and a linear ramp over the pair index
    blends the two in between.  The ramp's ends are rounded outwards (HF's
    `truncate`, its default) and clipped to the pair range.  The attention
    factor multiplies cos and sin; left out of the config it is
    0.1 * ln(factor) + 1."""
    base, orig = float(rp.rope_theta), float(rp.original_max_position)

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(orig / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(base)))

    low = max(math.floor(correction_dim(rp.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rp.beta_slow)), dim - 1)
    if low == high:
        high += 0.001  # HF: no division by zero on a degenerate ramp
    extrapolation = _plain_frequencies(base, dim)
    interpolation = extrapolation / rp.factor
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    inv_freq = interpolation * ramp + extrapolation * (1.0 - ramp)
    factor = rp.attention_factor
    if factor is None:
        factor = 0.1 * math.log(rp.factor) + 1.0 if rp.factor > 1 else 1.0
    return inv_freq, float(factor)


def kind_frequencies(cfg, kind: str) -> Tuple[jnp.ndarray, float]:
    """(inverse frequencies, attention factor) of one kind of layer: its
    entry of `cfg.rope_by_kind`, else the model-wide table."""
    rp = cfg.rope_of(kind)
    if rp is None:
        return rope_frequencies(cfg), 1.0
    if rp.rope_type == "yarn":
        return yarn_frequencies(rp, cfg.head_dim)
    return _plain_frequencies(rp.rope_theta, cfg.head_dim), 1.0


def rope_cos_sin(
    positions: jnp.ndarray, inv_freq: jnp.ndarray,
    attention_factor: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for integer positions [...]: returns [..., head_dim//2].
    `attention_factor` (YaRN) scales both; 1.0 leaves the program as it was."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    if attention_factor != 1.0:
        return (jnp.cos(angles) * attention_factor,
                jnp.sin(angles) * attention_factor)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Rotate q or k. x: [..., heads, head_dim]; cos/sin broadcast on heads.

    Uses the HF-style "rotate_half" pairing (first half / second half), so
    converted HuggingFace checkpoints produce identical outputs.
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[..., None, :].astype(jnp.float32)
    sin = sin[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    r1 = x1f * cos - x2f * sin
    r2 = x2f * cos + x1f * sin
    return jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)
