"""Plain float32 reference of the Mellum2 decoder (JetBrains/Mellum2-12B-A2.5B-
Instruct, `model_type` "mellum"), written from its published config.json and
the HF building blocks that config names.  Imports nothing of `kafka_tpu`
(a test scans for it); `benchmarks/tests/test_mellum2.py` holds it to
`kafka_tpu.models.forward` at a tiny size in float32.

The decoder: pre-norm RMSNorm; grouped-query attention (32 query / 4 KV heads
x 128) with rotary positions in the half-split pairing; `layer_types` gives
each layer's kind, three `sliding_attention` then one `full_attention`:

* a sliding layer's query at position p attends keys p - W < k <= p (HF
  `sliding_window_causal_mask`: W = `sliding_window` keys, the query's own
  included) and rotates with `rope_parameters.sliding_attention` (default,
  theta 500000);
* a full layer attends every k <= p and rotates with
  `rope_parameters.full_attention`: YaRN as HF `_compute_yarn_parameters`
  (factor 16 over 8192 original positions, beta_fast 32, beta_slow 1, the
  ramp's ends rounded outwards), cos and sin multiplied by the config's
  `attention_factor`;

every MLP is `sparse`: a linear router over 64 experts, softmax, top-8,
renormalised (`norm_topk_prob` true: the same numbers as a softmax over the
top-8 logits alone), each expert a SwiGLU of width 896; final RMSNorm and an
untied head.  Float32 under `default_matmul_precision("highest")`, no cache,
no kernels, no batching, no scan; the stacked bf16 weights are upcast one
layer (one expert) at a time so it fits beside the served model.

Departures from the published model, each deliberate: what the config does
not declare is taken as absent (no QK-norm, no biases, `attention_bias`
false, no attention gate, no shared expert); the MTP head is not served and
not computed; weights are random (the check compares programs, not models).

ROUTER TIES.  `reference.compare_logits` skips a position whose reported
`router_gap` is under 0.05 and needs 3 compared.  With 64 experts the 8th and
9th of 64 unit-variance router logits lie ~0.076 apart on average, so the
smallest raw gap over 8 layers is under 0.05 at 99% of positions: reporting
raw gaps would compare nothing.  What a flip costs was measured at the
published widths (PERF.md section 6, PR 27): the 8th and 9th experts carry
the smallest of eight weights, ~0.07 each, and a position whose routing the
served side decided differently reads 3.4-10.6% rel-rms against 1.3% where it
did not - at or above what int8 weights cost (4.2-15.5%), so flips cannot ride
in the tolerance either.  So this file reports, per
position, the smallest k-th-to-(k+1)-th gap over the layers RESCALED so that
compare_logits' fixed 0.05 falls on `ROUTER_FLIP_MARGIN`, the largest gap the
served side's measured router-logit error was seen to flip (chip readings
beside it), and the configuration compares enough positions (`check.n_decode`)
that three or more are settled.  What the check can tell: a dropped or wrong
term at any layer (window, rope table, norm, residual, routing rule, a
missing expert), and int8 weights (readings beside TOLERANCE).  What it
cannot: a fault that shows only at positions whose routing is within the
margin of a tie, and anything in the engine's own jitted step programs
(PERF.md section 7).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

WINDOWED, GLOBAL = "sliding_attention", "full_attention"

# compare_logits skips a position whose reported gap is under this (its own
# constant, copied: this file imports nothing but jax and numpy)
COMPARE_SKIPS_UNDER = 0.05

# A position is compared only where every layer's raw router gap (k-th minus
# (k+1)-th logit) is at least this.  Measured on the v5e at the published
# widths, 8 layers, Pallas, the 48 positions 1535..1582 (my chip run 1, PR 27;
# `benchmarks/check_power.py`, deterministic: fixed tokens, PRNGKey(0)
# weights): the served error is bimodal, 0.0126-0.0144 at 29 positions and
# 0.0336-0.1055 at 19, and every one of the 19 has a layer whose raw gap is
# under 0.0148 (the largest: position 1542, gap 0.01477, error 0.0389), while
# the eight positions with gaps from 0.0154 up all read 0.0126-0.0134.  0.02
# leaves 1.35x room over the largest flipping gap and five compared positions
# (gaps 0.0204-0.0301); 43 of 48 are skipped.
ROUTER_FLIP_MARGIN = 0.02

# Relative RMS error allowed per compared position, rms(served - ref) /
# rms(ref) over the vocabulary.  Two readings, as PERF.md section 2 asks
# (my chip run 1, PR 27): the served program (bf16 weights and activations,
# Pallas decode + flash prefill through the paged pool) reads 0.0126-0.0134
# at the five compared positions (0.0144 the largest at any settled one);
# this reference on int8 weights (per-output-channel abs-max, dequantised,
# float32 math) reads 0.0422-0.0874 at the same five (0.0422-0.155 over all
# 48: int8's larger router error flips experts at most positions).  0.03 is
# 2.2x the served error and under the smallest int8 reading, so int8 weights
# fail at every compared position and a single flipped 8th expert (>= 0.0336)
# fails too: the margin above, not the tolerance, is what carries ties.
TOLERANCE = {
    "value": 0.03,
    "why": "served bf16 0.0126-0.0134 at the compared positions, int8 "
           "weights 0.0422-0.0874 there (my chip run 1, PR 27; PERF.md 6)",
}


def _f32(x) -> jnp.ndarray:
    return jnp.asarray(x).astype(jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(w)


def rope_table(rope: Dict[str, Any], dim: int):
    """(inverse frequencies [dim/2], attention factor) of one kind's
    `rope_parameters` entry.  numpy float64, cast by the caller."""
    base = float(rope["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return 1.0 / pos_freqs, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"no rope_type {rope['rope_type']!r} in Mellum2")
    factor, orig = float(rope["factor"]), float(rope["original_max_position"])

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)
    att = rope.get("attention_factor")
    if att is None:
        att = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv, float(att)


def _rope(x, inv, att):
    """x [S, H, D] at positions 0..S-1; pairs (i, i + D/2)."""
    d = x.shape[-1]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = (jnp.cos(ang) * att)[:, None, :], (jnp.sin(ang) * att)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _at(stacked, i):
    return jax.lax.dynamic_index_in_dim(stacked, i, axis=0, keepdims=False)


def _attention(x, lp, inv, att, window, eps):
    """x + attention(rms_norm(x)); window 0 = a full-attention layer."""
    s = x.shape[0]
    h = _rms_norm(x, lp["ln_attn"], eps)
    q = jnp.einsum("sh,hnd->snd", h, _f32(lp["wq"]))
    k = jnp.einsum("sh,hnd->snd", h, _f32(lp["wk"]))
    v = jnp.einsum("sh,hnd->snd", h, _f32(lp["wv"]))
    q, k = _rope(q, inv, att), _rope(k, inv, att)
    rep = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)  # query head n reads kv head n // rep
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("snd,tnd->nst", q, k) / np.sqrt(q.shape[-1])
    qp, kp = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    allowed = kp <= qp
    if window:
        allowed = allowed & (kp > qp - window)
    scores = jnp.where(allowed[None], scores, -jnp.inf)
    out = jnp.einsum("nst,tnd->snd", jax.nn.softmax(scores, axis=-1), v)
    return x + jnp.einsum("snd,ndh->sh", out, _f32(lp["wo"]))


def _moe(h, lp, k):
    """softmax over all experts, top-k, renormalised; one expert upcast at a
    time.  Returns (out [S, H], gap [S]): the k-th minus the (k+1)-th router
    logit."""
    logits = h @ _f32(lp["router"])  # [S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    order = jnp.argsort(-logits, axis=-1)
    srt = jnp.take_along_axis(logits, order, axis=-1)
    gap = srt[:, k - 1] - srt[:, k]
    top = order[:, :k]
    p_top = jnp.take_along_axis(probs, top, axis=-1)
    w_top = p_top / jnp.sum(p_top, axis=-1, keepdims=True)  # norm_topk_prob

    def add_expert(e, out):
        w_e = jnp.sum(jnp.where(top == e, w_top, 0.0), axis=-1)  # [S]
        g = h @ _f32(_at(lp["wg"], e))
        u = h @ _f32(_at(lp["wu"], e))
        return out + w_e[:, None] * ((jax.nn.silu(g) * u)
                                     @ _f32(_at(lp["wd"], e)))

    out = jax.lax.fori_loop(0, logits.shape[-1], add_expert,
                            jnp.zeros_like(h))
    return out, gap


@partial(jax.jit, static_argnames=("att", "window", "eps", "k"))
def _layer(x, layers, l, inv, *, att: float, window: int, eps: float, k: int):
    lp = {name: _at(w, l) for name, w in layers.items()}
    x = _attention(x, lp, inv, att, window, eps)
    y, gap = _moe(_rms_norm(x, lp["ln_mlp"], eps), lp, k)
    return x + y, gap


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, positions_out, *, eps: float):
    return _rms_norm(x, final_norm, eps)[positions_out] @ _f32(head)


@jax.jit
def _embed(table, ids):
    return _f32(table[ids])


def hyper(model_cfg) -> Dict[str, Any]:
    """The numbers the reference needs, read by attribute name off the
    served model's config (any object with these attributes)."""
    if model_cfg.tie_word_embeddings or not model_cfg.num_experts:
        raise ValueError("Mellum2: untied head, every MLP routed")
    ropes = {kind: {
        "rope_type": rp.rope_type, "rope_theta": rp.rope_theta,
        "factor": rp.factor, "original_max_position": rp.original_max_position,
        "beta_fast": rp.beta_fast, "beta_slow": rp.beta_slow,
        "attention_factor": rp.attention_factor,
    } for kind, rp in model_cfg.rope_by_kind}
    kinds = list(model_cfg.layer_types)
    if set(kinds) - set(ropes):
        raise ValueError("Mellum2: a rope_parameters entry per layer kind")
    return {
        "layer_types": kinds,
        "sliding_window": int(model_cfg.sliding_window),
        "rope": ropes,
        "head_dim": int(model_cfg.head_dim),
        "rms_norm_eps": float(model_cfg.rms_norm_eps),
        "num_experts_per_tok": int(model_cfg.num_experts_per_tok),
    }


def reference_logits(params, hp: Dict[str, Any], token_ids,
                     positions_out: List[int]) -> Dict[str, Any]:
    """Full causal forward over `token_ids` [S]; float32 logits [n, V] at
    `positions_out`; `router_gap` as the module docstring says (rescaled),
    and `raw_router_gap`, the smallest raw gap over the layers."""
    k = hp["num_experts_per_tok"]
    tables = {kind: rope_table(r, hp["head_dim"])
              for kind, r in hp["rope"].items()}
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(token_ids, jnp.int32)
        x = _embed(params["embed"], ids)
        min_gap = jnp.full((ids.shape[0],), jnp.inf)
        for l, kind in enumerate(hp["layer_types"]):
            inv, att = tables[kind]
            x, gap = _layer(
                x, params["layers"], jnp.int32(l),
                jnp.asarray(inv, jnp.float32), att=att,
                window=(hp["sliding_window"]
                        if kind == WINDOWED and not hp.get("ignore_window")
                        else 0),
                eps=hp["rms_norm_eps"], k=k)
            min_gap = jnp.minimum(min_gap, gap)
        logits = _head(x, params["final_norm"], params["lm_head"],
                       jnp.asarray(positions_out, jnp.int32),
                       eps=hp["rms_norm_eps"])
        raw = np.asarray(min_gap)[np.asarray(positions_out)]
        return {"logits": np.asarray(logits),
                "router_gap": raw * (COMPARE_SKIPS_UNDER / ROUTER_FLIP_MARGIN),
                "raw_router_gap": raw}


def variants(hp: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """The reference with one mechanism taken out, for the check's POWER:
    were the served program to make this mistake, would the logits at the
    compared positions move by more than the tolerance?  `all_global`: no
    layer honours its window (each keeps its own rotary table).  `default_rope`: the full-attention layers
    rotate with the sliding layers' default table instead of YaRN."""
    return {
        "all_global": dict(hp, ignore_window=True),
        "default_rope": dict(hp, rope=dict(hp["rope"],
                                           **{GLOBAL: hp["rope"][WINDOWED]})),
    }
