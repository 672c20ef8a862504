"""Plain float32 reference of the decoder the benchmark's configurations run,
and the comparison that decides the logit part of `correct`.

The decoder: pre-norm RMSNorm, rotary positions (half-split pairing, as HF
Llama/Mistral/Mixtral), grouped-query causal attention, SwiGLU MLP - dense, or
Mixtral-routed (softmax over exactly the top-k router logits).  No bias, no
QK-norm, no window.  Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no cache, no kernels, no batching, no
scan.  Weights come in as the program's stacked parameter tree and are upcast
ONE LAYER (one expert) AT A TIME, so the reference fits beside the served
model on a 16 GB chip.

It shares no code with `kafka_tpu.models`; `benchmarks/tests/` holds it to
`kafka_tpu.models.forward` at tiny dense and tiny MoE sizes in float32.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Relative RMS error allowed between served logits (bf16 weights and
# activations, f32 accumulation, Pallas or XLA attention through the paged
# cache) and this reference, per compared position:
#     rms(served - ref) / rms(ref)  over the vocabulary.
# Both sides read the SAME bf16 weights, so what differs is bf16 rounding of
# activations on the served side: ~2^-9 relative per rounding, a few roundings
# on the residual path per layer, adding in quadrature.
# Measured on the v5e (my chip runs 1-2, PR 22; the check is deterministic:
# fixed tokens, weights from PRNGKey(0), the same value in every run):
#   yi-1.5-9b, 20 dense layers, Pallas:  0.0129 - 0.0145 over the 5 positions
#   mixtral-8x7b, 2 routed layers, XLA:  0.0256 - 0.0389
# Dense: 0.03, twice the measured error.  Int8 weights (per-channel abs-max:
# ~1.1% RMS error PER MATMUL, seven matmuls a layer) land near 10% at 20
# layers and a dropped term (a residual, the RoPE, a norm) is O(1): both fail.
# Routed: 0.06.  The larger error at only 2 layers comes from the router: the
# served side rounds routing weights and expert outputs to bf16 before the
# combine, and a near-tie at any EARLIER position (not skipped by the rule
# below, which looks at the compared position only) sends that token to
# another expert and reaches the compared position through attention.  At 2
# layers this tolerance catches a dropped term but cannot tell int8 weights
# (~4%) from bf16 rounding; the dense configuration's check can.
LOGIT_REL_RMS_TOL = {"dense": 0.03, "routed": 0.06}

# A routed position is compared only where the reference's own router
# decision is numerically settled: if the gap between the k-th and (k+1)-th
# router logit is under this margin in any layer, bf16 rounding on the served
# side may legitimately pick the other expert, and the position is skipped
# (counted, and at least MIN_COMPARED positions must remain).
ROUTER_TIE_MARGIN = 0.05
MIN_COMPARED = 3


def _f32(x) -> jnp.ndarray:
    return jnp.asarray(x).astype(jnp.float32)


# One jitted program per kind of block, called from a plain Python loop over
# the layers; the layer (and, in a routed layer, the expert) is picked out of
# the program's stacked bf16 arrays INSIDE the program and upcast there, so
# only one layer's (one expert's) float32 copy is alive at a time and a boot
# compiles a handful of programs, not one per eager op.


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(w)


def _rope(x, positions, theta):
    """x [S, H, D]; rotate pairs (i, i + D/2) by positions * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _at(stacked, i):
    return jax.lax.dynamic_index_in_dim(stacked, i, axis=0, keepdims=False)


def _attention(x, lp, theta, eps):
    """x + attention(rms_norm(x)).  wq [H, Hq, D], wk/wv [H, Hkv, D]."""
    positions = jnp.arange(x.shape[0])
    h = _rms_norm(x, lp["ln_attn"], eps)
    q = jnp.einsum("sh,hnd->snd", h, _f32(lp["wq"]))
    k = jnp.einsum("sh,hnd->snd", h, _f32(lp["wk"]))
    v = jnp.einsum("sh,hnd->snd", h, _f32(lp["wv"]))
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    rep = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)  # query head n reads kv head n // rep
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("snd,tnd->nst", q, k) / np.sqrt(q.shape[-1])
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nst,tnd->snd", probs, v)
    return x + jnp.einsum("snd,ndh->sh", out, _f32(lp["wo"]))


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def _moe(h, lp, k):
    """Mixtral routing: softmax over exactly the top-k router logits.
    Returns (out [S, H], gap [S]): gap is the k-th minus the (k+1)-th router
    logit, the margin by which the routing decision was taken."""
    logits = h @ _f32(lp["router"])  # [S, E]
    order = jnp.argsort(-logits, axis=-1)
    srt = jnp.take_along_axis(logits, order, axis=-1)
    gap = (srt[:, k - 1] - srt[:, k] if logits.shape[-1] > k
           else jnp.full((h.shape[0],), jnp.inf))
    top, w_top = order[:, :k], jax.nn.softmax(srt[:, :k], axis=-1)

    def add_expert(e, out):  # one expert upcast at a time
        w_e = jnp.sum(jnp.where(top == e, w_top, 0.0), axis=-1)  # [S]
        y = _swiglu(h, _at(lp["wg"], e), _at(lp["wu"], e), _at(lp["wd"], e))
        return out + w_e[:, None] * y

    out = jax.lax.fori_loop(0, logits.shape[-1], add_expert,
                            jnp.zeros_like(h))
    return out, gap


@partial(jax.jit, static_argnames=("theta", "eps", "k"))
def _layer(x, layers, l, *, theta: float, eps: float, k: int):
    """One decoder layer `l` of the stacked tree; k = 0 for a dense MLP."""
    lp = {name: _at(w, l) for name, w in layers.items()}
    x = _attention(x, lp, theta, eps)
    h = _rms_norm(x, lp["ln_mlp"], eps)
    if k > 0:
        y, gap = _moe(h, lp, k)
    else:
        y = _swiglu(h, lp["wg"], lp["wu"], lp["wd"])
        gap = jnp.full((x.shape[0],), jnp.inf)
    return x + y, gap


@partial(jax.jit, static_argnames=("eps", "tied"))
def _head(x, final_norm, head, positions_out, *, eps: float, tied: bool):
    sel = _rms_norm(x, final_norm, eps)[positions_out]
    return sel @ (_f32(head).T if tied else _f32(head))


@jax.jit
def _embed(table, ids):
    return _f32(table[ids])


def hyper(model_cfg) -> Dict[str, Any]:
    """The numbers the reference needs, read off a kafka_tpu ModelConfig (or
    any object with the same attributes); head counts and sizes are the
    weights' own shapes."""
    if getattr(model_cfg, "rope_scaling_factor", None):
        raise ValueError("reference.py has no rope scaling")
    return {
        "num_layers": model_cfg.num_layers,
        "rope_theta": float(model_cfg.rope_theta),
        "rms_norm_eps": float(model_cfg.rms_norm_eps),
        "tie_word_embeddings": bool(model_cfg.tie_word_embeddings),
        "num_experts": int(model_cfg.num_experts),
        "num_experts_per_tok": int(model_cfg.num_experts_per_tok),
    }


def reference_logits(params, hp: Dict[str, Any], token_ids,
                     positions_out: List[int]) -> Dict[str, Any]:
    """Full causal forward over `token_ids` [S]; float32 logits [n, V] at
    `positions_out`, and per output position the smallest router gap over the
    layers (inf for a dense model)."""
    k = hp["num_experts_per_tok"] if hp["num_experts"] > 0 else 0
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(token_ids, jnp.int32)
        x = _embed(params["embed"], ids)
        min_gap = jnp.full((ids.shape[0],), jnp.inf)
        for l in range(hp["num_layers"]):
            x, gap = _layer(x, params["layers"], jnp.int32(l),
                            theta=hp["rope_theta"], eps=hp["rms_norm_eps"],
                            k=k)
            min_gap = jnp.minimum(min_gap, gap)
        tied = hp["tie_word_embeddings"]
        logits = _head(x, params["final_norm"],
                       params["embed"] if tied else params["lm_head"],
                       jnp.asarray(positions_out, jnp.int32),
                       eps=hp["rms_norm_eps"], tied=tied)
        return {"logits": np.asarray(logits),
                "router_gap": np.asarray(min_gap)[np.asarray(positions_out)]}


def compare_logits(served: np.ndarray, ref: np.ndarray,
                   router_gap: np.ndarray,
                   tol: Optional[float] = None) -> Dict[str, Any]:
    """The logit part of `correct`.  served, ref: [n, V] float32.  `tol`
    defaults to the dense or the routed tolerance, by whether the reference
    routed (a finite router gap)."""
    if tol is None:
        routed = bool(np.isfinite(np.asarray(router_gap)).any())
        tol = LOGIT_REL_RMS_TOL["routed" if routed else "dense"]
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    rel, compared, skipped = [], 0, 0
    for i in range(ref.shape[0]):
        if router_gap[i] < ROUTER_TIE_MARGIN:
            skipped += 1
            continue
        compared += 1
        err = np.sqrt(np.mean((served[i] - ref[i]) ** 2))
        rel.append(float(err / max(np.sqrt(np.mean(ref[i] ** 2)), 1e-30)))
    worst = max(rel) if rel else float("inf")
    finite = bool(np.isfinite(served).all() and np.isfinite(ref).all())
    return {
        "ok": bool(finite and compared >= min(MIN_COMPARED, ref.shape[0])
                   and worst <= tol),
        "rel_rms_max": worst,
        "rel_rms": rel,
        "max_abs": float(np.max(np.abs(served - ref))),
        "compared": compared,
        "skipped_router_ties": skipped,
        "tol": tol,
    }
