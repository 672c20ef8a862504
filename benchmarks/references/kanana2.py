"""Plain float32 reference of the Kanana-2 decoder (kakaocorp/kanana-2-30b-a3b-
instruct-2601, `model_type` "deepseek_v3"), written from its published
config.json and the HF building blocks that config names.  Imports nothing of
`kafka_tpu` (a test scans for it); `benchmarks/tests/test_kanana2.py` holds it
to `kafka_tpu.models.forward` at a tiny size in float32.

The decoder, per token x at position p, pre-norm residual blocks, RMSNorm:

* attention, every layer (MLA without a query low-rank): q = x W_q, per head
  [q_nope | q_rope]; [c | k_r] = x W_kva; c~ = RMSNorm(c) with its own weight;
  per head [k_nope | v] = c~ W_kvb,h.  Rotary (theta over the rotary width) on
  q_rope and on k_r, ONE vector a token shared by all heads; the published
  values are interleaved pairs (`rope_interleave`): de-interleaved
  (x[0::2] ++ x[1::2]) and then rotated half-split, as HF
  `apply_rotary_pos_emb_interleave`.  Scores (q_nope . k_nope + q_rope . k_r)
  / sqrt(nope + rope widths), causal softmax, values, W_o.  This is the
  EXPANDED form, as published; the served program's paged decode runs the
  algebraically equal absorbed form.
* FFN: the first `first_k_dense` layers a dense SwiGLU; the others
  sigma = sigmoid(x W_r); the k experts with the largest sigma + b are chosen
  (b = `e_score_correction_bias`; `n_group` = `topk_group` = 1, so no group
  limit), each weighs scale * sigma_e / (sum of the chosen sigma + 1e-20) (the
  bias chooses, it does not weigh), plus one always-on shared SwiGLU.
* final RMSNorm, untied head.

Float32 under `default_matmul_precision("highest")`, no cache, no kernels, no
batching, no scan; the stacked bf16 weights are upcast one layer (one expert)
at a time so it fits beside the served model.

Departures from the published model, each deliberate: no MTP head (none is
declared); weights are random (the check compares programs, not models).

ROUTER TIES.  `reference.compare_logits` skips a position whose reported
`router_gap` is under 0.05 and needs 3 compared.  Here the gap is the k-th
minus the (k+1)-th of sigma + b, in sigma's units; with 128 experts the 6th
and 7th lie a few thousandths apart, and a flip swaps an expert that weighs
~scale / k = 0.41 of the routed sum: far above any tolerance that fails int8
weights.  So, as `references/mellum2.py` does, this file reports per position
the smallest gap over the routed layers RESCALED so that compare_logits' fixed
0.05 falls on `ROUTER_FLIP_MARGIN`, the largest gap the served side's measured
router error was seen to flip (chip readings beside it), and the
configuration compares enough positions (`check.n_decode`) that three or more
are settled.  What the check can tell: a dropped or wrong term at any layer
(`variants` below, one each), and int8 weights.  What it cannot: a fault that
shows only at positions whose routing is within the margin of a tie, and
anything in the engine's own jitted step programs (PERF.md section 7).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# compare_logits skips a position whose reported gap is under this (its own
# constant, copied: this file imports nothing but jax and numpy)
COMPARE_SKIPS_UNDER = 0.05

# A position is compared only where every routed layer's raw gap (k-th minus
# (k+1)-th of sigma + b) is at least this.  Measured on the v5e at the
# published widths, 6 layers, Pallas decode, the 48 positions 1535..1582 (my
# chip run 3, PR 31; `benchmarks/check_power.py`, deterministic: fixed tokens,
# PRNGKey(0) weights): the served error is bimodal, 0.0202-0.0247 at 32
# positions and 0.155-0.756 at 16, and 15 of the 16 have a layer whose raw gap
# is 0.0020 or less; the sixteenth (position 1552, error 0.191) has 0.0052.
# That is what a served hidden state 2% off does: router logits move by
# ~0.02, sigma near the 6th place (0.8-0.9) by ~0.002-0.003, and a difference
# of two such errors reaches 0.005 at one position in a few dozen.  0.008
# leaves 1.5x room over that gap and six compared positions (gaps
# 0.0087-0.0120, served 0.0208-0.0239); 42 of 48 are skipped.
ROUTER_FLIP_MARGIN = 0.008

# Relative RMS error allowed per compared position, rms(served - ref) /
# rms(ref) over the vocabulary.  Two readings, as PERF.md section 2 asks (my
# chip run 3, PR 31): the served program (bf16 weights and activations,
# expanded XLA prefill and the absorbed Pallas decode through the paged
# latent pool) reads 0.0208-0.0239 at the six compared positions (0.0247 the
# largest at any settled one); this reference on int8 weights
# (per-output-channel abs-max, dequantised, float32 math) reads
# 0.0450-0.0535 at five of the six and 0.231 at the sixth, where int8's
# router error flips an expert (0.0447 the smallest at any of the 48).  0.033
# is 1.38x the served error and 0.73x the smallest int8 reading, so int8
# weights fail at every compared position, a flipped 6th expert (>= 0.155)
# fails, and every one of `variants` (0.65-1.26 at every position) fails:
# the margin above, not the tolerance, is what carries ties.
TOLERANCE = {
    "value": 0.033,
    "why": "served bf16 0.0208-0.0239 at the compared positions, int8 "
           "weights 0.0450-0.231 there (my chip run 3, PR 31; PERF.md 6)",
}


def _f32(x) -> jnp.ndarray:
    return jnp.asarray(x).astype(jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(w)


def _rope(x, theta: float, interleave: bool):
    """x [S, ..., d] at positions 0..S-1: de-interleave the published pairs,
    then rotate with pairs (i, i + d/2)."""
    d = x.shape[-1]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32)[None, :])
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _at(stacked, i):
    return jax.lax.dynamic_index_in_dim(stacked, i, axis=0, keepdims=False)


def _attention(x, lp, hp):
    """x + attention(rms_norm(x)), expanded as published."""
    r, dn = hp["kv_lora_rank"], hp["qk_nope_head_dim"]
    dr, eps = hp["qk_rope_head_dim"], hp["rms_norm_eps"]
    s = x.shape[0]
    h = _rms_norm(x, lp["ln_attn"], eps)
    q = jnp.einsum("sh,hnd->snd", h, _f32(lp["wq"]))
    kva = h @ _f32(lp["wkva"])
    c = kva[:, :r]
    if not hp.get("skip_latent_norm"):
        c = _rms_norm(c, lp["ln_kv"], eps)
    interleave = hp["rope_interleave"] and not hp.get("skip_deinterleave")
    q_rope = _rope(q[..., dn:], hp["rope_theta"], interleave)
    k_rope = _rope(kva[:, r:], hp["rope_theta"], interleave)
    kv = jnp.einsum("tr,nrd->tnd", c, _f32(lp["wkvb"]))  # [k_nope | v]
    scores = (jnp.einsum("snd,tnd->nst", q[..., :dn], kv[..., :dn])
              + jnp.einsum("snd,td->nst", q_rope, k_rope)) / np.sqrt(dn + dr)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("nst,tnd->snd", jax.nn.softmax(scores, axis=-1),
                     kv[..., dn:])
    return x + jnp.einsum("snd,ndh->sh", out, _f32(lp["wo"]))


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ _f32(wg)) * (h @ _f32(wu))) @ _f32(wd)


def _moe(h, lp, hp):
    """The routed experts and the shared branch; one expert upcast at a
    time.  Returns (out [S, H], gap [S]): the k-th minus the (k+1)-th of what
    the choice is made by."""
    k, scale = hp["num_experts_per_tok"], hp["routed_scaling_factor"]
    logits = h @ _f32(lp["router"])  # [S, E]
    if hp.get("softmax_routing"):
        choose_by, order = logits, jnp.argsort(-logits, axis=-1)
        top = order[:, :k]
        w_top = jax.nn.softmax(
            jnp.take_along_axis(logits, top, axis=-1), axis=-1)
    else:
        sigma = jax.nn.sigmoid(logits)
        choose_by = sigma if hp.get("ignore_bias") \
            else sigma + _f32(lp["router_bias"])[None, :]
        # stable: a tie goes to the lower index
        order = jnp.argsort(-choose_by, axis=-1)
        top = order[:, :k]
        w_top = jnp.take_along_axis(sigma, top, axis=-1)
        if not hp.get("skip_renormalise"):
            w_top = w_top / (jnp.sum(w_top, axis=-1, keepdims=True) + 1e-20)
        if not hp.get("skip_scale"):
            w_top = w_top * scale
    srt = jnp.take_along_axis(choose_by, order, axis=-1)
    gap = srt[:, k - 1] - srt[:, k]

    def add_expert(e, out):
        w_e = jnp.sum(jnp.where(top == e, w_top, 0.0), axis=-1)  # [S]
        return out + w_e[:, None] * _swiglu(
            h, _at(lp["wg"], e), _at(lp["wu"], e), _at(lp["wd"], e))

    out = jax.lax.fori_loop(0, logits.shape[-1], add_expert,
                            jnp.zeros_like(h))
    if "ws_g" in lp and not hp.get("skip_shared"):
        out = out + _swiglu(h, lp["ws_g"], lp["ws_u"], lp["ws_d"])
    return out, gap


def _freeze(hp: Dict[str, Any]):
    return tuple(sorted(hp.items()))


@partial(jax.jit, static_argnames=("hp", "routed"))
def _layer(x, stack, l, *, hp, routed: bool):
    hp = dict(hp)
    lp = {name: _at(w, l) for name, w in stack.items()}
    x = _attention(x, lp, hp)
    h = _rms_norm(x, lp["ln_mlp"], hp["rms_norm_eps"])
    if not routed:
        return x + _swiglu(h, lp["wg"], lp["wu"], lp["wd"]), \
            jnp.full((x.shape[0],), jnp.inf)
    y, gap = _moe(h, lp, hp)
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
    if model_cfg.tie_word_embeddings or not model_cfg.kv_lora_rank:
        raise ValueError("Kanana-2: latent attention, untied head")
    if model_cfg.moe_scoring != "sigmoid":
        raise ValueError("Kanana-2: sigmoid-scored routing")
    return {
        "kv_lora_rank": int(model_cfg.kv_lora_rank),
        "qk_nope_head_dim": int(model_cfg.qk_nope_head_dim),
        "qk_rope_head_dim": int(model_cfg.qk_rope_head_dim),
        "rope_theta": float(model_cfg.rope_theta),
        "rope_interleave": bool(model_cfg.rope_interleave),
        "rms_norm_eps": float(model_cfg.rms_norm_eps),
        "num_experts_per_tok": int(model_cfg.num_experts_per_tok),
        "routed_scaling_factor": float(model_cfg.routed_scaling_factor),
    }


def reference_logits(params, hp: Dict[str, Any], token_ids,
                     positions_out: List[int]) -> Dict[str, Any]:
    """Full causal forward over `token_ids` [S]; float32 logits [n, V] at
    `positions_out`; `router_gap` as the module docstring says (rescaled),
    and `raw_router_gap`, the smallest raw gap over the routed layers."""
    frozen = _freeze(hp)
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(token_ids, jnp.int32)
        x = _embed(params["embed"], ids)
        min_gap = jnp.full((ids.shape[0],), jnp.inf)
        stacks = [(params[name], routed) for name, routed in
                  (("dense_layers", False), ("layers", True))
                  if name in params]
        for stack, routed in stacks:
            for l in range(stack["wq"].shape[0]):
                x, gap = _layer(x, stack, jnp.int32(l), hp=frozen,
                                routed=routed)
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
    compared positions move by more than the tolerance?"""
    return {
        "bias_ignored_in_choice": dict(hp, ignore_bias=True),
        "weights_not_renormalised": dict(hp, skip_renormalise=True),
        "scale_one": dict(hp, skip_scale=True),
        "no_shared_expert": dict(hp, skip_shared=True),
        "rope_not_deinterleaved": dict(hp, skip_deinterleave=True),
        "latent_not_normed": dict(hp, skip_latent_norm=True),
        "softmax_routing": dict(hp, softmax_routing=True),
    }
