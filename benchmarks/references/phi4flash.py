"""Plain float32 reference of the Phi-4-mini-flash-reasoning decoder
(microsoft/Phi-4-mini-flash-reasoning, `model_type` "phi4flash"), written
from its published config.json, the model's own description (arXiv:2507.06607,
"decoder-hybrid-decoder", and `modeling_phi4flash.py`) and ISSUE 38's
equations.  Imports nothing of `kafka_tpu` (a test scans for it);
`benchmarks/tests/test_phi4flash.py` and `tests/test_hybrid_model.py` hold
`kafka_tpu.models.forward` to it at a tiny size in float32.

The decoder, 32 layers of `h += mixer(LN(h)); h += MLP(LN(h))`, LayerNorm
with weight and bias (eps `layer_norm_eps`), a final LayerNorm, a tied head,
NO positional encoding on any layer, MLP `W_d(up * silu(gate))`.  The mixer
of layer i (L = 32, own = L / 2 + 2 = 18):

* i even, i < own: Mamba-1.  `[x | z] = W_in u`; x = silu(causal depthwise
  conv (kernel 4) of x + b_c); `[dr | B | C] = W_x x`; dt = softplus(W_dt dr
  + b_dt); A = -exp(A_log); h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) (x) B_t;
  y_t = h_t C_t + D x_t; out = W_out (y * silu(z)).  The LAST Mamba layer
  (i = own - 2 = 16) also hands y (with its D x term, ahead of the gate) to
  the gated memory units as the memory m.
* i odd, i < own - 1: differential attention over a sliding window (a query
  sees itself and the `sliding_window` - 1 keys before it), own K/V.
* i = own - 1 = 17: differential attention, full causal, own K/V.
* i odd, i >= own: differential CROSS attention: q = W_q u only, keys and
  values are layer 17's, full causal.
* i even, i >= own: gated memory unit, out = W_2 (m_t * silu(W_1 u_t)).

Differential attention: q as [Hq/2, 2, D], k and v as [Hkv/2, 2, D]; query
pair j reads key-value pair g = j // (Hq / Hkv); P_s = softmax(q_{j,s}
k_{g,s}^T / sqrt(D) + mask); V_g = [v_{g,1} | v_{g,2}]; o_j = (1 - l0) *
RMSNorm_2D((P_1 - lam P_2) V_g) (learnt weight, eps 1e-5); lam = exp(lq1 .
lk1) - exp(lq2 . lk2) + l0; l0 = 0.8 - 0.6 exp(-0.3 i), i the ABSOLUTE layer
index; the Hq/2 outputs of 2D are concatenated into W_o.

ASSUMED (the config has no key for them; the configuration file lists the
same): differential attention itself (the paper's abstract and the modeling
file's `FlashDiffCustomAttention`); mamba d_state 16, d_conv 4, expand 2,
dt_rank ceil(hidden / 16), conv and dt biases present, W_in / W_x / W_out
without bias (the configuration class's defaults); biases on W_qkv, W_q and
W_o; the window's edge (`sliding_window` - 1 back); pairs interleaved (head
2j with 2j + 1), a relabelling under random weights; the memory taken WITH
its D x term; l0 from the absolute layer index.  Weights are random (the
check compares programs, not models): the tree is the program's
(`kafka_tpu/models/hybrid.init_params`), read here leaf by leaf; `A_log` and
the state are held [d_state, inner] there and here.

Float32 under `default_matmul_precision("highest")`, no cache, no kernels, no
batching; stacked bf16 weights are upcast one layer at a time.  There is no
router: `router_gap` is +inf at every position (all are compared).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

MAMBA, WINDOWED, GLOBAL = "mamba", "sliding_attention", "full_attention"
GMU, CROSS = "gmu", "cross_attention"

# Relative RMS error allowed per compared position, rms(served - ref) /
# rms(ref) over the vocabulary.  Set from two readings on the v5e at the
# published widths, 32 layers, prefill 1,488 + 48 rows in two launches and 47
# decode steps through pages and state slots, the 48 positions 1535..1582 (my
# chip run 2, PR 38; `benchmarks/check_power.py`, deterministic: fixed tokens,
# PRNGKey(0) weights; run 1 with the launches meeting at row 768 read the
# same band, 0.0351-0.0445): the served bf16 program (Pallas decode and flash
# prefill with the differential pairing, the scan kernel) reads 0.0349-0.0439;
# this reference with the MLP and embedding matrices rounded to int8 (per
# output channel, the nearest precision below bf16; check_power's
# `int8_weights` reaches those leaves of this tree) reads 0.0854-0.1104.  0.06
# is 1.37x the largest served reading and 0.70x the smallest int8 one.  Why
# the served error is three times Mellum2's: differential attention subtracts
# P_1 V and P_2 V AFTER the kernels have rounded each to bfloat16, and the
# sub-layer norm rescales the difference, on 16 layers (PERF.md section 7).
# The variants below (one mechanism out each) read, at the same positions: a
# dropped D x 1.30-1.38, no sub-layer norm 1.21-1.29, the state zeroed where
# the two launches meet 0.0637-0.150 (every position over the tolerance; at
# row 768 it read 0.017-0.029, which is why the second launch is short), a
# state rounded to bfloat16 0.0048-0.0154: UNDER the tolerance and under the
# served reading, so no limit on the logits (nor on the state's values, which
# the served bfloat16 activations move by as much) can lie between the two:
# the driver reads the lane's slot instead and fails the check by name where
# the state is not carried in float32 (`drivers/phi4flash_pool.py`,
# `state_f32_share`); tests/test_hybrid_model.py holds the float32 program to
# this reference at 1e-4, where a bfloat16 state reads 2.8e-3 at the median
# position.
TOLERANCE = {
    "value": 0.06,
    "why": "served bf16 0.0349-0.0439 over 48 positions, int8 MLP+embedding "
           "0.0854-0.1104 (my chip run 2, PR 38; PERF.md 6)",
}

# The check prefills n_prefill = 1536 rows in two launches: all but the last
# TAIL rows, then those (`drivers/phi4flash_pool.py` has the same TAIL).
TAIL = 48
BOUNDARY = 1536 - TAIL


def _f32(x) -> jnp.ndarray:
    return jnp.asarray(x).astype(jnp.float32)


def _at(tree, i):
    return {k: _f32(jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False))
            for k, v in tree.items()}


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _mlp(x, lp, eps):
    u = _layer_norm(x, lp["ln_mlp"], lp["ln_mlp_b"], eps)
    return x + (jax.nn.silu(u @ lp["wg"]) * (u @ lp["wu"])) @ lp["wd"]


def _mamba(u, mp, hp):
    """u [S, H] -> (out [S, H], memory [S, inner])."""
    di = mp["in_proj"].shape[1] // 2
    ds, dc = mp["A_log"].shape[0], mp["conv_w"].shape[0]
    r = mp["dt_w"].shape[0]
    xz = u @ mp["in_proj"]
    x, z = xz[:, :di], xz[:, di:]
    pad = jnp.concatenate([jnp.zeros((dc - 1, di)), x], axis=0)
    # tap dc - 1 multiplies the row's own input, tap 0 the one dc - 1 before
    x = mp["conv_b"] + sum(mp["conv_w"][i] * pad[i:i + x.shape[0]]
                           for i in range(dc))
    x = jax.nn.silu(x)
    dbc = x @ mp["x_proj"]
    dt = jax.nn.softplus(dbc[:, :r] @ mp["dt_w"] + mp["dt_b"])
    b, c = dbc[:, r:r + ds], dbc[:, r + ds:]
    a = -jnp.exp(mp["A_log"])  # [ds, di]
    zero_at = hp.get("zero_state_at")
    state_dtype = hp.get("state_dtype")

    def step(h, row):
        t, x_t, dt_t, b_t, c_t = row
        if zero_at is not None:
            h = jnp.where(t == zero_at, 0.0, h)
        h = jnp.exp(dt_t[None, :] * a) * h + (dt_t * x_t)[None, :] * b_t[:, None]
        if state_dtype == "bfloat16":
            # (reduce_precision, not a cast there and back: the chip's
            # compiler is allowed to keep the excess precision of that)
            h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
        return h, jnp.sum(h * c_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((ds, di)),
                        (jnp.arange(x.shape[0]), x, dt, b, c))
    memory = y if hp.get("drop_dx") else y + mp["D"] * x
    return (memory * jax.nn.silu(z)) @ mp["out_proj"], memory


def _diff_attention(q, k, v, ap, layer, window, hp):
    """q [S, Hq, D], k / v [T = S, Hkv, D] (T == S: no cache) -> [S, H]."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    jq, jk = hq // 2, hkv // 2
    qp = q.reshape(s, jq, 2, d)
    kp = k.reshape(s, jk, 2, d)
    vp = v.reshape(s, jk, 2 * d)  # V_g = [v_g1 | v_g2]
    g_of = np.arange(jq) // (jq // jk)
    rows, cols = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    allowed = cols <= rows
    if window:
        allowed = allowed & (cols > rows - window)
    scores = jnp.einsum("sjtd,kjtd->jtsk", qp, kp[:, g_of]) / np.sqrt(d)
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    l0 = 0.8 - 0.6 * jnp.exp(-0.3 * layer.astype(jnp.float32))
    lam = (jnp.exp(jnp.sum(ap["lq1"] * ap["lk1"]))
           - jnp.exp(jnp.sum(ap["lq2"] * ap["lk2"])) + l0)
    o = jnp.einsum("jsk,kje->sje", probs[:, 0] - lam * probs[:, 1],
                   vp[:, g_of])
    if not hp.get("no_subln"):
        o = (o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-5)
             * ap["subln"])
    o = o * (1.0 - l0)
    return jnp.einsum("sje,jeh->sh", o, ap["wo"]) + ap["bo"]


@partial(jax.jit, static_argnames=("kind", "hp_key"))
def _layer(x, memory, kv, layers, mixer, i, layer, *, kind: str, hp_key):
    """Layer `layer` (absolute), the i-th of its kind's stacked tree;
    `hp_key` the (hashable) items of the numbers it needs."""
    hp = dict(hp_key)
    eps = hp["eps"]
    lp = _at(layers, layer)
    mp = _at(mixer, i)
    u = _layer_norm(x, lp["ln_attn"], lp["ln_attn_b"], eps)
    if kind == MAMBA:
        out, memory = _mamba(u, mp, hp)
    elif kind == GMU:
        out = (memory * jax.nn.silu(u @ mp["w1"])) @ mp["w2"]
    else:
        q = jnp.einsum("sh,hnd->snd", u, mp["wq"]) + mp["bq"]
        if kind != CROSS:
            kv = (jnp.einsum("sh,hnd->snd", u, mp["wk"]) + mp["bk"],
                  jnp.einsum("sh,hnd->snd", u, mp["wv"]) + mp["bv"])
        out = _diff_attention(
            q, kv[0], kv[1], mp, layer,
            hp["window"] if kind == WINDOWED else 0, hp)
    return _mlp(x + out, lp, eps), memory, kv


@partial(jax.jit, static_argnames=("eps",))
def _head(x, w, b, table, positions_out, *, eps: float):
    return _layer_norm(x, _f32(w), _f32(b), eps)[positions_out] @ _f32(table).T


def hyper(model_cfg) -> Dict[str, Any]:
    """The numbers the reference needs, read by attribute name off the
    served model's config (any object with these attributes)."""
    kinds = list(model_cfg.layer_types)
    if MAMBA not in kinds or not model_cfg.tie_word_embeddings:
        raise ValueError("phi4flash: Mamba layers and a tied head")
    return {"layer_types": kinds,
            "window": int(model_cfg.sliding_window),
            "eps": float(model_cfg.rms_norm_eps)}


# which stacked tree holds each kind's mixers
MIXER_OF = {MAMBA: "mamba", WINDOWED: "attn", GLOBAL: "attn", GMU: "gmu",
            CROSS: "cross"}


def reference_logits(params, hp: Dict[str, Any], token_ids,
                     positions_out: List[int]) -> Dict[str, Any]:
    """Full causal forward over `token_ids` [S]; float32 logits [n, V] at
    `positions_out`."""
    hp_key = tuple(sorted((k, v) for k, v in hp.items()
                          if k != "layer_types"))
    seen: Dict[str, int] = {}
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(token_ids, jnp.int32)
        x = _f32(params["embed"][ids])
        # (placeholders of the final shapes: one trace a kind of layer)
        memory = jnp.zeros((ids.shape[0], params["gmu"]["w2"].shape[1]))
        kv = (jnp.zeros((ids.shape[0],) + params["attn"]["wk"].shape[2:]),) * 2
        for layer, kind in enumerate(hp["layer_types"]):
            tree = MIXER_OF[kind]
            i = seen.get(tree, 0)
            seen[tree] = i + 1
            x, memory, kv = _layer(
                x, memory, kv, params["layers"], params[tree], jnp.int32(i),
                jnp.int32(layer), kind=kind, hp_key=hp_key)
        logits = _head(x, params["final_norm"], params["final_norm_b"],
                       params["embed"],
                       jnp.asarray(positions_out, jnp.int32), eps=hp["eps"])
    n = len(positions_out)
    return {"logits": np.asarray(logits),
            "router_gap": np.full((n,), np.inf)}


def variants(hp: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """The reference with one mechanism taken out, for the check's POWER
    (`benchmarks/check_power.py`): `bf16_state` rounds the recurrent state to
    bfloat16 at every step; `drop_dx` leaves D x out of the scan's output
    (and so of the memory); `no_subln` skips differential attention's
    sub-layer norm; `zero_state_at_boundary` zeroes every Mamba layer's state
    ahead of row BOUNDARY, where the check's two prefill launches meet (the
    driver's second launch holds the prompt's last TAIL rows: the state it is
    handed is then 48-95 rows old at the compared positions, and not the
    768+ rows over which most of a state has decayed)."""
    return {
        "bf16_state": dict(hp, state_dtype="bfloat16"),
        "drop_dx": dict(hp, drop_dx=True),
        "no_subln": dict(hp, no_subln=True),
        "zero_state_at_boundary": dict(hp, zero_state_at=BOUNDARY),
    }
