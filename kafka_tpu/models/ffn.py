"""The feed-forward half of a layer: the MLP, and the routed block
(Mixtral's softmax-over-top-k rule and `deepseek_v3`'s sigmoid rule with a
selection bias and shared experts) in its two forms of the same arithmetic,
dense and by token, chosen by `moe_dispatch_form`.  The activation is a row of
`ACTIVATIONS`: gated SiLU (SwiGLU: gate, up and down matrices) or
`nemotron_h`'s ungated squared ReLU (up and down alone).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .quant import Params, QTensor, _w


def _relu2(u: jnp.ndarray) -> jnp.ndarray:
    r = jax.nn.relu(u)
    return r * r


# `cfg.mlp_act` -> (gated, what stands between the up and the down product):
# a gated block holds a gate matrix and multiplies act(gate(x)) * up(x), an
# ungated one holds none and applies act to up(x) itself.
# An ungated EXPERT's up matrix is stored as published, out x in: "wu" [E, f,
# H] beside "wd" [E, f, H].  Nemotron-H's f = 1,856 is no whole number of
# 128-lane tiles, and the chip then lays an [H, f] leaf out with H in the
# lanes whatever order the program names: handed to the grouped matmul, which
# wants the named order, the whole 4.3 GB stack was copied ahead of every
# product (compiled for the v5e, PR 60).  Stored [f, H] the lanes hold H
# either way and the kernel contracts the minor axis (`transposed`).
ACTIVATIONS = {
    "silu": (True, lambda g, u: jax.nn.silu(g) * u),
    "relu2": (False, lambda _, u: _relu2(u)),
}


def _mlp_block(x: jnp.ndarray, lp: Params,
               names=("wg", "wu", "wd"),
               multipliers: Tuple[float, ...] = (),
               act: str = "silu") -> jnp.ndarray:
    """The MLP: SwiGLU, down( silu(gate(x)) * up(x) ), or with an ungated
    `act` down( act(up(x)) ) (`names[0]` is then read by nobody).
    `multipliers` (gate, down), a muP model's: the gate's pre-activation and
    the block's output are scaled, in the activations' dtype."""
    gated, mid = ACTIVATIONS[act]
    g = (jnp.einsum("bsh,hf->bsf", x, _w(lp, names[0], x.dtype)) if gated
         else None)
    u = jnp.einsum("bsh,hf->bsf", x, _w(lp, names[1], x.dtype))
    if not multipliers:
        return jnp.einsum(
            "bsf,fh->bsh", mid(g, u), _w(lp, names[2], x.dtype))
    gate_m, down_m = (jnp.asarray(m, x.dtype) for m in multipliers)
    return jnp.einsum(
        "bsf,fh->bsh", mid(g * gate_m, u),
        _w(lp, names[2], x.dtype)) * down_m


def _routing_weights(t: jnp.ndarray, router: jnp.ndarray,
                     top_k: int, picks: bool = False,
                     choice: Optional[jnp.ndarray] = None):
    """Per-token expert weights [T, E]: softmax over EXACTLY the top-k
    router logits, scattered back (HF MixtralSparseMoeBlock semantics —
    a >=threshold mask would activate extra experts on k-th-place ties).
    The canonical routing implementation; parallel/expert.py reuses it.
    `picks`: the same choice unscattered, (experts [T, k] i32, weights
    [T, k] f32), for token dispatch (`_experts_token`).
    `choice` [E] float32, a leaf no published tree holds ("router_choice":
    None, and not an op traced): added to the logits for the CHOICE alone,
    as the sigmoid rule's selection bias is; the softmax is over the chosen
    experts' own logits.  A check hands it over to name a row's experts
    (benchmarks/drivers/granitemoehybrid_pool.py).
    """
    logits = jnp.einsum(
        "th,he->te", t, router, preferred_element_type=jnp.float32
    )
    if choice is None:
        top_vals, top_idx = jax.lax.top_k(logits, top_k)
    else:
        _, top_idx = jax.lax.top_k(
            logits + choice.astype(jnp.float32), top_k)
        top_vals = jnp.take_along_axis(logits, top_idx, axis=-1)
    w_top = jax.nn.softmax(top_vals, axis=-1)
    if picks:
        return top_idx, w_top
    return jnp.zeros_like(logits).at[
        jnp.arange(t.shape[0])[:, None], top_idx
    ].set(w_top)


def _routing_weights_sigmoid(t: jnp.ndarray, router: jnp.ndarray,
                             bias: jnp.ndarray, top_k: int,
                             scale: float, picks: bool = False):
    """Per-token expert weights [T, E] of HF deepseek_v3's `noaux_tc` rule
    with one group: sigma = sigmoid(logits) in f32; the top_k experts by
    sigma + bias are CHOSEN (the bias chooses, it does not weigh; ties go to
    the lower index, as lax.top_k); a chosen expert weighs
    scale * sigma_e / (sum of the chosen sigma + 1e-20).  `picks` as in
    `_routing_weights`."""
    logits = jnp.einsum(
        "th,he->te", t, router, preferred_element_type=jnp.float32
    )
    sigma = jax.nn.sigmoid(logits)
    _, top_idx = jax.lax.top_k(sigma + bias.astype(jnp.float32), top_k)
    rows = jnp.arange(t.shape[0])[:, None]
    chosen = sigma[rows, top_idx]
    w_top = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    if picks:
        return top_idx, w_top
    return jnp.zeros_like(sigma).at[rows, top_idx].set(w_top)


# Rows of one pass (lanes x bucket) from which the routed block dispatches by
# token.  Dense dispatch does 2 T FLOPs a weight element (2 bytes), so on a
# v5e (197 TFLOP/s, 819 GB/s) it is weight-read-bound below T ~ 240 and
# compute-bound above.  Measured, the block alone on the chip, us a layer,
# dense | token (scripts/moe_dispatch_bench.py; PERF.md section 6, PR 45):
#   rows  Mixtral      Mellum2      Kanana-2     K-EXAONE     dots3
#   256   4348 | 4986  1267 | 1367  1969 | 1870  1921 | 1968  2362 | 2361
#   320   5001 | 5570  1586 | 1465  2360 | 1930  2295 | 2084  2808 | 2361
#   384   6137 | 5587  1810 | 1548  2817 | 1978  2755 | 2294  3416 | 2448
#   512   8370 | 6188  2435 | 1723  3689 | 2118  3820 | 2424  4613 | 2626
# 384 is the first row count at which the token form is the faster one at
# every routed configuration (a sort, two row gathers and a visit a (row
# tile, expert) are what it pays below).
TOKEN_DISPATCH_MIN_ROWS = 384


# Below TOKEN_DISPATCH_MIN_ROWS the weights' read bounds the block, and token
# dispatch visits only the experts that have rows: the form reads fewer bytes
# where the pass leaves a share of the held experts unpicked.  That share is
# expected to be (1 - top_k / routed) ** rows under even routing (random
# weights route evenly; a trained router is more skewed and reads fewer).
# Measured, the block alone on the chip, every row active, us a layer,
# dense | token, the experts read of those held and the expected unread share
# (scripts/moe_dispatch_bench.py --rows 16 32 64; PERF.md section 6, PR 48):
#   rows  Mixtral              Mellum2                LFM2
#   16    3743 | 3772  8/8  .010   1095 |  928 55/64 .118    943 | 787 26/32 .118
#   32    3746 | 3776  8/8  .000   1102 | 1056 62/64 .014    948 | 965 32/32 .014
#   64    3796 | 3793  8/8  .000   1095 | 1151 64/64 .000    957 | 979 32/32 .000
#   rows  Kanana-2             K-EXAONE               dots3
#   16    1613 |  871  67/128 .464  1665 | 1463 14/16 .356   2018 |  931 14/32 .602
#   32    1620 | 1273  97/128 .215  1686 | 1288 12/16 .127   2030 | 1210 18/32 .362
#   64    1637 | 1569 119/128 .046  1745 | 1711 16/16 .016   2053 | 1873 28/32 .131
# From an expected share of 0.046 up the token form is the faster one at every
# configuration and row count measured (by 4 % at the least); at 0.016 and
# under it is within 5 % of dense on either side (its sort and two row
# gathers, with little or nothing left unread to pay for them).  (A kernel
# that walked the picked experts with dense dispatch's arithmetic, no sort
# and no gathers, read 0-4 % faster than the token form in the same table
# and was not kept.)
TOKEN_DISPATCH_MIN_UNREAD = 0.04
# ... and the fewest rows that table timed, one sublane tile of bf16: a pass
# of fewer rows (a single stream's decode, the benchmark's one-lane logit
# check) keeps the dense einsums.
TOKEN_DISPATCH_UNREAD_ROWS = 16


def moe_dispatch_form(rows: int, held: int, top_k: int, sharded: bool,
                      routed: Optional[int] = None,
                      int8: bool = False) -> str:
    """"token" or "dense": the form of the routed block for a pass of `rows`
    rows (static) over `held` experts, of the `routed` the router knows
    (None: all held), of which a row picks `top_k`; `int8`: the experts'
    leaves are quantized.  Token dispatch where dense dispatch is
    compute-bound and computes products it then zeroes
    (TOKEN_DISPATCH_MIN_ROWS), and below that, where the weights' read
    bounds both, where few rows over many experts are expected to leave a
    share of them unpicked (TOKEN_DISPATCH_MIN_UNREAD: decode at 16-32
    lanes over 64 experts or more, a 64-row launch over 128 or more): token
    dispatch fetches no expert without rows.  Dense between the two (nearly
    every expert is somebody's pick: no sort, no gather), where every held
    expert takes every row anyway, at decode over int8 experts (dequantized
    whole, a layer) and on an ep / tp mesh (GSPMD partitions the dense
    einsums; a sharded grouped matmul is ROADMAP R4's).  The one rule:
    `_moe_block` traces by it and the engine counts launches by it."""
    if sharded or held <= top_k:
        return "dense"
    if rows >= TOKEN_DISPATCH_MIN_ROWS:
        return "token"
    if (not int8 and rows >= TOKEN_DISPATCH_UNREAD_ROWS
            and (1.0 - top_k / (routed or held)) ** rows
            >= TOKEN_DISPATCH_MIN_UNREAD):
        return "token"
    return "dense"


# XLA's row gather on the v5e (jaxlib 0.9.0) keeps an operand of up to ~7.3 MB
# in VMEM and then asks for twice the operand + ~3 MiB of scoped VMEM, of which
# a fusion has 16 MiB: with an operand between ~6.9 and ~7.3 MB the program
# does not compile ("Ran out of memory in memory space vmem ... please file a
# bug against XLA": Mellum2's 1,536 rows x 2,304 bf16, the logit check's
# launch; 1,504 and 1,600 rows compile).  `_experts_token` pads an operand of
# (6, 7.5] MiB past the window, where the gather reads it from HBM as it does
# every larger one; tests/test_exaone_moe.py compiles the case for a
# described v5e.
GATHER_VMEM_WINDOW = (6 << 20, 15 << 19)

# the routed experts' leaves: what token dispatch reads from the layer stack
# (an ungated expert's tree holds no "wg": `expert_leaves`)
EXPERT_LEAVES = ("wg", "wu", "wd")


def expert_leaves(tree: Params) -> Tuple[str, ...]:
    """The EXPERT_LEAVES a layer tree (stacked, or one layer's) holds."""
    return tuple(name for name in EXPERT_LEAVES if name in tree)


def experts_int8(layers: Params) -> bool:
    """Whether the routed experts' leaves of a layer tree (stacked, or one
    layer's) are int8 `QTensor`s."""
    return any(isinstance(layers.get(name), QTensor)
               for name in EXPERT_LEAVES)


def _experts_token(t: jnp.ndarray, top_idx: jnp.ndarray, w_top: jnp.ndarray,
                   stack: Params, layer, routed: int, offset: int,
                   real: Optional[jnp.ndarray] = None, act: str = "silu"):
    """The routed experts by token: the T x k (row, expert, weight) picks
    sorted by expert, the rows gathered into that order, each projection ONE
    grouped matmul whose groups are the held experts (operands in t's dtype,
    f32 accumulation, as the dense einsums; three products an expert, two
    where `act` is ungated and `stack` holds no "wg"), and each row's k
    results weighted and summed in f32.  t [T, H]; `stack` the expert leaves
    stacked over layers [L, E, ...], of which this is `layer`; top_idx [T, k]
    counts over ALL the router's `routed` experts, of which this chip holds
    offset.. ; `real` [T] bool marks the rows that hold a token.  A pick of
    an expert held elsewhere, or of a pad row, sorts past every group: no
    matmul rows, zero weight.  No capacity, nothing dropped.  -> (out [T, H],
    the held experts that have rows, i32: the ones whose weights the grouped
    matmuls read)."""
    from ..ops.pallas.grouped_matmul import grouped_matmul, tile_rows

    n, k = top_idx.shape
    gated, mid = ACTIVATIONS[act]
    held = stack["wu"].shape[1]
    e = top_idx - offset
    mine = (e >= 0) & (e < held)
    if real is not None:
        mine = mine & real[:, None]
    e = jnp.where(mine, e, held).reshape(-1)
    order = jnp.argsort(e, stable=True)
    sizes = jnp.sum(e[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    # whole row tiles: the rows added sort past every group too
    tile = tile_rows(n * k, routed)
    src, row_bytes = t, t.shape[1] * t.dtype.itemsize
    low, high = GATHER_VMEM_WINDOW
    if low < n * row_bytes <= high:
        src = jnp.pad(t, ((0, high // row_bytes + 1 - n), (0, 0)))
    xs = src[jnp.pad(order // k, (0, -(n * k) % tile))]
    g = (grouped_matmul(xs, stack["wg"], sizes, layer, tile) if gated
         else None)
    u = grouped_matmul(xs, stack["wu"], sizes, layer, tile,
                       transposed=not gated)
    y = grouped_matmul(mid(g, u), stack["wd"], sizes, layer, tile)
    # each pick's result from where the sort put it (a pick that is not
    # `mine` finds a row no group wrote: whatever the buffer held)
    y = y[jnp.argsort(order)].reshape(n, k, -1)
    y = jnp.where(mine[:, :, None], y.astype(jnp.float32), 0.0)
    out = jnp.sum(y * w_top[:, :, None], axis=1).astype(t.dtype)
    return out, jnp.sum(sizes > 0, dtype=jnp.int32)


def _moe_block(x: jnp.ndarray, lp: Params, cfg: ModelConfig,
               chunk_len: Optional[jnp.ndarray] = None,
               sharded: bool = False,
               stacked: Optional[Tuple[Params, Any]] = None,
               count_picks: bool = False):
    """Top-k routed MoE MLP. x: [B, S, H] -> (output [B, S, H], the held
    experts whose weights the block read: an i32 the token form counts, all
    of them, a Python int, in the dense form).  `count_picks`: the second
    value is i32 [2] instead, (experts read, the real rows' picks that fell
    on an expert HELD here), in either form; the second is counted only
    where the config holds a SHARE of the experts (else 0 and not an op more
    a layer: every pick is held, and `forward` knows how many a pass makes):
    what `kafka_tpu_engine_moe_picks_total` counts.

    Routing: softmax over the top-k router logits only (HF
    MixtralSparseMoeBlock semantics), computed in f32; `cfg.moe_scoring`
    "sigmoid" picks deepseek_v3's rule instead.  The experts then run in one
    of two forms of the same arithmetic, chosen by `moe_dispatch_form` (which
    says where each pays) from the pass's static row count B x S:

    dense: every expert computes every row (parallel/expert.py's
    capacity-unlimited formulation, validated there against a per-token
    loop), the [T, E] routing weights zero the non-selected contributions,
    and the combine einsum contracts the expert axis.  With wg/wu/wd sharded
    P(layer, "ep", ..., "tp") GSPMD partitions the expert einsums over ep and
    inserts the combine psum, so the same program serves single-device, ep,
    and ep x tp meshes.

    token: `_experts_token`, each row through its own k experts only.
    `chunk_len` [B] or scalar (the view's: a prefill's real rows, a decode
    step's active lanes): rows at or past it are padding, fall in no group,
    pick nothing and get a zero routed output in the token form (nothing
    reads their feed-forward output; None: every row is real).
    `stacked`: (the EXPERT_LEAVES as the layer stack holds them, this
    layer's index), which `forward` hands over in place of `lp`'s slices of
    them so that the grouped matmul reads the weights where they lie (None:
    `lp` holds the layer's own).

    A shared branch (`cfg.shared_intermediate_size`: one always-on SwiGLU
    beside the routed experts) runs under its own scope, `moe_shared`.
    A config that HOLDS a share of the experts (`cfg.num_experts_routed`: one
    chip of an expert-parallel layer) routes over all the router knows and
    computes the part of the result its own experts give; what the absent
    ones would add is left out (the other chips' part of the combine).
    """
    b, s, h = x.shape
    t = x.reshape(b * s, h)
    token = moe_dispatch_form(
        b * s, cfg.num_experts, cfg.num_experts_per_tok, sharded,
        cfg.num_router_experts, experts_int8(lp)) == "token"
    with jax.named_scope("moe_router"):
        if cfg.moe_scoring == "sigmoid":
            w = _routing_weights_sigmoid(
                t, lp["router"], lp["router_bias"], cfg.num_experts_per_tok,
                cfg.routed_scaling_factor, token)
        else:
            w = _routing_weights(
                t, lp["router"], cfg.num_experts_per_tok, token,
                lp.get("router_choice"))
        if cfg.num_experts_routed and not token:
            # the weights of the experts HELD: chosen and renormalised over
            # all the router's experts, then this share's columns
            w = w[:, cfg.expert_offset:cfg.expert_offset + cfg.num_experts]
    read = cfg.num_experts
    with jax.named_scope("moe_experts"):
        real = None
        share = count_picks and bool(cfg.num_experts_routed)
        if chunk_len is not None and (token or share):
            real = (jnp.arange(s)[None, :]
                    < jnp.reshape(chunk_len, (-1, 1))).reshape(b * s)
        if token:
            stack, at = stacked or (
                {name: _w(lp, name, t.dtype)[None]
                 for name in expert_leaves(lp)}, 0)
            offset = cfg.expert_offset if cfg.num_experts_routed else 0
            out, read = _experts_token(
                t, *w, stack, at, cfg.num_router_experts, offset, real,
                cfg.mlp_act)
            if share:
                mine = (w[0] >= offset) & (w[0] < offset + cfg.num_experts)
                if real is not None:
                    mine = mine & real[:, None]
                held_picks = jnp.sum(mine, dtype=jnp.int32)
        else:
            gated, mid = ACTIVATIONS[cfg.mlp_act]
            g = (jnp.einsum("th,ehf->tef", t, _w(lp, "wg", t.dtype))
                 if gated else None)
            u = jnp.einsum("th,ehf->tef" if gated else "th,efh->tef", t,
                           _w(lp, "wu", t.dtype))
            y = jnp.einsum(
                "tef,efh->teh", mid(g, u), _w(lp, "wd", t.dtype))
            out = jnp.einsum("te,teh->th", w.astype(y.dtype), y)
            if share:
                # (a chosen expert's weight is a softmax's or a sigmoid's
                # share: never exactly 0)
                mine = w != 0 if real is None else (w != 0) & real[:, None]
                held_picks = jnp.sum(mine, dtype=jnp.int32)
        if count_picks:
            read = jnp.stack([jnp.asarray(read, jnp.int32),
                              held_picks if share else jnp.int32(0)])
    out = out.reshape(b, s, h)
    if cfg.shared_intermediate_size:
        with jax.named_scope("moe_shared"):
            out = out + _mlp_block(x, lp, ("ws_g", "ws_u", "ws_d"),
                                   act=cfg.mlp_act)
    return out, read
