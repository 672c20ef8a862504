"""The gated delta rule with a decay per key channel (Kimi Delta Attention,
arXiv:2510.26692; `solar_open2`'s linear-attention layers), the state updated
IN PLACE in its slot.

Per head, with k, q in R^dk, v in R^dv, alpha_t = exp(g_t) in (0, 1]^dk and
beta_t in [0, 2):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

The slot holds S TRANSPOSED, [dv, dk] float32 a head (the key channels in the
lanes, so the decay is a row broadcast), the heads stacked along the rows: a
state leaf is [layers, n_slots, H * dv, dk].

Three forms of the one recurrence, behind `gated_delta`:

* `gated_delta_chunk` (prefill, the Pallas backend): the chunked form.  With
  G the cumulative log-decay inside a chunk of C rows and u_t the row's
  pseudo-value (S_t = Diag(alpha_t) S_(t-1) + k_t u_t^T),

      (I + A) U = beta (V - (K exp G) S_0),  A[t, s] = beta_t sum_c k_tc k_sc
                                             exp(G_tc - G_sc) for s < t
      O = (Q exp G) S_0 + P U,               P the same with q_t, s <= t
      S_C = Diag(exp G_C) S_0 + (K exp(G_C - G))^T U

  The decay is per channel, so exp(G_t - G_s) does not factor into a row and
  a column term that both stay finite (dividing k by the cumulative decay
  overflows).  A and P are therefore built from exponents that are
  DIFFERENCES and never positive: inside a sub-block of 16 rows lag by lag
  (row t against row t - d, exactly), between sub-blocks against the later
  sub-block's first row (G_t - G_r and G_r - G_s, both <= 0), which is a
  matmul.  (I + A) is unit lower triangular; its inverse is built by
  doubling, [[T1, 0], [-T2 A21 T1, T2]], ten 64 x 64 matmuls, with no power
  of A taken (a Neumann series loses float32 to cancellation once beta
  passes 1 on repeated keys).  The state stays in VMEM across a lane's
  chunks; it comes from the lane's `src` slot by one DMA and goes to `dst`
  and `snap` by two, the leaf aliased in and out: HBM sees 3 x 64 KB a head
  a lane and no copy of the leaf.
* `gated_delta_step` (decode, the Pallas backend): the closed form of one
  row, the slot's block read, updated and written back through the aliased
  leaf.
* `_scan_xla`: a `lax.scan` over the rows (the XLA backend, the CPU, and
  shapes the kernels do not tile): what the kernels are tested against.

Rows past a lane's `lens` are the identity (g = 0, beta = 0), so the state
after a padded chunk is the state after its last real row; a lane with no
real row leaves its slot untouched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .state_slot import chunk_slots, kernel_form, load_state, store_state

CHUNK = 64        # rows of one triangular system
SUB = 16          # rows of a sub-block: one reference point for the decay
HEADS_A_STEP = 4  # heads a grid step holds (independent chains to overlap)
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def chunk_rows(S: int):
    """Rows a triangular system takes of a launch of S rows, or None where
    the chunk kernel does not tile it (the XLA scan runs then)."""
    if S > 1 and S % CHUNK == 0:
        return CHUNK
    return S if S in (SUB, 2 * SUB) else None


def _heads_a_step(H: int) -> int:
    return HEADS_A_STEP if H % HEADS_A_STEP == 0 else 1


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=_F32)


def _chunk_head(q, k, kb, vb, G, S):
    """One chunk of one head.  q, k, kb (= beta k), G [C, dk]; vb (= beta v)
    [C, dv]; S [dv, dk] -> (o [C, dv], S after the chunk)."""
    C = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    zero = jnp.zeros((C, C), _F32)
    # inside a sub-block, lag by lag: row t against row t - d, exactly
    P = jnp.where(row == col, jnp.sum(q * k, axis=1, keepdims=True), zero)
    A = zero
    for d in range(1, min(SUB, C)):
        e = jnp.exp(jnp.minimum(G - pltpu.roll(G, d, 0), 0.0)) \
            * pltpu.roll(k, d, 0)
        m = (row - col == d) & ((row & (SUB - 1)) >= d)
        A = jnp.where(m, jnp.sum(kb * e, axis=1, keepdims=True), A)
        P = jnp.where(m, jnp.sum(q * e, axis=1, keepdims=True), P)
    # between sub-blocks, against the later one's first row
    for i in range(1, C // SUB):
        ref = G[i * SUB:i * SUB + 1, :]
        down = jnp.exp(jnp.minimum(G - ref, 0.0))
        up = jnp.exp(jnp.minimum(ref - G, 0.0)) * k
        m = (row // SUB == i) & (col < i * SUB)
        A = jnp.where(m, _dot(kb * down, up, ((1,), (1,))), A)
        P = jnp.where(m, _dot(q * down, up, ((1,), (1,))), P)
    # T = (I + A)^-1 by doubling: pairs of inverted blocks of w rows
    T = jnp.where(row == col, 1.0, zero) - jnp.where(
        (row // 2 == col // 2) & (row != col), A, zero)
    w = 2
    while w < C:
        m = (row // (2 * w) == col // (2 * w)) & (row // w != col // w)
        T = T - _dot(_dot(T, jnp.where(m, A, zero), ((1,), (0,))), T,
                     ((1,), (0,)))
        w *= 2
    e0 = jnp.exp(G)
    U = _dot(T, vb - _dot(kb * e0, S, ((1,), (1,))), ((1,), (0,)))
    o = _dot(q * e0, S, ((1,), (1,))) + _dot(P, U, ((1,), (0,)))
    last = G[C - 1:C, :]
    S = S * jnp.exp(last) + _dot(U, k * jnp.exp(last - G), ((0,), (0,)))
    return o, S


def _chunk_kernel(layer_ref, src_ref, dst_ref, snap_ref, flag_ref,
                  q_ref, k_ref, kb_ref, vb_ref, g_ref, leaf_in,
                  o_ref, leaf_out, s_scr, sem, *, hb, dk, dv):
    """One chunk of `hb` heads of one lane; the chunk axis is sequential and
    the heads' state stays in `s_scr` over it.  flag: 1 = the lane has real
    rows, 2 = it starts from zeros."""
    b, hg, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    layer = layer_ref[0]
    flag = flag_ref[b]
    active = (flag & 1) == 1
    rows = pl.ds(pl.multiple_of(hg * (hb * dv), hb * dv), hb * dv)

    @pl.when(active & (c == 0))
    def _():
        load_state(leaf_in, layer, src_ref, b, rows, s_scr, sem, flag)

    @pl.when(active)
    def _():
        for j in range(hb):
            ks = slice(j * dk, (j + 1) * dk)
            vs = slice(j * dv, (j + 1) * dv)
            o, S = _chunk_head(q_ref[0, :, ks], k_ref[0, :, ks],
                               kb_ref[0, :, ks], vb_ref[0, :, vs],
                               g_ref[0, :, ks], s_scr[vs, :])
            o_ref[0, :, vs] = o
            s_scr[vs, :] = S

    @pl.when(jnp.logical_not(active))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(active & (c == pl.num_programs(2) - 1))
    def _():
        store_state(leaf_out, layer, (dst_ref, snap_ref), b, rows, s_scr,
                    sem)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gated_delta_chunk(leaf, layer, src, dst, snap, flag, q, k, kb, vb, g, *,
                      chunk: int = CHUNK, interpret: bool = False):
    """leaf [L, n_slots, H * dv, dk] f32; layer [] int32; src / dst / snap /
    flag [B] int32; q, k, kb, g [B, S, H * dk] f32 (g the log-decay, 0 on
    padded rows; kb = beta k); vb [B, S, H * dv] f32 -> (o [B, S, H * dv]
    f32, leaf with each active lane's state after its rows in `dst` and
    `snap`).  `leaf` is aliased to the result: donate it."""
    B, S, width = q.shape
    dk = leaf.shape[3]
    H = width // dk
    dv = leaf.shape[2] // H
    hb = _heads_a_step(H)
    n = S // chunk
    # the cumulative log-decay INSIDE each chunk, row t's own g included
    G = jnp.cumsum(g.reshape(B, n, chunk, width), axis=2).reshape(B, S, width)

    def rows(d):
        return pl.BlockSpec((1, chunk, hb * d), lambda b, h, c, *_: (b, c, h))

    kernel = functools.partial(_chunk_kernel, hb=hb, dk=dk, dv=dv)
    o, leaf = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, H // hb, n),
            in_specs=[rows(dk), rows(dk), rows(dk), rows(dv), rows(dk),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[rows(dv), pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((hb * dv, dk), _F32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, S, H * dv), _F32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        # operands count the scalar-prefetch arguments: the leaf is the 11th
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="gated_delta_chunk",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), src, dst, snap, flag,
      q, k, kb, vb, G, leaf)
    return o, leaf


def _step_kernel(layer_ref, slot_ref, q_ref, k_ref, kb_ref, vb_ref, g_ref,
                 s_ref, o_ref, s_out_ref, *, hb, dk, dv):
    """One row of `hb` heads of one lane: the slot's block in, the updated
    block out (the same bytes of the aliased leaf)."""
    del layer_ref, slot_ref
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dv, dv), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dv, dv), 1)).astype(_F32)
    for j in range(hb):
        ks = slice(j * dk, (j + 1) * dk)
        vs = slice(j * dv, (j + 1) * dv)
        S = s_ref[0, 0, vs, :] * jnp.exp(g_ref[0, :, ks])
        # a row of dv lanes as a column of dv sublanes, and back: through
        # the identity (Mosaic does not transpose a single row)
        if dk == dv:  # one reduction for both terms
            u = jnp.sum(eye * vb_ref[0, :, vs] - S * kb_ref[0, :, ks],
                        axis=1, keepdims=True)
        else:
            u = (jnp.sum(eye * vb_ref[0, :, vs], axis=1, keepdims=True)
                 - jnp.sum(S * kb_ref[0, :, ks], axis=1, keepdims=True))
        S = S + u * k_ref[0, :, ks]
        o = jnp.sum(S * q_ref[0, :, ks], axis=1, keepdims=True)
        o_ref[0, :, vs] = jnp.sum(eye * o, axis=0, keepdims=True)
        s_out_ref[0, 0, vs, :] = S


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_step(leaf, layer, slots, q, k, kb, vb, g, *,
                     interpret: bool = False):
    """Decode's one row a lane.  leaf [L, n_slots, H * dv, dk] f32; layer []
    int32; slots [B] int32 (lane i's slot); q, k, kb, g [B, H * dk] f32; vb
    [B, H * dv] f32 (a lane that is not decoding: g = 0, kb = vb = 0, its
    block is written back as read) -> (o [B, H * dv], leaf)."""
    B, width = q.shape
    dk = leaf.shape[3]
    H = width // dk
    dv = leaf.shape[2] // H
    hb = _heads_a_step(H)

    def row(d):
        return pl.BlockSpec((1, 1, hb * d), lambda b, h, *_: (b, 0, h))

    state = pl.BlockSpec((1, 1, hb * dv, dk),
                         lambda b, h, layer, slot: (layer[0], slot[b], h, 0))
    kernel = functools.partial(_step_kernel, hb=hb, dk=dk, dv=dv)
    o, leaf = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb),
            in_specs=[row(dk), row(dk), row(dk), row(dv), row(dk), state],
            out_specs=[row(dv), state],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, 1, H * dv), _F32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gated_delta_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots,
      *(a[:, None, :] for a in (q, k, kb, vb, g)), leaf)
    return o[:, 0], leaf


def _scan_xla(q, k, v, g, beta, S0):
    """The recurrence row by row.  q, k, g [B, S, H, dk]; v [B, S, H, dv];
    beta [B, S, H]; S0 [B, H, dv, dk] -> (o [B, S, H, dv], S after the last
    row)."""
    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = S * jnp.exp(g_t)[:, :, None, :]
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhvd,bhd->bhv", S, k_t, precision=_HI))
        S = S + u[..., None] * k_t[:, :, None, :]
        return S, jnp.einsum("bhvd,bhd->bhv", S, q_t, precision=_HI)

    S, o = jax.lax.scan(step, S0, tuple(
        jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), S


def form(kernel: bool, cached: bool, S: int, own_slots: bool, dk: int,
         dv: int) -> str:
    """Which form a pass of S rows a lane takes, "kernel" or "xla"
    (state_slot.kernel_form: `gated_delta`'s own rule)."""
    return kernel_form(kernel, cached, S, own_slots, chunk_rows(S),
                       not (dk % 128 or dv % 128))


def gated_delta(leaf, layer, plan, q, k, v, g, beta, *, kernel: bool,
                read_state, write_state):
    """The layer's recurrence over a pass, from each lane's state and back
    into its slot.  q, k, g [B, S, H, dk], v [B, S, H, dv], beta [B, S, H],
    all float32; `leaf` the stacked state [state layers, n_slots, H * dv, dk]
    float32 (None: uncached, from zeros) and `layer` this layer's place in
    it; `plan` the pass's StatePlan (models/hybrid.py; `read_state` /
    `write_state` its slot read and write, which the XLA form goes through).
    Rows past `plan.lens` are the identity.  `kernel`: the Pallas kernels
    where they tile the pass.  Returns (o [B, S, H, dv] float32, leaf')."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    real = (jnp.arange(S)[None, :] < plan.lens[:, None])[..., None]
    g = jnp.where(real[..., None], g, 0.0)
    beta = jnp.where(real, beta, 0.0)
    tiles = chunk_rows(S) if S > 1 else 1
    on_chip = jax.default_backend() == "tpu"
    if form(kernel, leaf is not None, S, plan.src is not None, dk,
            dv) == "xla":
        S0 = (jnp.zeros((B, H, dv, dk), _F32) if leaf is None
              else read_state(leaf, layer, plan, B).astype(_F32).reshape(
                  B, H, dv, dk))
        o, S1 = _scan_xla(q, k, v, g, beta, S0)
        if leaf is not None:
            leaf = write_state(leaf, layer, plan,
                               S1.reshape(B, H * dv, dk),
                               S0.reshape(B, H * dv, dk))
        return o, leaf
    flat = (B, S, H * dk)
    kb = (beta[..., None] * k).reshape(flat)
    vb = (beta[..., None] * v).reshape(B, S, H * dv)
    q, k, g = q.reshape(flat), k.reshape(flat), g.reshape(flat)
    layer = jnp.asarray(layer, jnp.int32)
    if S == 1:
        o, leaf = gated_delta_step(
            leaf, layer, jnp.arange(B, dtype=jnp.int32), q[:, 0], k[:, 0], kb[:, 0], vb[:, 0],
            g[:, 0], interpret=not on_chip)
        return o.reshape(B, 1, H, dv), leaf
    src, dst, snap, flag = chunk_slots(plan, B)
    o, leaf = gated_delta_chunk(
        leaf, layer, src, dst, snap, flag, q, k, kb, vb, g, chunk=tiles,
        interpret=not on_chip)
    return o.reshape(B, S, H, dv), leaf
