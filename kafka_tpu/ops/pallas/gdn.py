"""The gated delta rule with ONE decay a head (Gated DeltaNet, arXiv:2412.06464;
`olmo_hybrid`'s linear-attention layers), key and value heads of sizes of
their own, the state updated IN PLACE in its slot.

Per head, with k, q in R^dk, v in R^dv, alpha_t = exp(g_t) in (0, 1] a SCALAR
and beta_t in [0, 2):

    S_t = alpha_t S_(t-1) + beta_t k_t (v_t - alpha_t S_(t-1)^T k_t)^T
    o_t = S_t^T q_t                                     S in R^(dk x dv)

The slot holds S ITSELF, [dk, dv] float32 a head, the heads side by side along
the lanes: a state leaf is [layers, n_slots, dk, H * dv].  With one decay a
head nothing has to lie along the key channels, so the value axis takes the
lanes: Olmo-Hybrid's 30 heads of 96 x 192 are a slot of [96, 5760], twelve
sublane tiles by forty-five lane tiles with nothing padded, where S transposed
(ops/pallas/gated_delta.py's layout, [H * dv, dk]) would pad 96 lanes to 128:
a third more bytes in HBM and in every pass.  No head starts on a lane tile
(192 = 1.5 tiles), so the kernels never cut a head out of the row: they work
on whole 128-lane tiles of a GROUP of heads whose lanes are whole tiles, and
tell the heads inside a tile apart with a lane mask.

Three forms of the one recurrence, behind `gdn`:

* `gdn_chunk` (prefill, the Pallas backend): the chunked form of
  gated_delta.py with the decay a scalar.  With G the cumulative log-decay
  inside a chunk of C rows, exp(G_t - G_s) is ONE [C, C] matrix Gamma a head
  (exponents that are differences and never positive), so

      (I + A) U = beta (V - (K exp G) S_0),   A = beta (K K^T) * Gamma, s < t
      O = (Q exp G) S_0 + P U,                P = (Q K^T) * Gamma,     s <= t
      S_C = exp(G_C) S_0 + (K exp(G_C - G))^T U

  are a matmul and a mask each: no lag-by-lag construction.  (I + A)^-1 by
  doubling, as there.  A grid step holds a group of heads (a pair at d_v =
  192): every product with the state is taken over the group's lanes and a
  head keeps its own, which multiplies `heads a group` times what it must
  and moves nothing.  The state stays in VMEM across a lane's chunks; it
  comes from the lane's `src` slot by one DMA and goes to `dst` and `snap`
  by two, the leaf aliased in and out.
* `gdn_step` (decode, the Pallas backend): the closed form of one row, a
  lane's whole slot block read, updated and written back through the aliased
  leaf.  q and k arrive a head to a 128-lane tile (zeros past dk) and are
  turned into columns through the identity; S^T k and S^T q are sums over
  the SUBLANES, so v, u and o stay rows as they are.
* `_scan_xla`: a `lax.scan` over the rows (the XLA backend, and sizes the
  kernels do not tile): what the kernels are tested against.

Rows past a lane's `lens` are the identity (g = 0, beta = 0); a lane with no
real row leaves its slot untouched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta import _dot, chunk_rows
from .state_slot import chunk_slots, kernel_form

LANES = 128
STEP_BLOCK_BYTES = 5 << 19   # 2.5 MB: the most a step's slot block holds
VMEM_LIMIT = 48 << 20
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def head_groups(H: int, dv: int, aligned: bool = True):
    """The numbers of heads a grid step may hold, ascending: divisors of H
    whose lanes, heads x dv, are whole 128-lane tiles (`aligned`: what the
    chip needs; the interpreter takes any divisor)."""
    return [n for n in range(1, H + 1)
            if H % n == 0 and not (aligned and (n * dv) % LANES)]


def tiles(H: int, dk: int, dv: int) -> bool:
    """Whether the kernels take this geometry ON THE CHIP: key rows in whole
    sublane tiles and some group of heads in whole lane tiles (Olmo-Hybrid's
    30 x 96 x 192: pairs of 384 lanes)."""
    return dk % 8 == 0 and bool(head_groups(H, dv))


def step_heads(H: int, dk: int, dv: int, aligned: bool = True) -> int:
    """Heads of one grid step of `gdn_step`: as many as STEP_BLOCK_BYTES
    hold (all 30 of Olmo-Hybrid's, one contiguous 2.2 MB block a lane)."""
    fit = [n for n in head_groups(H, dv, aligned)
           if 4 * dk * n * dv <= STEP_BLOCK_BYTES]
    return (fit or head_groups(H, dv, aligned))[-1 if fit else 0]


def _key_tiles(dk: int) -> int:
    """Lanes a head's q / k row takes in the kernels' operands: whole tiles."""
    return -(-dk // LANES) * LANES


def _columns(row_ref, eye, heads, dkp):
    """Each head's [1, dkp] row of `row_ref` ([1, heads * dkp]) as a column
    [dk, 1], through the identity (Mosaic does not transpose a single row)."""
    return [jnp.sum(eye * row_ref[0, :, j * dkp:(j + 1) * dkp], axis=1,
                    keepdims=True) for j in range(heads)]


def _lane_tiles(width: int):
    return [(lo, min(lo + LANES, width)) for lo in range(0, width, LANES)]


def _by_head(cols, lo, hi, dv):
    """The heads' columns [dk, 1] laid over lanes lo .. hi of the group's
    row: one column broadcast where the tile lies inside a head, a select by
    lane where a head ends inside it."""
    first, last = lo // dv, (hi - 1) // dv
    out = cols[last]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, hi - lo), 1) + lo
    for j in range(last - 1, first - 1, -1):
        out = jnp.where(lane < (j + 1) * dv, cols[j], out)
    return out


def _step_kernel(layer_ref, slot_ref, q_ref, k_ref, vb_ref, a_ref, b_ref,
                 s_ref, o_ref, s_out_ref, *, hb, dk, dv):
    """One row of `hb` heads of one lane: the slot's block in, the updated
    block out (the same bytes of the aliased leaf), a lane tile at a time."""
    del layer_ref, slot_ref
    dkp = q_ref.shape[2] // hb
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dkp), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dkp), 1)).astype(_F32)
    kc = _columns(k_ref, eye, hb, dkp)
    qc = _columns(q_ref, eye, hb, dkp)
    for lo, hi in _lane_tiles(hb * dv):
        k = _by_head(kc, lo, hi, dv)
        S = s_ref[0, 0, :, lo:hi] * a_ref[0, :, lo:hi]
        u = vb_ref[0, :, lo:hi] - b_ref[0, :, lo:hi] * jnp.sum(
            S * k, axis=0, keepdims=True)
        S = S + k * u
        o_ref[0, :, lo:hi] = jnp.sum(S * _by_head(qc, lo, hi, dv), axis=0,
                                     keepdims=True)
        s_out_ref[0, 0, :, lo:hi] = S


@functools.partial(jax.jit, static_argnames=("dv", "heads_a_step",
                                             "interpret"))
def gdn_step(leaf, layer, slots, q, k, vb, a, b, *, dv: int,
             heads_a_step: int | None = None, interpret: bool = False):
    """Decode's one row a lane.  leaf [L, n_slots, dk, H * dv] f32; layer []
    int32; slots [B] int32 (lane i's slot); q, k [B, H * dkp] f32, a head's
    dk values at the head of its own dkp = whole-tile lanes; vb (= beta v), a
    (= exp g) and b (= beta), each a head's value over its dv lanes, [B, H *
    dv] f32 (a lane that is not decoding: a = 1, vb = b = 0, its block is
    written back as read) -> (o [B, H * dv], leaf)."""
    B = q.shape[0]
    dk, wide = leaf.shape[2:]
    H = wide // dv
    hb = heads_a_step or step_heads(H, dk, dv, aligned=not interpret)
    dkp = q.shape[1] // H

    def row(d):
        return pl.BlockSpec((1, 1, hb * d), lambda b, h, *_: (b, 0, h))

    state = pl.BlockSpec((1, 1, dk, hb * dv),
                         lambda b, h, layer, slot: (layer[0], slot[b], 0, h))
    kernel = functools.partial(_step_kernel, hb=hb, dk=dk, dv=dv)
    o, leaf = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb),
            in_specs=[row(dkp), row(dkp), row(dv), row(dv), row(dv), state],
            out_specs=[row(dv), state],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, 1, wide), _F32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        # operands count the scalar-prefetch arguments: the leaf is the 8th
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="gdn_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots,
      *(x[:, None, :] for x in (q, k, vb, a, b)), leaf)
    return o[:, 0], leaf


def _unit_lower_inverse(A, row, col):
    """(I + A)^-1 for A strictly lower triangular [C, C], by doubling: pairs
    of inverted blocks of w rows, [[T1, 0], [-T2 A21 T1, T2]], with no power
    of A taken (gated_delta.py: a Neumann series loses float32 once beta
    passes 1 on repeated keys)."""
    C = A.shape[0]
    zero = jnp.zeros_like(A)
    T = jnp.where(row == col, 1.0, zero) - jnp.where(
        (row // 2 == col // 2) & (row != col), A, zero)
    w = 2
    while w < C:
        m = (row // (2 * w) == col // (2 * w)) & (row // w != col // w)
        T = T - _dot(_dot(T, jnp.where(m, A, zero), ((1,), (0,))), T,
                     ((1,), (0,)))
        w *= 2
    return T


def _chunk_kernel(layer_ref, src_ref, dst_ref, snap_ref, flag_ref,
                  q_ref, k_ref, vb_ref, g_ref, gt_ref, b_ref, leaf_in,
                  o_ref, leaf_out, s_scr, sem, *, hb, dk, dv):
    """One chunk of `hb` heads of one lane; the chunk axis is sequential and
    the heads' state stays in `s_scr` ([dkp, hb * dv]: the rows past dk are
    zeros and stay so, k's lanes past dk being zeros) over it.  flag: 1 = the
    lane has real rows, 2 = it starts from zeros."""
    b, hg, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    layer = layer_ref[0]
    flag = flag_ref[b]
    active = (flag & 1) == 1
    W = hb * dv
    lanes = pl.ds(pl.multiple_of(hg * W, W), W)
    held = s_scr.at[pl.ds(0, dk)]
    C = q_ref.shape[1]
    dkp = q_ref.shape[2] // hb

    @pl.when(active & (c == 0))
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

        @pl.when((flag & 2) == 0)
        def _():
            cp = pltpu.make_async_copy(
                leaf_in.at[layer, src_ref[b], pl.ds(0, dk), lanes], held,
                sem.at[0])
            cp.start()
            cp.wait()

    @pl.when(active)
    def _():
        row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        heads = jax.lax.broadcasted_iota(jnp.int32, g_ref.shape[1:], 1)
        heads_t = jax.lax.broadcasted_iota(jnp.int32, gt_ref.shape[2:], 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
        S = s_scr[...]
        vb = vb_ref[0]
        o, S1 = jnp.zeros_like(vb), S
        for j in range(hb):
            h = hg * hb + j
            # head h's cumulative log-decay as a column and as a row, and
            # its beta as a column: one head's lane (or sublane) of a block
            # that holds every head's
            G = jnp.sum(jnp.where(heads == h, g_ref[0], 0.0), axis=1,
                        keepdims=True)                       # [C, 1]
            Gt = jnp.sum(jnp.where(heads_t == h, gt_ref[0, 0], 0.0), axis=0,
                         keepdims=True)                      # [1, C]
            beta = jnp.sum(jnp.where(heads == h, b_ref[0], 0.0), axis=1,
                           keepdims=True)
            q = q_ref[0, :, j * dkp:(j + 1) * dkp]
            k = k_ref[0, :, j * dkp:(j + 1) * dkp]
            kb = k * beta
            gamma = jnp.exp(jnp.minimum(G - Gt, 0.0))
            A = jnp.where(row > col, _dot(kb, k, ((1,), (1,))) * gamma, 0.0)
            P = jnp.where(row >= col, _dot(q, k, ((1,), (1,))) * gamma, 0.0)
            T = _unit_lower_inverse(A, row, col)
            e0 = jnp.exp(G)
            U = _dot(T, vb - _dot(kb * e0, S, ((1,), (0,))), ((1,), (0,)))
            mine = (lane >= j * dv) & (lane < (j + 1) * dv)
            o = jnp.where(mine, _dot(q * e0, S, ((1,), (0,)))
                          + _dot(P, U, ((1,), (0,))), o)
            last = jnp.min(G, axis=0, keepdims=True)   # g <= 0: the last row
            S1 = jnp.where(mine, S * jnp.exp(last) + _dot(
                k * jnp.exp(last - G), U, ((0,), (0,))), S1)
        o_ref[0] = o
        s_scr[...] = S1

    @pl.when(jnp.logical_not(active))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(active & (c == pl.num_programs(2) - 1))
    def _():
        out = [pltpu.make_async_copy(
            held, leaf_out.at[layer, ref[b], pl.ds(0, dk), lanes], sem.at[i])
            for i, ref in enumerate((dst_ref, snap_ref))]
        for cp in out:
            cp.start()
        for cp in out:
            cp.wait()


@functools.partial(jax.jit, static_argnames=("dv", "chunk", "heads_a_step",
                                             "interpret"))
def gdn_chunk(leaf, layer, src, dst, snap, flag, q, k, vb, g, beta, *,
              dv: int, chunk: int, heads_a_step: int | None = None,
              interpret: bool = False):
    """leaf [L, n_slots, dk, H * dv] f32; layer [] int32; src / dst / snap /
    flag [B] int32; q, k [B, S, H * dkp] f32 (a head's dk values at the head
    of its own dkp lanes); vb (= beta v) [B, S, H * dv] f32; g (the
    log-decay, 0 on padded rows) and beta [B, S, H] f32 -> (o [B, S, H * dv]
    f32, leaf with each active lane's state after its rows in `dst` and
    `snap`).  `leaf` is aliased to the result: donate it."""
    B, S, H = g.shape
    dk = leaf.shape[2]
    dkp = q.shape[2] // H
    # the FEWEST heads whose lanes are whole tiles: every product with the
    # state is taken over the group's lanes for each of its heads
    hb = heads_a_step or head_groups(H, dv, aligned=not interpret)[0]
    n = S // chunk
    # the cumulative log-decay INSIDE each chunk, row t's own g included,
    # rows down (a column a head) and rows across (a row a head)
    G = jnp.cumsum(g.reshape(B, n, chunk, H), axis=2)
    Gt = jnp.swapaxes(G, 2, 3)
    G = G.reshape(B, S, H)

    def rows(d):
        return pl.BlockSpec((1, chunk, hb * d), lambda b, h, c, *_: (b, c, h))

    every = pl.BlockSpec((1, chunk, H), lambda b, h, c, *_: (b, c, 0))
    kernel = functools.partial(_chunk_kernel, hb=hb, dk=dk, dv=dv)
    o, leaf = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, H // hb, n),
            in_specs=[rows(dkp), rows(dkp), rows(dv), every,
                      pl.BlockSpec((1, 1, H, chunk),
                                   lambda b, h, c, *_: (b, c, 0, 0)),
                      every, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[rows(dv), pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((dkp, hb * dv), _F32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, S, H * dv), _F32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        # operands count the scalar-prefetch arguments: the leaf is the 12th
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="gdn_chunk",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), src, dst, snap, flag,
      q, k, vb, G, Gt, beta, leaf)
    return o, leaf


def _scan_xla(q, k, v, g, beta, S0):
    """The recurrence row by row.  q, k [B, S, H, dk]; v [B, S, H, dv]; g,
    beta [B, S, H]; S0 [B, dk, H, dv] -> (o [B, S, H, dv], S after the last
    row)."""
    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = S * jnp.exp(g_t)[:, None, :, None]
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bkhv,bhk->bhv", S, k_t, precision=_HI))
        S = S + jnp.einsum("bhk,bhv->bkhv", k_t, u, precision=_HI)
        return S, jnp.einsum("bkhv,bhk->bhv", S, q_t, precision=_HI)

    S, o = jax.lax.scan(step, S0, tuple(
        jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), S


def form(kernel: bool, cached: bool, S: int, own_slots: bool, H: int,
         dk: int, dv: int) -> str:
    """Which form a pass of S rows a lane takes, "kernel" or "xla"
    (state_slot.kernel_form: `gdn`'s own rule)."""
    return kernel_form(kernel, cached, S, own_slots, chunk_rows(S),
                       tiles(H, dk, dv))


def gdn(leaf, layer, plan, q, k, v, g, beta, *, kernel: bool, read_state,
        write_state):
    """The layer's recurrence over a pass, from each lane's state and back
    into its slot.  q, k [B, S, H, dk], v [B, S, H, dv], g, beta [B, S, H],
    all float32; `leaf` the stacked state [state layers, n_slots, dk, H * dv]
    float32 (None: uncached, from zeros) and `layer` this layer's place in
    it; `plan` the pass's StatePlan (`read_state` / `write_state` its slot
    read and write, which the XLA form goes through).  Rows past `plan.lens`
    are the identity.  `kernel`: the Pallas kernels where they tile the pass
    (`form`).  Returns (o [B, S, H, dv] float32, leaf')."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    real = jnp.arange(S)[None, :, None] < plan.lens[:, None, None]
    g = jnp.where(real, g, 0.0)
    beta = jnp.where(real, beta, 0.0)
    if form(kernel, leaf is not None, S, plan.src is not None, H, dk,
            dv) == "xla":
        S0 = (jnp.zeros((B, dk, H, dv), _F32) if leaf is None
              else read_state(leaf, layer, plan, B).astype(_F32).reshape(
                  B, dk, H, dv))
        o, S1 = _scan_xla(q, k, v, g, beta, S0)
        if leaf is not None:
            leaf = write_state(leaf, layer, plan,
                               S1.reshape(B, dk, H * dv),
                               S0.reshape(B, dk, H * dv))
        return o, leaf
    on_chip = jax.default_backend() == "tpu"
    pad = ((0, 0),) * 3 + ((0, _key_tiles(dk) - dk),)
    q, k = (jnp.pad(a, pad).reshape(B, S, -1) for a in (q, k))
    vb = (beta[..., None] * v).reshape(B, S, H * dv)
    layer = jnp.asarray(layer, jnp.int32)
    if S == 1:
        a, b = (jnp.repeat(x[:, 0], dv, axis=-1) for x in (jnp.exp(g), beta))
        o, leaf = gdn_step(
            leaf, layer, jnp.arange(B, dtype=jnp.int32), q[:, 0], k[:, 0],
            vb[:, 0], a, b, dv=dv, interpret=not on_chip)
        return o.reshape(B, 1, H, dv), leaf
    src, dst, snap, flag = chunk_slots(plan, B)
    o, leaf = gdn_chunk(
        leaf, layer, src, dst, snap, flag, q, k, vb, g, beta, dv=dv,
        chunk=chunk_rows(S), interpret=not on_chip)
    return o.reshape(B, S, H, dv), leaf
