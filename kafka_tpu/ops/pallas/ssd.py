"""Mamba-2's recurrence (state-space duality, arXiv:2405.21060; `falcon_h1`'s
mixer), the state updated IN PLACE in its slot.

Per head h of a group g, with x_t in R^P, B_t, C_t in R^N shared by the
group's heads, dt_t > 0 and a_t = exp(g_t) in (0, 1], g_t = -exp(A_log_h) dt_t
a SCALAR a head:

    S_t = a_t S_(t-1) + (dt_t x_t) B_t^T,   S in R^(P x N), float32
    y_t = S_t C_t

(the D x_t skip is the caller's: it needs no state).  The slot holds S a
head, [P, N] (the state size in the lanes), the heads stacked along the rows:
a state leaf is [layers, n_slots, H * P, N].

Three forms of the one recurrence, behind `ssd`:

* `ssd_chunk` (prefill, the Pallas backend): the chunked form.  With G the
  cumulative log-decay inside a chunk of C rows, X the rows' dt x and S_0 the
  state before the chunk,

      L[t, s] = exp(G_t - G_s) for s <= t, else 0
      Y = (C exp G) S_0^T + (L * (C B^T)) X
      S_C = exp(G_C) S_0 + X^T (B exp(G_C - G))

  The decay is a scalar a head, so every exponent is a DIFFERENCE of two
  cumulative sums and never positive: no quotient of exponentials is taken
  and a chunk whose cumulative decay underflows float32 loses nothing but
  what had decayed.  Three MXU products a head a chunk; C B^T is taken ONCE
  for the heads of a grid step, which are one group's (or a part of one).
  Heads narrower than a lane tile (P = 64) are taken 128 / P together, the
  products over the tile and each head keeping its own lanes
  (`heads_a_tile`).
  The state stays in VMEM across a lane's chunks; it comes from the lane's
  `src` slot by one DMA and goes to `dst` and `snap` by two, the leaf aliased
  in and out (ops/pallas/state_slot.py, shared with gated_delta.py).
* `ssd_step` (decode, the Pallas backend): the closed form of one row, the
  slot's block read, updated and written back through the aliased leaf.
* `_scan_xla`: a `lax.scan` over the rows (the XLA backend, the CPU, and
  shapes the kernels do not tile): what the kernels are tested against.

Rows past a lane's `lens` are the identity (g = 0, dt x = 0), so the state
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

CHUNK = 128        # rows of one chunk (`mamba_chunk_size`)
SMALL = (16, 32, 64)  # launches of fewer rows are one chunk
STATE_BLOCK_BYTES = 2 << 20  # the heads' states a grid step holds in VMEM
CHUNK_VMEM_LIMIT = 64 << 20
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def chunk_rows(S: int):
    """Rows of a chunk of a launch of S rows, or None where the chunk kernel
    does not tile it (the XLA scan runs then)."""
    if S > 1 and S % CHUNK == 0:
        return CHUNK
    return S if S in SMALL else None


def heads_a_step(H: int, groups: int, P: int, N: int) -> int:
    """Heads a grid step holds: a whole group's where their states fit
    STATE_BLOCK_BYTES of VMEM, else the largest part of a group that does."""
    hb = H // groups
    while hb > 1 and hb % 2 == 0 and hb * P * N * 4 > STATE_BLOCK_BYTES:
        hb //= 2
    return hb


def heads_a_tile(P: int, hb: int) -> int:
    """Heads the kernels take together so that every slice of the lanes is a
    whole 128-lane tile: 1 where a head's P channels are whole tiles
    themselves (or nothing tiles: the interpreter slices anything), 128 / P
    of the `hb` heads of a grid step where P divides a tile and that many
    divide `hb` (Nemotron-H's heads of 64: pairs)."""
    q = 128 // P if P < 128 and 128 % P == 0 else 1
    return q if hb % q == 0 else 1


def tiles(H: int, groups: int, P: int, N: int) -> bool:
    """Whether the kernels tile this geometry on the chip: the state size in
    whole lane tiles, and a head's channels whole tiles or whole heads a
    tile."""
    hb = heads_a_step(H, groups, P, N)
    return N % 128 == 0 and (P * heads_a_tile(P, hb)) % 128 == 0


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=_F32)


def _column(block, j: int):
    """Column j of `block` [rows, n] as [rows, 1] (a masked lane reduction:
    n is a handful of heads, not a tile)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == j, block, 0.0), axis=1, keepdims=True)


def _chunk_kernel(layer_ref, src_ref, dst_ref, snap_ref, flag_ref,
                  x_ref, b_ref, c_ref, g_ref, leaf_in,
                  y_ref, leaf_out, s_scr, sem, *, hb, P):
    """One chunk of `hb` heads of one lane; the chunk axis is sequential and
    the heads' states stay in `s_scr` over it."""
    b, c = pl.program_id(0), pl.program_id(2)
    hg = pl.program_id(1)
    layer = layer_ref[0]
    flag = flag_ref[b]
    active = (flag & 1) == 1
    rows = pl.ds(pl.multiple_of(hg * (hb * P), hb * P), hb * P)

    @pl.when(active & (c == 0))
    def _():
        load_state(leaf_in, layer, src_ref, b, rows, s_scr, sem, flag)

    @pl.when(active)
    def _():
        Bm, Cm = b_ref[0], c_ref[0]                      # [C, N]
        G = g_ref[0, 0]                                  # [C, hb]
        C = Bm.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        eye = (row == col).astype(_F32)
        CB = _dot(Cm, Bm, ((1,), (1,)))                  # [C, C], once

        def decay(j):
            """Head j's cumulative log-decay [C, 1] and its L [C, C]."""
            Gc = _column(G, j)
            # the same values along the lanes (through the identity)
            Gr = jnp.sum(eye * Gc, axis=0, keepdims=True)  # [1, C]
            return Gc, jnp.where(
                row >= col, jnp.exp(jnp.minimum(Gc - Gr, 0.0)), 0.0)

        q = heads_a_tile(P, hb)
        W = q * P
        if q > 1:
            for u in range(hb // q):
                # `q` heads a lane tile: every product is taken over the tile's
                # W = 128 lanes (rows of the state) and each head keeps its own
                # lanes (rows) of the result, so no slice cuts a tile
                ws = slice(u * W, (u + 1) * W)
                X = x_ref[0, :, ws]                          # [C, W]: dt x
                S0 = s_scr[ws, :]                            # [W, N]
                lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
                srow = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
                y = jnp.zeros((C, W), _F32)
                S1 = jnp.zeros(S0.shape, _F32)
                for j in range(q):
                    Gc, L = decay(u * q + j)
                    yj = (_dot(Cm * jnp.exp(Gc), S0, ((1,), (1,)))
                          + _dot(L * CB, X, ((1,), (0,))))
                    y = y + jnp.where(
                        (lane >= j * P) & (lane < (j + 1) * P), yj, 0.0)
                    last = Gc[C - 1:C, :]                    # [1, 1]
                    Sj = S0 * jnp.exp(last) + _dot(
                        X, Bm * jnp.exp(last - Gc), ((0,), (0,)))
                    S1 = S1 + jnp.where(
                        (srow >= j * P) & (srow < (j + 1) * P), Sj, 0.0)
                y_ref[0, :, ws] = y
                s_scr[ws, :] = S1
        else:
            for j in range(hb):
                ps = slice(j * P, (j + 1) * P)
                Gc, L = decay(j)
                X = x_ref[0, :, ps]                          # [C, P]: dt x
                S0 = s_scr[ps, :]                            # [P, N]
                y_ref[0, :, ps] = (
                    _dot(Cm * jnp.exp(Gc), S0, ((1,), (1,)))
                    + _dot(L * CB, X, ((1,), (0,))))
                last = Gc[C - 1:C, :]                        # [1, 1]
                s_scr[ps, :] = S0 * jnp.exp(last) + _dot(
                    X, Bm * jnp.exp(last - Gc), ((0,), (0,)))

    @pl.when(jnp.logical_not(active))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(active & (c == pl.num_programs(2) - 1))
    def _():
        store_state(leaf_out, layer, (dst_ref, snap_ref), b, rows, s_scr,
                    sem)


@functools.partial(jax.jit,
                   static_argnames=("groups", "chunk", "interpret"))
def ssd_chunk(leaf, layer, src, dst, snap, flag, x, Bm, Cm, g, *,
              groups: int, chunk: int = CHUNK, interpret: bool = False):
    """leaf [L, n_slots, H * P, N] f32; layer [] int32; src / dst / snap /
    flag [B] int32; x [B, S, H * P] f32 (dt x, 0 on padded rows); Bm, Cm [B,
    S, groups * N] f32; g [B, S, H] f32 (the log-decay, 0 on padded rows) ->
    (y [B, S, H * P] f32, leaf with each active lane's state after its rows
    in `dst` and `snap`).  `leaf` is aliased to the result: donate it."""
    B, S, width = x.shape
    N = leaf.shape[3]
    H = g.shape[2]
    P = width // H
    hb = heads_a_step(H, groups, P, N)
    per_group = H // groups // hb  # grid steps that share a group's B and C
    n = S // chunk
    # the cumulative log-decay INSIDE each chunk, row t's own g included,
    # laid out a grid step's heads together: [B, H / hb, S, hb]
    G = jnp.cumsum(g.reshape(B, n, chunk, H), axis=2).reshape(B, S, H // hb,
                                                              hb)
    G = jnp.swapaxes(G, 1, 2)

    def shared(b, h, c, *_):
        return (b, c, h // per_group)

    kernel = functools.partial(_chunk_kernel, hb=hb, P=P)
    y, leaf = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, H // hb, n),
            in_specs=[
                pl.BlockSpec((1, chunk, hb * P), lambda b, h, c, *_: (b, c, h)),
                pl.BlockSpec((1, chunk, N), shared),
                pl.BlockSpec((1, chunk, N), shared),
                pl.BlockSpec((1, 1, chunk, hb),
                             lambda b, h, c, *_: (b, h, c, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((1, chunk, hb * P), lambda b, h, c, *_: (b, c, h)),
                pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((hb * P, N), _F32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, S, H * P), _F32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        # operands count the scalar-prefetch arguments: the leaf is the 10th
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=CHUNK_VMEM_LIMIT),
        interpret=interpret,
        name="ssd_chunk",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), src, dst, snap, flag,
      x, Bm, Cm, G, leaf)
    return y, leaf


def _step_kernel(layer_ref, slot_ref, x_ref, b_ref, c_ref, g_ref, s_ref,
                 y_ref, s_out_ref, *, hb, P):
    """One row of `hb` heads of one lane: the slot's block in, the updated
    block out (the same bytes of the aliased leaf)."""
    del layer_ref, slot_ref
    q = heads_a_tile(P, hb)
    W = q * P
    eye = (jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)).astype(_F32)
    Bm, Cm = b_ref[0], c_ref[0]                          # [1, N]
    a = jnp.exp(g_ref[0, 0])                             # [1, hb]
    if q > 1:
        for u in range(hb // q):
            # `q` heads a lane tile (`_chunk_kernel`): each row of the tile's
            # states decays by its own head's a
            ws = slice(u * W, (u + 1) * W)
            srow = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
            decay = jnp.zeros((W, 1), _F32)
            for j in range(q):
                decay = jnp.where((srow >= j * P) & (srow < (j + 1) * P),
                                  _column(a, u * q + j), decay)
            x = jnp.sum(eye * x_ref[0, :, ws], axis=1, keepdims=True)  # [W, 1]
            S = s_ref[0, 0, ws, :] * decay + x * Bm
            y = jnp.sum(S * Cm, axis=1, keepdims=True)       # [W, 1]
            y_ref[0, :, ws] = jnp.sum(eye * y, axis=0, keepdims=True)
            s_out_ref[0, 0, ws, :] = S
    else:
        for j in range(hb):
            ps = slice(j * P, (j + 1) * P)
            # a row of P lanes as a column of P sublanes, and back: through the
            # identity (Mosaic does not transpose a single row)
            x = jnp.sum(eye * x_ref[0, :, ps], axis=1, keepdims=True)  # [P, 1]
            S = s_ref[0, 0, ps, :] * _column(a, j) + x * Bm
            y = jnp.sum(S * Cm, axis=1, keepdims=True)       # [P, 1]
            y_ref[0, :, ps] = jnp.sum(eye * y, axis=0, keepdims=True)
            s_out_ref[0, 0, ps, :] = S


@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def ssd_step(leaf, layer, slots, x, Bm, Cm, g, *, groups: int,
             interpret: bool = False):
    """Decode's one row a lane.  leaf [L, n_slots, H * P, N] f32; layer []
    int32; slots [B] int32 (lane i's slot); x [B, H * P] f32 (dt x); Bm, Cm
    [B, groups * N] f32; g [B, H] f32 (a lane that is not decoding: g = 0, x
    = 0, its block is written back as read) -> (y [B, H * P], leaf)."""
    B, width = x.shape
    N = leaf.shape[3]
    H = g.shape[1]
    P = width // H
    hb = heads_a_step(H, groups, P, N)
    per_group = H // groups // hb

    def shared(b, h, *_):
        return (b, 0, h // per_group)

    state = pl.BlockSpec((1, 1, hb * P, N),
                         lambda b, h, layer, slot: (layer[0], slot[b], h, 0))
    kernel = functools.partial(_step_kernel, hb=hb, P=P)
    y, leaf = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb),
            in_specs=[
                pl.BlockSpec((1, 1, hb * P), lambda b, h, *_: (b, 0, h)),
                pl.BlockSpec((1, 1, N), shared),
                pl.BlockSpec((1, 1, N), shared),
                pl.BlockSpec((1, 1, 1, hb), lambda b, h, *_: (b, h, 0, 0)),
                state],
            out_specs=[
                pl.BlockSpec((1, 1, hb * P), lambda b, h, *_: (b, 0, h)),
                state],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, 1, H * P), _F32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=CHUNK_VMEM_LIMIT),
        interpret=interpret,
        name="ssd_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots,
      x[:, None, :], Bm[:, None, :], Cm[:, None, :],
      g.reshape(B, H // hb, 1, hb), leaf)
    return y[:, 0], leaf


def _scan_xla(x, Bm, Cm, g, S0):
    """The recurrence row by row.  x [B, S, H, P] (dt x); Bm, Cm [B, S, H, N]
    (each head's group's); g [B, S, H]; S0 [B, H, P, N] -> (y [B, S, H, P], S
    after the last row)."""
    def step(S, row):
        x_t, b_t, c_t, g_t = row
        S = (S * jnp.exp(g_t)[..., None, None]
             + x_t[..., :, None] * b_t[..., None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t, precision=_HI)

    S, y = jax.lax.scan(step, S0, tuple(
        jnp.swapaxes(a, 0, 1) for a in (x, Bm, Cm, g)))
    return jnp.swapaxes(y, 0, 1), S


def form(kernel: bool, cached: bool, S: int, own_slots: bool, H: int,
         groups: int, P: int, N: int) -> str:
    """Which form a pass of S rows a lane takes, "kernel" or "xla"
    (state_slot.kernel_form: `ssd`'s own rule)."""
    return kernel_form(kernel, cached, S, own_slots, chunk_rows(S),
                       tiles(H, groups, P, N))


def ssd(leaf, layer, plan, x, Bm, Cm, g, *, kernel: bool, read_state,
        write_state):
    """The layer's recurrence over a pass, from each lane's state and back
    into its slot.  x [B, S, H, P] (dt x), Bm, Cm [B, S, groups, N], g [B, S,
    H] (the log-decay), all float32; `leaf` the stacked state [state layers,
    n_slots, H * P, N] float32 (None: uncached, from zeros) and `layer` this
    layer's place in it; `plan` the pass's StatePlan (models/hybrid.py;
    `read_state` / `write_state` its slot read and write, which the XLA form
    goes through).  Rows past `plan.lens` are the identity.  `kernel`: the
    Pallas kernels where they tile the pass.  Returns (y [B, S, H, P]
    float32, leaf')."""
    B, S, H, P = x.shape
    groups, N = Bm.shape[2:]
    real = (jnp.arange(S)[None, :] < plan.lens[:, None])[..., None]
    g = jnp.where(real, g, 0.0)
    x = jnp.where(real[..., None], x, 0.0)
    rows = chunk_rows(S) if S > 1 else 1
    on_chip = jax.default_backend() == "tpu"
    if form(kernel, leaf is not None, S, plan.src is not None, H, groups, P,
            N) == "xla":
        S0 = (jnp.zeros((B, H, P, N), _F32) if leaf is None
              else read_state(leaf, layer, plan, B).astype(_F32).reshape(
                  B, H, P, N))
        per = H // groups
        y, S1 = _scan_xla(x, jnp.repeat(Bm, per, axis=2),
                          jnp.repeat(Cm, per, axis=2), g, S0)
        if leaf is not None:
            leaf = write_state(leaf, layer, plan, S1.reshape(B, H * P, N),
                               S0.reshape(B, H * P, N))
        return y, leaf
    x = x.reshape(B, S, H * P)
    Bm, Cm = Bm.reshape(B, S, groups * N), Cm.reshape(B, S, groups * N)
    layer = jnp.asarray(layer, jnp.int32)
    if S == 1:
        y, leaf = ssd_step(
            leaf, layer, jnp.arange(B, dtype=jnp.int32), x[:, 0], Bm[:, 0],
            Cm[:, 0], g[:, 0], groups=groups, interpret=not on_chip)
        return y.reshape(B, 1, H, P), leaf
    src, dst, snap, flag = chunk_slots(plan, B)
    y, leaf = ssd_chunk(leaf, layer, src, dst, snap, flag, x, Bm, Cm, g,
                        groups=groups, chunk=rows, interpret=not on_chip)
    return y.reshape(B, S, H, P), leaf
