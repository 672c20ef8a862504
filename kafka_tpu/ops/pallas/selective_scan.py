"""Selective scan (Mamba-1's recurrence) over a prefill chunk, state in VMEM.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) (x) B_t        [d_state, d_inner]
    y_t = C_t . h_t + D * x_t                                    [d_inner]

No matmul form exists: the decay exp(dt_t[d] * A[s, d]) differs by channel
AND state index.  In XLA the choice is an `associative_scan` that passes the
[S, d_state, d_inner] f32 products through HBM ~2 log2 S times (671 MB a
layer at 2,048 x 16 x 5,120) or a `lax.scan` of S dependent steps, each its
own HBM round trip.  Here the state [d_state, d_inner] f32 (327 KB) stays in
VMEM for the whole chunk and HBM sees x, dt, B, C once and y once.

* grid = (lanes, S / T): the time axis is sequential ("arbitrary"), the
  state's output block is indexed by the lane alone and so stays resident
  across a lane's time blocks; it is loaded from `h0` at the first.
* layout: d_state on the sublanes, d_inner on the lanes.  A time block's x
  and dt rows are read eight at a time (one f32 sublane tile) a 128-lane
  column block, and the rows sliced statically; B_t and C_t arrive pre-broadcast along 128 lanes
  ([S, d_state, 128]: a step reads one [d_state, 128] tile by its leading
  index), because Mosaic cannot turn a row of d_state lanes into a column.
* rows past a lane's chunk length are the CALLER's to neutralise: dt = 0
  there gives exp(0) = 1 and no input, so the state after the last row is
  the state after the last REAL row (`selective_scan` below does it).

`selective_scan` is the one entry: the kernel on the Pallas backend
(interpreted off the chip), a `lax.scan` over time elsewhere, and the
closed form of one step at S = 1 (decode) on both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TIME_BLOCK = 64   # rows a grid step holds (x, dt, y double-buffered in VMEM)
ROW_TILE = 8      # rows read at once: one f32 sublane tile
LANES = 128


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
                 y_ref, h_ref):
    """One time block of one lane.  x/dt/y [1, T, di] f32; a [ds, di]; b/c
    [1, T, ds, 128]; d [1, di]; h0 / h [1, ds, di] (h resident over t)."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = h0_ref[...]

    T, di = x_ref.shape[1], x_ref.shape[2]

    def tile(i, carry):
        r0 = pl.multiple_of(i * ROW_TILE, ROW_TILE)
        for c0 in range(0, di, LANES):           # a column block: 2 vregs of h
            cols = slice(c0, c0 + LANES)
            xs = x_ref[0, pl.ds(r0, ROW_TILE), cols]    # [8, 128]: one vreg
            dts = dt_ref[0, pl.ds(r0, ROW_TILE), cols]
            a = a_ref[:, cols]                   # [ds, 128]
            d = d_ref[:, cols]                   # [1, 128]
            h = h_ref[0, :, cols]
            rows = []
            for k in range(ROW_TILE):
                x_t = xs[k:k + 1]                # [1, 128]
                dt_t = dts[k:k + 1]
                b_t = b_ref[0, r0 + k]           # [ds, 128]
                c_t = c_ref[0, r0 + k]
                h = jnp.exp(dt_t * a) * h + (dt_t * x_t) * b_t
                rows.append(jnp.sum(h * c_t, axis=0, keepdims=True) + d * x_t)
            h_ref[0, :, cols] = h
            y_ref[0, pl.ds(r0, ROW_TILE), cols] = jnp.concatenate(rows, axis=0)
        return carry

    jax.lax.fori_loop(0, T // ROW_TILE, tile, 0)


def scan_time_block(S: int) -> int:
    """Rows a grid step of the kernel holds for a chunk of S rows."""
    return min(TIME_BLOCK, S)


def kernel_ok(S: int, di: int) -> bool:
    """Can the kernel take a chunk of S rows x di channels?  Whole row tiles
    a time block, whole time blocks a chunk, whole lane tiles a row."""
    t = scan_time_block(S)
    return S > 1 and S % t == 0 and t % ROW_TILE == 0 and di % LANES == 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_kernel(x, dt, a, b, c, d, h0, *, interpret: bool = False):
    """x, dt [W, S, di] f32; a [ds, di] f32; b, c [W, S, ds] f32; d [di] f32;
    h0 [W, ds, di] f32 -> (y [W, S, di] f32, h [W, ds, di] f32)."""
    W, S, di = x.shape
    ds = a.shape[0]
    T = scan_time_block(S)
    wide = (W, S, ds, LANES)
    b_w = jnp.broadcast_to(b[..., None], wide)
    c_w = jnp.broadcast_to(c[..., None], wide)
    rows = pl.BlockSpec((1, T, di), lambda w, t: (w, t, 0))
    bc = pl.BlockSpec((1, T, ds, LANES), lambda w, t: (w, t, 0, 0))
    state = pl.BlockSpec((1, ds, di), lambda w, t: (w, 0, 0))
    return pl.pallas_call(
        _scan_kernel,
        grid=(W, S // T),
        in_specs=[rows, rows, pl.BlockSpec((ds, di), lambda w, t: (0, 0)),
                  bc, bc, pl.BlockSpec((1, di), lambda w, t: (0, 0)), state],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct((W, S, di), jnp.float32),
                   jax.ShapeDtypeStruct((W, ds, di), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(x, dt, a, b_w, c_w, d[None, :], h0)


def _scan_xla(x, dt, a, b, c, d, h0):
    """The same recurrence as a `lax.scan` over time (the XLA backend, and
    chunk shapes the kernel does not tile)."""
    def step(h, row):
        x_t, dt_t, b_t, c_t = row                       # [W, di] / [W, ds]
        h = (jnp.exp(dt_t[:, None, :] * a[None]) * h
             + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.einsum("wsd,ws->wd", h, c_t) + d * x_t

    h, y = jax.lax.scan(step, h0, tuple(
        jnp.swapaxes(v, 0, 1) for v in (x, dt, b, c)))
    return jnp.swapaxes(y, 0, 1), h


def selective_scan(x, dt, a, b, c, d, h0, lens, *, kernel: bool):
    """The recurrence over a chunk, from state `h0`, ignoring rows past
    `lens` [W] (their dt is zeroed: the state passes through them).  All
    float32; returns (y [W, S, di], h after each lane's last real row).  `kernel`:
    run the Pallas kernel where it tiles the chunk."""
    S, di = x.shape[1], x.shape[2]
    real = jnp.arange(S)[None, :] < lens[:, None]
    dt = jnp.where(real[..., None], dt, 0.0)
    if S == 1:
        h = (jnp.exp(dt[:, 0, None, :] * a[None]) * h0
             + (dt[:, 0] * x[:, 0])[:, None, :] * b[:, 0, :, None])
        y = jnp.einsum("wsd,ws->wd", h, c[:, 0]) + d * x[:, 0]
        return y[:, None], h
    if kernel and kernel_ok(S, di):
        return selective_scan_kernel(
            x, dt, a, b, c, d, h0, interpret=jax.default_backend() != "tpu")
    return _scan_xla(x, dt, a, b, c, d, h0)
