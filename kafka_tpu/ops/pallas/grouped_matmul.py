"""Grouped matmul for token-dispatched experts: rows sorted by group, one
weight matrix a group.

The kernel is JAX's own (`jax.experimental.pallas.ops.tpu.megablox.gmm`): a
grid over (output tile, (row tile, group) visit, contraction tile) whose
visits are computed from the group sizes on the device, so a tile that two
groups share is visited once for each and an empty group or a tile past the
last group not at all.  What this module adds is the tiling.  Alone on the
chip at every routed configuration's widths (scripts/moe_dispatch_bench.py;
PERF.md section 6, PR 45): `jax.lax.ragged_dot` as XLA lowers it on the v5e
pays ~4-5 ms a call whatever the rows and loses to dense dispatch up to
1,024 rows; `gmm` at contraction / output tiles of at most 1,024 wins from
~300 rows (at 512 x 512 tiles it is 25-60 % slower, at 2,048 x 1,024 the
same).  Its cost is visits x (the group's weight tiles read + a row tile's
FLOPs), so the row tile is the one size that depends on the call:
`tile_rows`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

# most of the contraction and of the output a tile holds
TILE_WIDTH = 1024


def tile_rows(rows: int, groups: int) -> int:
    """Rows a tile for `rows` sorted rows spread over `groups` groups: 256
    from a mean of 64 rows a group (fewer visits: each reads its group's
    weights again, which is what bounds Mixtral's 8 large experts at every
    size), 128 below it (a visit multiplies the whole tile however few of
    its rows are the group's; Kanana-2's 24 rows a group at a 512-row
    launch: 2.15 ms a layer against 2.30)."""
    return 256 if rows >= 64 * groups else 128


def whole_tile(width: int, most: int = TILE_WIDTH) -> int:
    """The widest tile of whole 128-lane columns, at most `most`, that
    divides `width` (the width itself where none does)."""
    fits = [n for n in range(128, min(width, most) + 1, 128)
            if width % n == 0]
    return fits[-1] if fits else width


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray, sizes: jnp.ndarray,
                   layer, rows_a_tile: int,
                   transposed: bool = False) -> jnp.ndarray:
    """lhs [m, k] (rows sorted by group, m a multiple of `rows_a_tile`) x
    rhs[layer] of the STACKED rhs [L, g, k, n] by `sizes` [g] i32 consecutive
    rows a group -> [m, n] in lhs.dtype, accumulated in f32.  The sizes may
    sum to less than m: rows past the last group are multiplied by nothing
    and their output is whatever the buffer held.  `transposed`: the stack is
    [L, g, n, k], each matrix out x in, contracted over its minor axis.

    The kernel reads the layer's weights where the stack holds them: it is
    handed all L x g matrices as groups of which only `layer`'s have rows
    (an empty group is no visit).  Handed `rhs[layer]`, a slice at the layer
    scan's index, XLA would first copy the slice out, every layer of every
    launch (Mellum2: 0.8 GB a layer, as long as the matmuls themselves;
    compiled for the v5e, PR 45)."""
    stack, groups = rhs.shape[:2]
    every = jax.lax.dynamic_update_slice(
        jnp.zeros((stack * groups,), jnp.int32), sizes, (layer * groups,))
    k, n = rhs.shape[2:][::-1] if transposed else rhs.shape[2:]
    # (`transpose_rhs` is handed over only where asked for: the programs of
    # every [k, n] stack lower as they did)
    return gmm(
        lhs, rhs.reshape((stack * groups,) + rhs.shape[2:]), every,
        preferred_element_type=lhs.dtype,
        tiling=(rows_a_tile, whole_tile(k), whole_tile(n)),
        interpret=jax.default_backend() != "tpu",
        **({"transpose_rhs": True} if transposed else {}))
