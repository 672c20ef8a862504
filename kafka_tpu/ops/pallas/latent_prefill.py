"""The fold of one key chunk of latent prefill's walk, tile by tile in VMEM.

`models/mixers/latent.py::_latent_prefill_walk` folds a chunk of expanded keys and
values into the running (max, sum, accumulator) of every query row.  In XLA
each trip writes and re-reads the [heads, rows, keys] f32 scores and
probabilities through HBM, because XLA does not fuse score matmul -> softmax
-> value matmul (28 GB a full layer of a 512-row launch over 29k keys:
PERF.md section 6, PR 33).  Here one call is one trip: the score tile of a
head stays in VMEM between the two matmuls, and only the carries pass
through HBM (the accumulator once a trip, updated in place).

Everything is laid out with the query ROWS in the lanes:

    scores^T [keys, rows] = k_nope [keys, dn] @ q_nope^T [dn, rows]
                          + k_r    [keys, dr] @ q_rope^T [dr, rows]
    acc^T    [dv, rows]   = alpha * acc^T + v^T [dv, keys] @ p^T [keys, rows]

so every matmul is a plain [M, K] @ [K, N], the running max and sum of a
head are [1, rows] lane vectors (the walk's own [lanes, heads, rows] carries,
no relayout) reduced over the SUBLANE axis, and alpha broadcasts along
sublanes.  k_r is the one rotary row a key shares over heads: a second dot
against it, never a broadcast to heads.  The mask the walk builds (valid &
causal & window & chosen) arrives as an additive f32 bias, 0 where a query
attends a key and NEG_INF where not: `score + bias` is the walk's
`where(mask, score, NEG_INF)` bit for bit (|score| is far under NEG_INF's
ulp), at one add an element.

Mathematics of the XLA fold as it stands: operands in the pool's dtype with
f32 accumulation, the scale applied in f32, f32 max / sum / accumulator,
probabilities cast to the pool's dtype ahead of the value matmul.  What
differs is the order of sums inside a tile (the score's nope and rope parts
are two dots added in f32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
ROW_BLOCK = 512       # query rows a grid step holds (the lanes of a tile)
VMEM_BUDGET = 24 << 20  # what a step's blocks and temporaries may take


def _fold_kernel(qn_ref, qr_ref, kn_ref, kr_ref, vt_ref, bias_ref,
                 m_ref, l_ref, acc_ref, m_out, l_out, acc_out, *,
                 scale: float, heads: int):
    bias = bias_ref[0]  # [T, S] f32
    kr = kr_ref[0]      # [T, dr]

    def head(h, carry):
        sc = jnp.dot(kn_ref[0, h], qn_ref[0, h],
                     preferred_element_type=jnp.float32)
        sc = sc + jnp.dot(kr, qr_ref[0, h],
                          preferred_element_type=jnp.float32)
        sc = sc * scale + bias                                  # [T, S]
        m = m_ref[0, h]                                         # [1, S]
        m_new = jnp.maximum(m, jnp.max(sc, axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # a row that has met no key yet holds NEG_INF: against 0 instead its
        # masked scores still give exp(NEG_INF) = 0, the walk's where(mask,
        # exp, 0), with no select an element
        p = jnp.exp(sc - jnp.where(m_new > NEG_INF, m_new, 0.0))
        l_out[0, h] = alpha * l_ref[0, h] + jnp.sum(p, axis=0, keepdims=True)
        acc_out[0, h] = alpha * acc_ref[0, h] + jnp.dot(
            vt_ref[0, h], p.astype(vt_ref.dtype),
            preferred_element_type=jnp.float32)                 # [dv, S]
        m_out[0, h] = m_new
        return carry

    # rolled: unrolled, a head's softmax overlaps the next head's score
    # matmul (357 us a 512-row trip against 381) but Mosaic compiles the
    # kernel in 3 s against 0.7 and `tpot_p50_ms` does not tell the two
    # apart (my chip runs 2 and 5, PR 34)
    jax.lax.fori_loop(0, heads, head, 0)


def fold_blocks(n: int, s: int, t: int, dn: int, dr: int, dv: int,
                itemsize: int) -> tuple:
    """(heads a grid step, rows a grid step, VMEM bytes of a step) for a
    fold of `n` heads, `s` rows and `t` keys: the most heads, a power of two
    that divides n, whose double-buffered blocks and f32 score temporaries
    fit VMEM_BUDGET; all the rows up to ROW_BLOCK, past it the most whole
    lane tiles that divide them."""
    sb = s
    if s > ROW_BLOCK:
        if s % 128:
            raise ValueError(f"{s} rows are not whole lane tiles of 128")
        sb = max(d for d in range(128, ROW_BLOCK + 1, 128) if s % d == 0)
    fixed = 2 * t * sb * 4 + 2 * t * max(dr, 128) * itemsize  # bias, k_r
    temps = 3 * t * sb * 4                                    # sc, p, p cast
    per_head = (2 * (t * dn + dv * t + (dn + dr) * sb) * itemsize
                + 4 * dv * sb * 4 + 8 * 8 * sb * 4)           # k, v, q; acc, m/l
    hb = 1
    while (n % (hb * 2) == 0
           and fixed + temps + per_head * hb * 2 <= VMEM_BUDGET):
        hb *= 2
    return hb, sb, fixed + temps + per_head * hb


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_prefill_fold(
    qn_t: jnp.ndarray,    # [B, N, dn, S] the queries' nope part, rows in lanes
    qr_t: jnp.ndarray,    # [B, N, dr, S] their roped part
    k_nope: jnp.ndarray,  # [B, N, T, dn] the chunk's expanded keys
    k_rope: jnp.ndarray,  # [B, T, dr]    its roped rows, ONE a key
    v_t: jnp.ndarray,     # [B, N, dv, T] its expanded values, keys in lanes
    bias: jnp.ndarray,    # [B, T, S] f32: 0 where row s attends key t, else NEG_INF
    m: jnp.ndarray,       # [B, N, S] f32 running max
    l: jnp.ndarray,       # [B, N, S] f32 running sum
    acc: jnp.ndarray,     # [B, N, dv, S] f32 running weighted values
    *,
    scale: float,
    interpret: bool = False,
):
    """One trip of latent prefill's key walk: (m', l', acc') after the
    chunk's T keys are folded into every row's running softmax (module
    docstring).  S is whole lane tiles of 128 (the walk pads its bucket);
    rows past ROW_BLOCK take further grid steps over the same key and value
    blocks.  Every tile is computed: a masked key or a padded row
    costs what a real one costs."""
    B, N, dn, S = qn_t.shape
    dr, T, dv = qr_t.shape[2], k_nope.shape[2], v_t.shape[2]
    hb, sb, vmem = fold_blocks(N, S, T, dn, dr, dv, k_nope.dtype.itemsize)
    m4, l4 = m[:, :, None, :], l[:, :, None, :]  # a [1, S] tile a head

    by_head = lambda b, h, r: (b, h, 0, 0)       # noqa: E731
    by_rows = lambda b, h, r: (b, h, 0, r)       # noqa: E731
    carry_specs = [pl.BlockSpec((1, hb, 1, sb), by_rows),
                   pl.BlockSpec((1, hb, 1, sb), by_rows),
                   pl.BlockSpec((1, hb, dv, sb), by_rows)]
    m4, l4, acc = pl.pallas_call(
        functools.partial(_fold_kernel, scale=scale, heads=hb),
        grid=(B, N // hb, S // sb),
        in_specs=[
            pl.BlockSpec((1, hb, dn, sb), by_rows),
            pl.BlockSpec((1, hb, dr, sb), by_rows),
            pl.BlockSpec((1, hb, T, dn), by_head),
            pl.BlockSpec((1, T, dr), lambda b, h, r: (b, 0, 0)),
            pl.BlockSpec((1, hb, dv, T), by_head),
            pl.BlockSpec((1, T, sb), lambda b, h, r: (b, 0, r)),
        ] + carry_specs,
        out_specs=carry_specs,
        out_shape=[jax.ShapeDtypeStruct(m4.shape, jnp.float32),
                   jax.ShapeDtypeStruct(l4.shape, jnp.float32),
                   jax.ShapeDtypeStruct(acc.shape, jnp.float32)],
        input_output_aliases={6: 0, 7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, 2 * vmem)),
        interpret=interpret,
        name="latent_prefill_fold",
    )(qn_t, qr_t, k_nope, k_rope, v_t, bias, m4, l4, acc)
    return m4[:, :, 0], l4[:, :, 0], acc
