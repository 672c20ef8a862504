"""Flash prefill kernel over the paged KV pool.

The XLA prefill path materializes the full [Hq, S, C] score tensor per
layer — at an 8k window that is half a gigabyte of f32 per chunk per
layer.  This kernel streams the KV window in page chunks with online
softmax (flash attention), so peak memory is O(q_block x kv_chunk) and
HBM traffic is one pass over the valid window per q block.

Structure mirrors the decode kernel (paged_attention.py):

* merged-lane pool [TOTAL_SLOTS, Hkv*D] (the DMA lane-alignment contract);
* GQA via the block-diagonal q expansion — rows are (q position, q head)
  pairs, each row's D lanes sit in its kv head's block, one full-width
  MXU matmul per chunk, per-head lanes sliced out by the caller;
* grid = (num_q_blocks,); per block, a dynamic fori_loop over the kv
  chunks the causal mask can reach (a q block early in the prompt skips
  the chunks after it entirely, a q block of the bucket's padding skips
  them all), each chunk double-buffer DMA'd.

Causality: the engine writes the whole chunk's KV to the pool before
attention, so kv slots carry absolute positions page-order; a query at
absolute position p attends kv positions <= p, bounded by the written
total (start + chunk_len).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# the largest [q_block * Hq, Hkv * D] tile a q block may be (elements): see
# q_block_cap
PREFILL_TILE_ELEMS = 1024 * 832


def prefill_block_chunks(qb, start, chunk_len, *, q_block: int,
                         page_size: int, pages_per_chunk: int,
                         window: int | None = None) -> tuple:
    """(kv_hi, first_chunk, n_chunks) of q block `qb` of a chunk whose first
    `chunk_len` rows hold tokens at absolute positions start..: the block
    attends kv positions [0, kv_hi) and walks the KV chunks
    [first_chunk, n_chunks).  _prefill_kernel calls this with its traced
    scalars; on plain ints it gives concrete scalars, so a test can hold
    the walk without a chip.

    A block whose first row is at or past chunk_len holds no token and gets
    kv_hi 0 and n_chunks 0: it walks nothing.  Any other block attends up to its last
    REAL row (already bounded by the written total start + chunk_len).  A
    windowed layer (static `window`): the block's FIRST query, at start +
    qb * q_block, reads nothing below its own position - window + 1, and
    the later rows read nothing below that either, so KV chunks wholly
    below it are skipped; the bound per query row is in the kernel's mask.
    """
    chunk = page_size * pages_per_chunk
    live = qb * q_block < chunk_len
    kv_hi = jnp.where(
        live, start + jnp.minimum((qb + 1) * q_block, chunk_len), 0)
    n_chunks = pl.cdiv(pl.cdiv(kv_hi, page_size), pages_per_chunk)
    first_chunk = 0
    if window is not None:
        first_chunk = jnp.maximum(
            start + qb * q_block - window + 1, 0) // chunk
    return kv_hi, first_chunk, n_chunks


def _prefill_kernel(
    # scalar prefetch
    page_row_ref,   # [P] i32 physical pages of this sequence
    bounds_ref,     # [2] i32: (start, chunk_len)
    # inputs
    qx_ref,         # [QB*Hq, Hkv*D] VMEM block (block-diagonal expanded)
    k_pages_hbm,    # [num_pages, ps, Hkv*D] ANY
    v_pages_hbm,    # [num_pages, ps, Hkv*D] ANY
    out_ref,        # [QB*Hq, Hkv*D] VMEM block
    # scratch
    kbuf, vbuf, ksem, vsem,
    m_ref, l_ref, acc_ref,
    *,
    num_q_heads: int,
    page_size: int,
    pages_per_chunk: int,
    q_block: int,
    scale: float,
    window: int | None = None,
):
    qb = pl.program_id(0)
    ps, cp, hq = page_size, pages_per_chunk, num_q_heads
    chunk = cp * ps
    start = bounds_ref[0]
    chunk_len = bounds_ref[1]
    # A block wholly past chunk_len gets kv_hi 0, n_chunks 0: every DMA below
    # is guarded by n_pages and the chunk loop by n_chunks, so it starts no
    # copy, signals no semaphore, runs no chunk, and its out block is the
    # zero accumulator over the 1e-30 floor: exact zeros.
    kv_hi, first_chunk, n_chunks = prefill_block_chunks(
        qb, start, chunk_len, q_block=q_block, page_size=ps,
        pages_per_chunk=cp, window=window)
    n_pages = pl.cdiv(kv_hi, ps)

    def issue(c, slot):
        for j in range(cp):
            @pl.when(c * cp + j < n_pages)
            def _():
                page = page_row_ref[c * cp + j]
                pltpu.make_async_copy(
                    k_pages_hbm.at[page],
                    kbuf.at[slot, pl.ds(j * ps, ps)],
                    ksem.at[slot, j],
                ).start()
                pltpu.make_async_copy(
                    v_pages_hbm.at[page],
                    vbuf.at[slot, pl.ds(j * ps, ps)],
                    vsem.at[slot, j],
                ).start()

    def wait(c, slot):
        for j in range(cp):
            @pl.when(c * cp + j < n_pages)
            def _():
                page = page_row_ref[c * cp + j]
                pltpu.make_async_copy(
                    k_pages_hbm.at[page],
                    kbuf.at[slot, pl.ds(j * ps, ps)],
                    ksem.at[slot, j],
                ).wait()
                pltpu.make_async_copy(
                    v_pages_hbm.at[page],
                    vbuf.at[slot, pl.ds(j * ps, ps)],
                    vsem.at[slot, j],
                ).wait()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    if window is None:
        issue(0, 0)
    else:
        issue(first_chunk, jax.lax.rem(first_chunk, 2))

    rows = q_block * hq
    # absolute q position of each folded row (row = q_idx * Hq + head)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    q_pos = start + qb * q_block + row_ids // hq  # [rows, 1]

    def body(c, carry):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            issue(c + 1, jax.lax.rem(c + 1, 2))

        wait(c, slot)

        col_ids = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        kv_pos = c * chunk + col_ids  # [1, chunk]
        mask = (q_pos >= kv_pos) & (kv_pos < kv_hi)  # [rows, chunk]
        if window is not None:
            mask = mask & (kv_pos > q_pos - window)
        # column-shaped validity built directly (Mosaic cannot transpose a
        # boolean vector)
        col_iota = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        col_valid = col_iota < (kv_hi - c * chunk)

        kc = kbuf[slot].astype(jnp.float32)  # [chunk, HD]
        # zero junk V rows (never-DMA'd NaNs poison 0-weight matmuls)
        vc = jnp.where(col_valid, vbuf[slot].astype(jnp.float32), 0.0)
        qx = qx_ref[...].astype(jnp.float32)  # [rows, HD]
        s = (
            jax.lax.dot_general(
                qx, kc,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [rows, chunk]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)
        pexp = jnp.where(mask, pexp, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp, vc,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(first_chunk, n_chunks, body, 0)
    denom = jnp.maximum(l_ref[...], 1e-30)
    out_ref[...] = (acc_ref[...] / denom).astype(out_ref.dtype)


def q_block_cap(num_q_heads: int, lanes: int) -> int:
    """The most query positions a q block may hold at `num_q_heads` heads
    over block-diagonal rows of `lanes` (= Hkv * D) lanes.

    Scoped-VMEM bound: the kernel's per-block footprint scales with
    rows = q_block * Hq (qx/out pipeline buffers, f32 accumulator, and
    the [rows, chunk] softmax temporaries).  rows = 2048 measured
    17.91 MB of scoped VMEM against the 16 MB core limit (Mosaic
    stack-OOM at compile, first hit by the 2048-token prefill bucket at
    32 heads); rows <= ~1024 keeps ~9 MB with headroom for the DMA
    buffers.  The cap is rounded DOWN to a power of two so it divides
    the power-of-two chunk buckets for any head count (1024//24 = 42
    would fail S % qb for every bucket).

    The row is Hkv*D lanes wide, so the same rows cost more VMEM the more
    kv heads there are: 64 query / 8 kv heads x 128 at rows = 1024 is a
    [1024, 1024] tile, twice what 32 / 4 x 128 holds, and the chip refused
    it when the program ran (18.04 MB of scoped VMEM against the 16 MB
    limit: my chip run 1, PR 43; the compile for a DESCRIBED v5e had
    passed).  So the block is halved until rows x lanes is at most
    PREFILL_TILE_ELEMS; every geometry that ran before keeps the block it
    had (the widest, 40 / 20 x 64 at rows 640, is 819,200 elements)."""
    cap = max(8, 1024 // num_q_heads)
    cap = 1 << (cap.bit_length() - 1)
    while cap > 8 and cap * num_q_heads * lanes > PREFILL_TILE_ELEMS:
        cap //= 2
    return cap


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "pages_per_chunk", "q_block", "scale",
                     "interpret", "window", "diff"),
)
def paged_prefill_attention(
    q: jnp.ndarray,          # [S, Hq, D] roped queries of this chunk
    k_pool: jnp.ndarray,     # [TOTAL_SLOTS, Hkv*D] merged-lane pool
    v_pool: jnp.ndarray,
    page_row: jnp.ndarray,   # [P] i32 pages of this sequence
    start: jnp.ndarray,      # scalar i32: chunk's first absolute position
    chunk_len: jnp.ndarray,  # scalar i32: real tokens in the chunk
    *,
    page_size: int,
    pages_per_chunk: int = 8,
    q_block: int = 64,
    scale: float | None = None,
    interpret: bool = False,
    window: int | None = None,
    diff: bool = False,
) -> jnp.ndarray:
    """Flash attention of one prefill chunk against the paged window.
    `diff`: differential attention's pairing (paged_attention.diff_heads);
    returns [S, Hq, 2 D], each query head over both value heads of its pair.
    `window` (static): a sliding-window layer; each query row attends
    q_pos - window < kv_pos <= q_pos and a q block skips the KV chunks
    wholly below its first row's window.

    Returns [S, Hq, D] in q.dtype.  Rows past chunk_len are garbage (their
    KV went to the trash page) — same contract as the XLA path, which only
    samples from the last real row — except that the rows of a q block
    wholly past chunk_len are zeros: such a block walks no KV
    (prefill_block_chunks), so a bucket costs what its real blocks cost.
    """
    S, Hq, D = q.shape
    HD = k_pool.shape[1]
    Hkv = HD // D
    G = Hq // Hkv
    if scale is None:
        scale = D**-0.5
    qb = min(q_block, S, q_block_cap(Hq, HD))
    if S % qb:
        raise ValueError(f"chunk length {S} not divisible by q_block {qb}")
    cp = min(pages_per_chunk, page_row.shape[0])
    k_pages = k_pool.reshape(-1, page_size, HD)
    v_pages = v_pool.reshape(-1, page_size, HD)

    # block-diagonal expansion, rows = (q position, head) pairs
    if diff:
        from .paged_attention import diff_heads

        kv_of_q, pair_of_q = diff_heads(Hq, Hkv)
    else:
        kv_of_q = jnp.repeat(jnp.arange(Hkv), G)  # [Hq]
    qx = jnp.zeros((S, Hq, Hkv, D), q.dtype)
    qx = qx.at[:, jnp.arange(Hq), kv_of_q].set(q)
    qx = qx.reshape(S * Hq, HD)
    bounds = jnp.stack([jnp.asarray(start, jnp.int32),
                        jnp.asarray(chunk_len, jnp.int32)])

    rows = qb * Hq
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S // qb,),
        in_specs=[
            pl.BlockSpec((rows, HD), lambda b, pr, bd: (b, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((rows, HD), lambda b, pr, bd: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, cp * page_size, HD), k_pool.dtype),
            pltpu.VMEM((2, cp * page_size, HD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, cp)),
            pltpu.SemaphoreType.DMA((2, cp)),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, HD), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel,
        num_q_heads=Hq,
        page_size=page_size,
        pages_per_chunk=cp,
        q_block=qb,
        scale=scale,
        window=window,
    )
    out_wide = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S * Hq, HD), q.dtype),
        interpret=interpret,
    )(page_row, bounds, qx, k_pages, v_pages)
    if diff:
        return out_wide.reshape(
            S, Hq, Hkv // 2, 2 * D)[:, jnp.arange(Hq), pair_of_q]
    return out_wide.reshape(S, Hq, Hkv, D)[:, jnp.arange(Hq), kv_of_q]
