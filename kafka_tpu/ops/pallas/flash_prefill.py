"""Flash prefill kernel over the paged KV pool.

The XLA prefill path materializes the full [Hq, S, C] score tensor per
layer — at an 8k window that is half a gigabyte of f32 per chunk per
layer.  This kernel streams the KV window in page chunks with online
softmax (flash attention), so peak memory is O(q_block x kv_chunk) and
HBM traffic is one pass over the valid window per q block.

Structure:

* merged-lane pool [TOTAL_SLOTS, Hkv*D] (the DMA lane-alignment contract);
  a chunk's pages are DMA'd whole, double-buffered, as in the decode kernel
  (paged_attention.py);
* GQA by LANE GROUP (lane_group): the KV heads that fill one 128-lane tile
  are multiplied together, one KV head at D = 128, two at D = 64.  A chunk
  step is, per group, `[q_block * heads of the group, 128] x [128, chunk]`
  against the lane slice of the chunk's rows and the same shape back
  through V; the running max / sum / accumulator are per (group, row).  At
  D = 128 no lane multiplied is a zero; below it the block-diagonal q
  expansion survives inside the 128 lanes only (the caller places each
  row's D lanes in its KV head's slot of the group).  Before PR 44 the
  expansion ran over the whole merged row: rows of Hkv*D lanes, (Hkv - 1)
  / Hkv of them zeros;
* q arrives as [S, Hq * group lanes], the heads side by side as the model
  has them; a q block's heads are stacked head-major into VMEM once a
  block (cast there, not once a chunk) and un-stacked into the output
  block at the end: at D = 128 no XLA op is left around the call;
* the loops over heads, lane groups and a chunk's pages are ROLLED (dynamic
  tile-aligned slices of lanes and rows): unrolled, the
  kernel's jaxpr was ten times the parent's and every warm boot paid for
  it in `jit.lower` (K-EXAONE's `setup_s` 75 -> 96 s; PERF.md section 6,
  PR 44);
* grid = (num_q_blocks,); per block, a dynamic fori_loop over the kv
  chunks the causal mask can reach (a q block early in the prompt skips
  the chunks after it entirely, a q block of the bucket's padding skips
  them all).  Every chunk takes the one guarded step (mask, select, V's
  junk rows zeroed).  A second, unmasked step for the chunks wholly under
  the block's first query was built and measured: 5-6% of the 512-row call
  over 28.7k keys at 64 / 8 x 128, 2-4% of the 2,048-row one over 9.4k at
  32 / 4 x 128, nothing under a window, and 0.15-0.4% of a cell's
  `tpot_p50_ms`, which no cell can show: not kept (PERF.md section 6,
  PR 44).

Causality: the engine writes the whole chunk's KV to the pool before
attention, so kv slots carry absolute positions page-order; a query at
absolute position p attends kv positions <= p, bounded by the written
total (start + chunk_len).

Precision: as the decode kernel's.  The MXU operands are the pool's dtype
(K and V as they lie in VMEM, q and the probabilities cast to match: q's
values are the caller's, `scale` multiplies the f32 scores);
scores, the softmax state and the accumulator are f32.  An f32 pool
multiplies in f32, a bf16 pool in bf16 — which is what Mosaic made of the
f32 operands this kernel used to cast them to (one bf16 pass: the output
is the same bit for bit, PERF.md section 6, PR 44).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # one lane tile

# Keys a softmax step attends, under a window too.  Measured alone on the chip
# (PERF.md section 6, PR 44): a lane group's step is one dependent chain
# (matmul, max, exp, sum, matmul) of ~1.65 us however few keys it holds, and a
# step's accumulator and softmax state are read and written once whatever it
# holds; at 64 / 8 x 128 over 28.7k keys the 512-row global call ran 24 ms
# at 128 keys a step, 12 at 256, 6.7 at 512 (Yi's 2,048 rows over 9.4k
# keys: 9.4 / 8.5 / 4.5), and the 128-key windowed call 0.34 ms at 128-key
# chunks, 0.24 at 512.
STEP_KEYS = 512

# Scoped VMEM the call is compiled with, and the part of it a q block's own
# state may take (q_block_rows): what the chip ran, not only compiled
# (PERF.md section 6, PR 44).
PREFILL_VMEM_LIMIT = 32 * 1024 * 1024
PREFILL_VMEM_BYTES = 12 * 1024 * 1024
# ... and the part the K / V chunk buffers may take (chunk_pages)
CHUNK_VMEM_BYTES = 8 * 1024 * 1024


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


def lane_group(num_kv_heads: int, head_dim: int, diff: bool = False) -> int:
    """KV heads a chunk step multiplies together: as many as fill one
    128-lane tile (one at D = 128, two at D = 64), so that a group's lane
    slice of the merged row is tile-aligned.  Differential attention reads
    both value heads of a pair, so its group is never under a pair.  A row
    that such groups do not divide (fewer lanes than a tile, an odd head
    count) is one group: the block-diagonal form over the whole row."""
    per = max(LANES // head_dim, 2 if diff else 1)
    if (per * head_dim) % LANES or num_kv_heads % per:
        return num_kv_heads
    return per


def q_block_rows(num_q_heads: int, group_lanes: int, itemsize: int) -> int:
    """The most query positions a q block may hold, from the scoped-VMEM
    bytes a position costs: its Hq rows of `group_lanes` lanes twice in the
    pipeline's q buffers and twice in its output buffers, once stacked in
    the pool's dtype, once as the f32 accumulator, and the running max and
    sum, which are a lane tile wide in VMEM whatever their shape says.  The
    [rows of a group, chunk] softmax temporaries, the K / V chunk buffers
    and Mosaic's own stack share what PREFILL_VMEM_BYTES leaves of the
    limit the call is compiled with (PREFILL_VMEM_LIMIT).  Rounded DOWN to
    a power of two, so that it divides the power-of-two chunk buckets for
    any head count, and never under 16 (a bf16 sublane tile)."""
    per_position = num_q_heads * (
        group_lanes * (5 * itemsize + 4) + 2 * LANES * 4)
    cap = max(16, PREFILL_VMEM_BYTES // per_position)
    return 1 << (cap.bit_length() - 1)


def _prefill_kernel(
    # scalar prefetch
    page_row_ref,   # [P] i32 physical pages of this sequence
    bounds_ref,     # [2] i32: (start, chunk_len)
    # inputs
    q_ref,          # [QB, Hq*GL] VMEM block: the heads side by side
    k_pages_hbm,    # [num_pages, ps, Hkv*D] ANY
    v_pages_hbm,    # [num_pages, ps, Hkv*D] ANY
    out_ref,        # [QB, Hq*GL] VMEM block
    # scratch
    kbuf, vbuf,     # [2, chunk, Hkv*D] pool dtype
    ksem, vsem,     # DMA sems [2]: a buffer's page copies all signal one
    qg_ref,         # [groups, heads of a group * QB, GL] operand dtype
    m_ref, l_ref,   # [groups, heads of a group * QB, 1] f32
    acc_ref,        # [groups, heads of a group * QB, GL] f32
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
    n_groups, rows, gl = qg_ref.shape
    gq = hq // n_groups  # query heads of a lane group, contiguous
    dt = qg_ref.dtype
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
    pools = ((k_pages_hbm, kbuf, ksem), (v_pages_hbm, vbuf, vsem))

    def dma(c, op):
        """Start or wait the page copies of chunk c, one scattered page each,
        into buffer c % 2: a loop over the pages the chunk has (the walk's
        last chunk alone can end short of cp), not a guard a page and not
        unrolled: the call sites are what `jit.lower` pays for at every boot
        (PR 30; PR 44 measured 32 unrolled pages a site)."""
        slot = jax.lax.rem(c, 2)
        base = c * cp

        def copy(j, carry):  # one scattered page, K and V
            # a wait needs the copy's size only, not where it came from
            page = page_row_ref[base + j] if op == "start" else 0
            for hbm, buf, sem in pools:
                cpy = pltpu.make_async_copy(
                    hbm.at[page],
                    buf.at[slot, pl.ds(pl.multiple_of(j * ps, ps), ps)],
                    sem.at[slot])
                getattr(cpy, op)()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(cp, n_pages - base), copy, 0)

    def head_lanes(h):  # head h's lanes of the q / out block
        return pl.ds(pl.multiple_of(h * gl, gl), gl)

    def head_rows(h):  # head h's rows of its group's stack
        return pl.ds(pl.multiple_of(jax.lax.rem(h, gq) * q_block, q_block),
                     q_block)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(first_chunk < n_chunks)
    def _():
        dma(first_chunk, "start")

        # the block's heads stacked head-major per lane group, cast to the
        # operands' dtype: once a block
        def stack(h, carry):
            qg_ref[h // gq, head_rows(h), :] = (
                q_ref[:, head_lanes(h)].astype(dt))
            return carry

        jax.lax.fori_loop(0, hq, stack, 0)

    # absolute q position of each stacked row (row = head * QB + q index)
    q0 = start + qb * q_block
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    q_pos = q0 + jax.lax.rem(row_ids, q_block)  # [rows, 1]

    def body(c, carry):
        """One online-softmax step over chunk c, a lane group at a time."""
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            dma(c + 1, "start")

        dma(c, "wait")

        def group(g, carry):
            # the masks are built here, once a group: held over the loop
            # instead, [rows, chunk] of them cost 1-3% (PERF.md section 6)
            col_ids = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
            kv_pos = c * chunk + col_ids  # [1, chunk]
            mask = (q_pos >= kv_pos) & (kv_pos < kv_hi)  # [rows, chunk]
            if window is not None:
                mask = mask & (kv_pos > q_pos - window)
            # column-shaped validity built directly (Mosaic cannot transpose a
            # boolean vector)
            col_iota = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            col_valid = col_iota < (kv_hi - c * chunk)
            lanes = pl.ds(pl.multiple_of(g * gl, gl), gl)
            kc = kbuf[slot, :, lanes].astype(dt)  # [chunk, GL]
            # zero junk V rows (never-DMA'd NaNs poison 0-weight matmuls);
            # selected in f32, as the kernel always has
            vc = jnp.where(col_valid,
                           vbuf[slot, :, lanes].astype(jnp.float32), 0.0)
            s = jax.lax.dot_general(
                qg_ref[g], kc,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rows, chunk]
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_ref[g] = l_ref[g] * alpha + jnp.sum(pexp, axis=-1,
                                                  keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
                pexp.astype(dt), vc.astype(dt),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[g] = m_new
            return carry

        # (two groups a trip, so that one's waits fill with the other's
        # work, ran 4-7% faster at 32 / 4 x 128 and 40 / 20 x 64 and 7%
        # slower at 64 / 8 x 128: one, the simpler)
        jax.lax.fori_loop(0, n_groups, group, 0)
        return carry

    jax.lax.fori_loop(first_chunk, n_chunks, body, 0)

    def unstack(h, carry):
        g, part = h // gq, head_rows(h)
        denom = jnp.maximum(l_ref[g, part, :], 1e-30)
        out_ref[:, head_lanes(h)] = (
            acc_ref[g, part, :] / denom).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, hq, unstack, 0)


def chunk_pages(page_size: int, row_bytes: int = 0) -> int:
    """Pages a KV chunk holds: STEP_KEYS keys, under a window too (a 128-key
    window's q block reaches one or two such chunks and copies four times
    the keys it attends, which costs less than the two or three short steps
    of 128-key chunks did: a lane group's step has a latency floor).  Over
    pool rows of `row_bytes` (0: not said) the keys are halved until the two
    K and two V buffers fit CHUNK_VMEM_BYTES: a merged row of 3,840 lanes
    (30 KV heads x 128) takes 256 keys a chunk, 7.9 MB, where 512 would be
    15.7 MB beside the q block's 12."""
    keys = STEP_KEYS
    while keys > 128 and 4 * keys * row_bytes > CHUNK_VMEM_BYTES:
        keys //= 2
    return max(1, keys // page_size)


def prefill_plan(S: int, num_q_heads: int, num_kv_heads: int, head_dim: int,
                 itemsize: int, *, diff: bool = False,
                 q_block: int | None = None) -> dict:
    """What paged_prefill_attention chooses for a chunk of S rows: KV heads
    a lane group, its lanes, and the q block (the largest power of two
    under q_block_rows that divides S; `q_block` takes its place)."""
    kvg = lane_group(num_kv_heads, head_dim, diff)
    gl = kvg * head_dim
    qb = min(q_block or q_block_rows(num_q_heads, gl, itemsize), S)
    while S % qb:
        if qb & (qb - 1) or qb <= 8:
            raise ValueError(
                f"chunk length {S} not divisible by q_block {qb}")
        qb //= 2
    return {"kv_heads_per_group": kvg, "group_lanes": gl, "q_block": qb,
            "lane_groups": num_kv_heads // kvg}


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
    pages_per_chunk: int | None = None,
    q_block: int | None = None,
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
    wholly below its first row's window.  `q_block`, `pages_per_chunk`: the
    q block and the KV chunk, where prefill_plan and chunk_pages are not to
    size them (tests, the bench).

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
    dt = jnp.promote_types(q.dtype, k_pool.dtype)
    plan = prefill_plan(S, Hq, Hkv, D, jnp.dtype(dt).itemsize, diff=diff,
                        q_block=q_block)
    kvg, gl, qb = (plan[k] for k in
                   ("kv_heads_per_group", "group_lanes", "q_block"))
    n_groups = Hkv // kvg
    cp = min(pages_per_chunk
             or chunk_pages(page_size, HD * k_pool.dtype.itemsize),
             page_row.shape[0])
    k_pages = k_pool.reshape(-1, page_size, HD)
    v_pages = v_pool.reshape(-1, page_size, HD)

    # the KV head each query head reads (static), and its slot in the group
    # (paged_attention.diff_heads' pairing, in numpy: under jit its arange
    # is a tracer, and the slots below index statically)
    heads = np.arange(Hq)
    kv_of_q = 2 * (heads // (2 * G)) + heads % 2 if diff else heads // G
    slot = kv_of_q % kvg
    assert (kv_of_q // kvg == heads // (Hq // n_groups)).all()
    if kvg > 1:
        # block-diagonal inside the group's lanes only: each row's D lanes
        # in its KV head's slot, zeros in the group's other slots
        onehot = jnp.asarray(slot[:, None] == np.arange(kvg)[None, :],
                             q.dtype)  # [Hq, kvg]
        q = q[:, :, None, :] * onehot[None, :, :, None]
    q = q.reshape(S, Hq * gl)
    bounds = jnp.stack([jnp.asarray(start, jnp.int32),
                        jnp.asarray(chunk_len, jnp.int32)])

    rows = qb * (Hq // n_groups)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S // qb,),
        in_specs=[
            pl.BlockSpec((qb, Hq * gl), lambda b, pr, bd: (b, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((qb, Hq * gl), lambda b, pr, bd: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, cp * page_size, HD), k_pool.dtype),
            pltpu.VMEM((2, cp * page_size, HD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((n_groups, rows, gl), dt),
            pltpu.VMEM((n_groups, rows, 1), jnp.float32),
            pltpu.VMEM((n_groups, rows, 1), jnp.float32),
            pltpu.VMEM((n_groups, rows, gl), jnp.float32),
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
        out_shape=jax.ShapeDtypeStruct((S, Hq * gl), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=PREFILL_VMEM_LIMIT),
        interpret=interpret,
    )(page_row, bounds, q, k_pages, v_pages)
    if diff:
        # each query row's result over BOTH value heads of its pair
        out = out_wide.reshape(S, Hq, kvg // 2, 2 * D)
        return out[:, :, 0] if kvg == 2 else out[:, heads, slot // 2]
    if kvg == 1:
        return out_wide.reshape(S, Hq, D)
    # each query row's result lives in its own kv head's slot of the group
    return out_wide.reshape(S, Hq, kvg, D)[:, heads, slot]
