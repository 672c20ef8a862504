"""Attention ops — XLA reference implementations.

These einsum formulations are the portable baseline: they run on CPU (tests)
and TPU, and XLA already fuses mask+softmax+matmul chains well on the MXU.
The Pallas kernels in ops/pallas/ override them on TPU for the flash
(prefill) and paged (decode) paths; this module is the numerics ground truth
those kernels are tested against.

Two forms live here.  `causal_attention` attends a materialised K/V window
in one shot: prefill chunks, speculative verify, the contiguous cache.
`paged_decode_walk` is the decode read of a paged pool (one query a lane):
it never builds a lane's static window but walks the live context in chunks
of `DECODE_WALK_KEYS` keys, folding each into a running softmax, and on one
device contracts a chunk on the pool row's merged Hkv*D axis so that no
K/V is re-laid out between the page gather and the matmuls.  Where the
lanes' page tables open on the same pages (`common_pages`: a prefix attached
to all of them) those trips read the pages once for every lane.

Layout convention throughout the framework: activations are
[batch, seq, heads, head_dim] ("BSHD") — the layout that shards naturally
over a ("dp", "tp") mesh with heads on "tp".
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax.numpy as jnp
import jax


NEG_INF = -1e30  # large-negative mask value; -inf breaks softmax when a row is fully masked

# Keys one trip of `paged_decode_walk` attends.  Chosen on the chip at
# Mixtral's geometry (scripts/paged_decode_bench.py --backend xla; PERF.md
# section 6, PR 32): 1.03 ms a call at 512 against 1.11 at 256, 1.36 at
# 1,024 and 1.50 at 2,048; up to 2,048 XLA keeps the gathered chunk in
# VMEM, at 4,096 it goes through HBM (3.49 ms).
DECODE_WALK_KEYS = 512


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Expand KV heads for GQA: [B, S, Hkv, D] -> [B, S, Hkv*n_rep, D]."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d))
    return x.reshape(b, s, h * n_rep, d)


def causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    kv_valid: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Masked scaled-dot-product attention with GQA.

    q: [B, Sq, Hq, D]   k/v: [B, Skv, Hkv, D]
    q_positions: [B, Sq] absolute position of each query token
    kv_positions: [B, Skv] absolute position of each kv slot
    kv_valid: [B, Skv] bool — False for empty cache slots/padding
    Causality: a query at position p attends kv slots with position <= p.
    window (static): a sliding-window layer also drops positions <= p - window,
    so a query reads `window` keys, its own included (HF's sliding mask).
    Works for prefill (Sq == Skv), chunked prefill, and decode (Sq == 1)
    against a longer cache.
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    n_rep = hq // hkv
    if scale is None:
        scale = d**-0.5

    # Grouped GQA formulation: fold the repeat factor into the einsum batch
    # dims instead of materializing n_rep copies of K/V (repeat_kv would
    # stream the whole KV window through HBM n_rep times per layer).  The
    # matmuls take bf16 inputs with f32 accumulation (the MXU-native mode);
    # only the [.., Sq, Skv] score tensor is ever f32.
    qg = q.reshape(b, sq, hkv, n_rep, d)
    # [B, Hkv, G, Sq, Skv]
    logits = (
        jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32)
        * scale
    )

    mask = q_positions[:, None, None, :, None] >= kv_positions[:, None, None, None, :]
    if window is not None:
        mask = mask & (
            kv_positions[:, None, None, None, :]
            > q_positions[:, None, None, :, None] - window
        )
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def decode_walk_pages(max_pages: int, page_size: int) -> int:
    """Pages a trip of `paged_decode_walk` gathers per lane: DECODE_WALK_KEYS
    keys' worth, clamped to the page table's width (tiny test geometries)."""
    return max(1, min(DECODE_WALK_KEYS // page_size, max_pages))


def decode_walk_trips(seq_lens: jnp.ndarray, active: jnp.ndarray,
                      chunk_keys: int) -> jnp.ndarray:
    """Chunks `paged_decode_walk` visits: enough for the longest ACTIVE
    lane's seq_lens + 1 keys (its own new row included); 0 with no lane
    active.  The engine counts the same bound on the host
    (StepPrograms.decode_keys)."""
    n_keys = jnp.max(jnp.where(active, seq_lens + 1, 0))
    return (n_keys + chunk_keys - 1) // chunk_keys


def common_pages(table: jnp.ndarray, held: jnp.ndarray):
    """(lane, common): the first lane of `held` [B] bool, and how many of
    page table `table`'s [B, P] leading columns name that lane's page in
    EVERY held lane (a prefix attached to all of them: the same physical
    pages in the same columns).  A lane not held (idle, its row on the trash
    page; still prefilling) does not end the run; a lane shorter than the
    others ends it past its own last page, where its columns differ."""
    lane = jnp.argmax(held)
    same = jnp.all((table == table[lane][None, :]) | ~held[:, None], axis=0)
    cols = jnp.arange(same.shape[0], dtype=jnp.int32)
    return lane, jnp.min(jnp.where(same, same.shape[0], cols))


def shared_walk_trips(lanes, steps: int, P: int, cp: int, ck: int):
    """The SHARED trips of each of `steps` decode steps of a walk in trips
    of `cp` pages (`ck` keys) over a page table `P` pages wide, by the
    device's own arithmetic (`common_pages`, whole trips of it, within the
    trips the longest lane needs) on the host: `lanes` [(pages, tokens held
    before the first step)] of the dispatch's active lanes.  Plain ints,
    one a step."""
    if not lanes:
        return [0] * steps
    # the rows part where their lexicographic extremes part; rows alike to
    # the end are alike in the trash columns behind them too
    lo, hi = min(p for p, _ in lanes), max(p for p, _ in lanes)
    common = P if lo == hi else next(
        (j for j, (a, b) in enumerate(zip(lo, hi)) if a != b),
        min(len(lo), len(hi)))
    longest = max(n for _, n in lanes)
    return [min(common // cp, -(-(longest + i + 1) // ck), -(-P // cp))
            for i in range(steps)]


def paged_decode_walk(
    q: jnp.ndarray,
    read_pages: Callable[[jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]],
    page_table: jnp.ndarray,
    seq_lens: jnp.ndarray,
    active: jnp.ndarray,
    *,
    page_size: int,
    num_kv_heads: int,
    window: Optional[int] = None,
    heads_batched: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Decode attention over a paged pool without materialising the window.

    q: [B, Hq, D], one query a lane at position seq_lens[b] (its own K/V row
    already in the pool).  page_table [B, P], seq_lens [B], active [B] bool.
    read_pages(pages [B, cp]) -> (k, v), each [B, cp * page_size, Hkv*D]:
    the pool rows of those pages in the activation dtype, heads merged in
    the minor axis as the pool stores them.

    A loop whose trip count is computed on the device,
    ceil(max over active lanes of (seq_lens + 1) / chunk keys), slices the
    page table chunk by chunk, gathers that chunk's pages and folds it into
    a running max / sum / accumulator in f32 (the online softmax of the
    Pallas kernels' walk; probabilities are cast to the value dtype before
    the weighted sum, as `causal_attention` does).  A lane attends
    positions <= seq_lens (and > seq_lens - window in a sliding-window
    layer) if active; a lane with nothing to attend returns zeros.

    The walk splits where the lanes' page tables part (`common_pages` over
    the active lanes: a prefix attached to all of them).  A trip whose pages
    every active lane names reads them ONCE, from the first active lane's
    row (read_pages of [1, cp] pages -> [1, ck, Hkv*D]), and folds them into
    the same carry for all lanes in one product, on one device
    [B*Hq, Hkv*D] x [Hkv*D, ck]; whole trips only, and each lane's own mask
    as in any trip, so nothing rests on the shared pages being full.  From
    there on, and from trip 0 where the lanes share nothing, a trip gathers
    each lane's own pages.  The same chunks in the same order either way.

    The contraction adapts to where heads live.  On one device
    (`heads_batched` False) q is expanded block-diagonally to
    [B, Hq, Hkv*D] (zeros in the other kv heads' lanes) and both matmuls
    contract / produce the merged axis: Hkv x the useful FLOPs of a
    bandwidth-bound read, and no transpose of K or V.  On a mesh heads are
    the sharded axis and stay a batch dimension of the grouped GQA einsum
    (a merged-axis contraction would all-reduce over tp).
    Returns [B, Hq, D] in q's dtype.
    """
    b, hq, d = q.shape
    hkv = num_kv_heads
    g = hq // hkv
    if scale is None:  # (a published softmax scale: `attention_multiplier`)
        scale = d**-0.5
    cp = decode_walk_pages(page_table.shape[1], page_size)
    ck = cp * page_size
    # the first trip that gathers lane by lane: whole trips of the pages
    # every active lane names come before it (a lone lane shares them all)
    lane, common = common_pages(page_table, active)
    # whole chunks: the padding names the trash page, and its positions lie
    # past every seq_len, so the mask drops them
    page_table = jnp.pad(page_table, ((0, 0), (0, -page_table.shape[1] % cp)))
    n_chunks = page_table.shape[1] // cp
    trips = jnp.minimum(decode_walk_trips(seq_lens, active, ck), n_chunks)
    own = jnp.minimum(common // cp, trips)

    if heads_batched:
        qe = q.reshape(b, hkv, g, d)
        sc, wt, row = "bhgd,{}khd->bhgk", "bhgk,{}khd->bhgd", (hkv, d)
        lead, acc_shape = (b, hkv, g), (b, hkv, g, d)
    else:
        mine = jnp.arange(hq)[:, None] // g == jnp.arange(hkv)[None, :]
        qe = jnp.where(mine[None, :, :, None], q[:, :, None, :], 0).reshape(
            b, hq, hkv * d)
        sc, wt, row = "bnh,{}kh->bnk", "bnk,{}kh->bnh", (hkv * d,)
        lead, acc_shape = (b, hq), (b, hq, hkv * d)
    expand = (slice(None),) + (None,) * (len(lead) - 1)

    def fold(rows, lanes):
        """A trip over `rows`: every lane's page-table row ([B, P], `lanes`
        "b": each lane's scores against its own chunk), or the one row
        [1, P] all of them read (""): ONE chunk, and every lane's heads
        against it in one product.  The same masks and running softmax."""
        def trip(c, carry):
            m, l, acc = carry
            k, v = read_pages(
                jax.lax.dynamic_slice_in_dim(rows, c * cp, cp, axis=1))
            if not lanes:
                k, v = k[0], v[0]
            k = k.reshape(k.shape[:-1] + row)
            v = v.reshape(v.shape[:-1] + row)
            pos = c * ck + jnp.arange(ck)[None, :]
            mask = (pos <= seq_lens[:, None]) & active[:, None]
            if window is not None:
                mask = mask & (pos > seq_lens[:, None] - window)
            mask = mask[expand]
            s = jnp.einsum(sc.format(lanes), qe, k,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
            l = alpha * l + jnp.sum(p, axis=-1)
            acc = alpha[..., None] * acc + jnp.einsum(
                wt.format(lanes), p.astype(v.dtype), v,
                preferred_element_type=jnp.float32)
            return m_new, l, acc
        return trip

    carry = jax.lax.fori_loop(
        0, own, fold(page_table[lane][None], ""),
        (jnp.full(lead, NEG_INF, jnp.float32), jnp.zeros(lead, jnp.float32),
         jnp.zeros(acc_shape, jnp.float32)))
    _, l, acc = jax.lax.fori_loop(own, trips, fold(page_table, "b"), carry)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    if not heads_batched:
        # each head's own D lanes of the merged accumulator (the others hold
        # its probabilities against other kv heads' values)
        out = jnp.sum(
            jnp.where(mine[None, :, :, None], out.reshape(b, hq, hkv, d), 0.0),
            axis=2)
    return out.reshape(b, hq, d).astype(q.dtype)
