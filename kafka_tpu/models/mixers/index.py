"""The learned key selection of a latent layer (DeepSeek-V3.2's sparse
attention; `cfg.has_indexer`: dots3): the indexer's projections, its scores
over the live context walked off its own pool rows, the exact top-k without
a sort, and the read of the chosen rows; mixers/latent.py calls it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops.attention import common_pages
from ...ops.rope import apply_rope
from ..cache import PagedView, _read_pages
from ..config import ModelConfig
from ..quant import Params, _w


class LatentPathError(NotImplementedError):
    """An attention path that has no latent (MLA) form was reached by a
    latent-attention model.  The engine refuses such options when it is
    built (runtime/engine.py LatentAttentionUnsupported); this is the
    backstop for direct callers of `forward`."""


def _index_projections(x, c_q, lp: Params, cfg: ModelConfig, cos, sin):
    """The indexer's three projections (DeepSeek-V3.2's sparse attention),
    under `attn_index`: q^I [B, S, Hi, Di] from the query latent, the key k^I
    [B, S, Di] = layernorm(x W^I_k), ONE row a token, and the head weights
    w [B, S, Hi] in f32, the two score scales folded in.  Rotary on the
    first `qk_rope_head_dim` values of q^I and k^I, half-split pairs (never
    de-interleaved), the layer's own table."""
    dt = x.dtype
    hi, di, dr = cfg.index_n_heads, cfg.index_head_dim, cos.shape[-1] * 2
    with jax.named_scope("attn_index"):
        q_idx = jnp.einsum("bsr,rnd->bsnd", c_q, _w(lp, "wiq", dt))
        k32 = jnp.einsum("bsh,hd->bsd", x, _w(lp, "wik", dt),
                         preferred_element_type=jnp.float32)
        mu = jnp.mean(k32, axis=-1, keepdims=True)
        var = jnp.mean((k32 - mu) ** 2, axis=-1, keepdims=True)
        k_idx = ((k32 - mu) * jax.lax.rsqrt(var + cfg.rms_norm_eps)
                 * lp["ln_ik"].astype(jnp.float32)
                 + lp["ln_ik_b"].astype(jnp.float32)).astype(dt)
        q_idx = jnp.concatenate(
            [apply_rope(q_idx[..., :dr], cos, sin), q_idx[..., dr:]], axis=-1)
        k_idx = jnp.concatenate(
            [apply_rope(k_idx[..., None, :dr], cos, sin)[..., 0, :],
             k_idx[..., dr:]], axis=-1)
        w_idx = jnp.einsum("bsh,hn->bsn", x, _w(lp, "wiw", dt),
                           preferred_element_type=jnp.float32
                           ) * (hi ** -0.5 * di ** -0.5)
    return q_idx, k_idx, w_idx


def _index_scores(q_idx, w_idx, k_idx) -> jnp.ndarray:
    """I[t, s] = sum_j w[t, j] * relu(q^I[t, j] . k^I[s]) in f32.  q_idx
    [B, S, Hi, Di], w_idx [B, S, Hi] f32, k_idx [B, T, Di] -> [B, S, T];
    k_idx [T, Di] where every lane scores the SAME keys: one
    [B * S * Hi, Di] x [Di, T] product, the keys read once."""
    keys = "btd" if k_idx.ndim == 3 else "td"
    dots = jnp.einsum(f"bsnd,{keys}->bsnt", q_idx, k_idx,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bsn,bsnt->bst", w_idx, jax.nn.relu(dots))


# Bits of the threshold one trip of `_threshold` decides: 2**r - 1 counts a
# trip.  Chosen on the chip (my chip runs 1, 2, 5 and 6, PR 61; `_chosen_mask`
# alone, top_k 2,048, us a call over [32, 1, 32768] | [1, 512, 32768]): 1: 81.9
# | 943, 2: 78.9 | 1,090, and on the cell's own scores 1: 88.8, 2: 78.8, 4:
# 133.9; unrolled, no loop at all, 1: 78.8 | 900, 2: 71.7 | 1,000, 3: 87.5 |
# 1,351, 4: 126.5 | 2,285, 8 (the key search alone, 255 candidates on a
# leading axis): 2,766 | not run.  A trip is VPU-bound (1.2 us at one
# candidate, 2.3 at three, 9.3 at fifteen), so a wider trip's candidates cost
# what they save in trips: 2 gains 10 us a layer at decode and loses 147 a
# layer of a 512-row launch, and the cell makes a launch every nine passes.
# The unrolled forms cost a boot 10 s of tracing and compiling (`setup_s` 98
# -> 108).
PASS_BITS = 1


def _threshold(holds, rows: tuple, n_bits: int, dtype) -> jnp.ndarray:
    """The largest number of `n_bits` bits that `holds`, one a row, [*rows]:
    `holds(c)` [*rows] is true of every c up to it and of none past it.  Built from the top, `PASS_BITS` bits a trip of one loop: a trip
    asks of each candidate that extends the prefix found so far by one digit
    (a count a candidate, siblings over one read of the rows), and the digit
    is how many of them hold.  The bits `PASS_BITS` does not divide go
    first."""
    def step(acc, shift, n):
        return acc | (sum(holds(acc | (dtype(d) << shift)).astype(dtype)
                          for d in range(1, n + 1)) << shift)

    r, acc = PASS_BITS, jnp.zeros(rows, dtype)
    trips, short = divmod(n_bits, r)
    if short:
        acc = step(acc, n_bits - short, (1 << short) - 1)
    return jax.lax.fori_loop(0, trips, lambda i, acc: step(
        acc, ((trips - 1 - i) * r).astype(dtype), (1 << r) - 1), acc)


def _chosen_mask(scores: jnp.ndarray, mask: jnp.ndarray,
                 top_k: int) -> jnp.ndarray:
    """`mask` [B, S, T] narrowed to each query's chosen keys: of the keys it
    allows, the `top_k` of largest score (all of them where it allows no
    more), EXACTLY the set `lax.top_k` picks, ties to the lower position.

    No sort: XLA's top-k of 2,048 among 32,768 sorts the whole row (4.1 ms
    a layer a decode pass, 17 ms a 512-row prefill launch: my chip run 2,
    PR 33).  The scores become unsigned keys of the same order; the k-th
    largest key is the largest candidate that `key >= candidate` still
    counts k times (`_threshold`: 32 counts over the rows, each waiting for
    the one before), then the lowest positions among the keys EQUAL to it
    fill what is left of k, by the same search over the position's bits (15
    at T = 32,768).  With one query a lane (decode) the lanes are the rows,
    [B, T]: held [B, 1, T] across the loops, a row took a tile to itself,
    one sublane of eight, and a count 10 us where it takes 1.2 (566 us a
    call against 82: my chip run 5, PR 61)."""
    shape, t = mask.shape, mask.shape[-1]
    if t <= top_k:
        return mask  # every allowed key is chosen
    if shape[-2] == 1:
        scores, mask = scores.reshape(-1, t), mask.reshape(-1, t)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores).astype(jnp.float32), jnp.int32)
    keys = jax.lax.bitcast_convert_type(
        bits ^ ((bits >> 31) & 0x7FFFFFFF), jnp.uint32) ^ jnp.uint32(1 << 31)
    keys = jnp.where(mask, keys, jnp.uint32(0))  # under every real score
    k = jnp.minimum(jnp.sum(mask, axis=-1, dtype=jnp.int32), top_k)

    def count(hit):
        return jnp.sum(hit, axis=-1, dtype=jnp.int32)

    kth = _threshold(lambda c: count(keys >= c[..., None]) >= k,
                     k.shape, 32, jnp.uint32)
    above = keys > kth[..., None]
    equal = keys == kth[..., None]
    left = k - count(above)  # how many of the equal keys are chosen
    pos = jnp.arange(t, dtype=jnp.int32)
    # the position of the `left`-th equal key: the largest p with fewer than
    # `left` equal keys under it
    last = _threshold(lambda c: count(equal & (pos < c[..., None])) < left,
                      k.shape, max(t - 1, 1).bit_length(), jnp.int32)
    return (above | (equal & (pos <= last[..., None]) & (left > 0)[..., None])
            ).reshape(shape)


COMPACT_BLOCK = 128


def _compact_chosen(chosen: jnp.ndarray, values: jnp.ndarray, top_k: int):
    """(values [B, K], ok [B, K]): `values` [B, T] (int32, under 2**23) at
    the positions `chosen` [B, T] marks, in ascending position, K =
    min(top_k, T); `ok` is False past the last one where fewer than K are
    marked, and such an entry repeats the first value.

    No sort, no scatter and no gather of single elements (65k of them cost
    0.65 ms on the v5e, a binary search over a running count 10 ms a layer:
    my chip run 3, PR 33).  The row is cut in blocks of 128; an output
    slot's block is found by counting the blocks that end at or before it;
    ONE gather of whole 128-value rows brings each slot its block, in which
    every value is packed with its rank among the block's marked ones, and
    the slot takes the value whose rank is its own."""
    b, t = values.shape
    k, blk = min(top_k, t), COMPACT_BLOCK
    pad = -t % blk
    marked = jnp.pad(chosen, ((0, 0), (0, pad))).reshape(b, -1, blk)
    vals = jnp.pad(values, ((0, 0), (0, pad))).reshape(b, -1, blk)
    ones = marked.astype(jnp.int32)
    upto = jnp.cumsum(ones, axis=-1)                 # within the block
    counts = upto[..., -1]                           # [B, blocks]
    ends = jnp.cumsum(counts, axis=-1)
    slots = jnp.arange(k, dtype=jnp.int32)
    before = ends[:, None, :] <= slots[None, :, None]    # [B, K, blocks]
    block_of = jnp.minimum(jnp.sum(before, axis=-1, dtype=jnp.int32),
                           marked.shape[1] - 1)
    rank = slots[None, :] - jnp.sum(
        jnp.where(before, counts[:, None, :], 0), axis=-1)
    # (value, rank among the block's marked ones | 255 where unmarked)
    packed = (vals << 8) | jnp.where(marked, upto - ones, 255)
    rows = jnp.take_along_axis(packed, block_of[..., None], axis=1)
    out = jnp.sum(jnp.where((rows & 255) == rank[..., None], rows >> 8, 0),
                  axis=-1)
    ok = slots[None, :] < ends[:, -1:]
    return jnp.where(ok, out, out[:, :1]), ok


# Keys one trip of the paged index scoring reads (fewer at many queries:
# `_walk_chunks`): [Hi, S, keys] f32 scores are held a trip, not a window.
INDEX_WALK_KEYS = 2048


def walk_pages(P: int, ps: int, keys: int, queries: int = 1) -> int:
    """Pages a trip of a walk over a page table of width P reads: about
    `keys` keys, fewer where `queries` rows would make a trip's f32 scores
    large.  Plain ints: the engine counts trips with it on the host."""
    keys = max(ps, min(keys, (1 << 19) // max(queries, 1)))
    return max(1, min(keys // ps, P))


def _walk_chunks(paged: "PagedView", cp: int):
    """(padded page table, trips): a walk over the page table's LIVE part
    in chunks of `cp` pages (`walk_pages`), up to the longest lane's last
    valid key: the bound is computed on the device."""
    ps = paged.page_size
    P = paged.page_table.shape[1]
    table = jnp.pad(paged.page_table, ((0, 0), (0, -P % cp)))
    n_keys = jnp.max(jnp.sum(paged.kv_valid, axis=-1))
    trips = jnp.minimum((n_keys + cp * ps - 1) // (cp * ps),
                        table.shape[1] // cp)
    return table, trips


def _common_pages(paged: "PagedView"):
    """`common_pages` over the lanes that hold keys: (the first of them, the
    page table's leading columns that name its page in every one)."""
    return common_pages(paged.page_table, jnp.sum(paged.kv_valid, axis=-1) > 0)


def _paged_index_scores(q_idx, w_idx, i_cache, paged: "PagedView",
                        dt) -> jnp.ndarray:
    """Index scores of every query against the lanes' live keys, f32
    [B, S, C], walked chunk by chunk off the indexer's own pool rows (keys
    past the longest live context stay unscored, 0).

    At decode (S = 1) the walk splits where the lanes' page tables part
    (`_common_pages`): a trip whose pages every lane shares reads them ONCE
    and scores all lanes against them in one product (an indexer key is
    rotated by position, not by lane), whole trips only; from there on, and
    from trip 0 where the lanes share nothing, a trip gathers each lane's
    own pages.  The same scores either way, in the same places.  A prefill
    chunk (S > 1) is one lane's rows against its own keys and never splits."""
    ps = paged.page_size
    b, s = q_idx.shape[:2]
    di = q_idx.shape[-1]
    C = paged.kv_positions.shape[1]
    cp = walk_pages(paged.page_table.shape[1], ps, INDEX_WALK_KEYS,
                    b * s if s > 1 else 1)
    table, trips = _walk_chunks(paged, cp)

    def score(rows):
        """A trip over `rows`: every lane's page-table row [B, P], or the
        one row [P] all of them share."""
        def trip(c, scores):
            pages = jax.lax.dynamic_slice_in_dim(rows, c * cp, cp, axis=-1)
            keys = _read_pages(i_cache, pages, ps, dt)[..., :di]
            return jax.lax.dynamic_update_index_in_dim(
                scores, _index_scores(q_idx, w_idx, keys), c, 0)
        return trip

    # held trip-major while the walk runs: a trip's scores land in one
    # block (as a slice of [B, S, C]'s key axis they are B x S strided rows,
    # 15 us a trip at decode on the v5e: twice the product that makes them)
    scores = jnp.zeros((table.shape[1] // cp, b, s, cp * ps), jnp.float32)
    own = 0  # the first trip that gathers lane by lane
    if s == 1:
        lane, common = _common_pages(paged)
        own = jnp.minimum(common // cp, trips)
        scores = jax.lax.fori_loop(0, own, score(table[lane]), scores)
    scores = jax.lax.fori_loop(own, trips, score(table), scores)
    return jnp.moveaxis(scores, 0, 2).reshape(b, s, -1)[..., :C]


def _paged_index_choice(q_idx, w_idx, i_cache, paged: "PagedView", positions,
                        cfg: ModelConfig, dt, as_mask: bool = False):
    """The selection step over a paged pool: `_paged_index_scores`, then
    the exact top-k of each query's causal keys.  Returns for decode (S = 1)
    the chosen keys' pool slots and which of them are real, (slots [B, K],
    ok [B, 1, K]); with `as_mask` (None, chosen [B, S, C]) for a walk that
    masks."""
    scores = _paged_index_scores(q_idx, w_idx, i_cache, paged, dt)
    mask = (paged.kv_valid[:, None, :]
            & (paged.kv_positions[:, None, :] <= positions[:, :, None]))
    chosen = _chosen_mask(scores, mask, cfg.index_topk)
    if as_mask:
        return None, chosen
    # decode: the chosen keys' pool slots (read_idx names every position's)
    if i_cache.shape[0] >= 1 << 23:
        raise LatentPathError(
            "a pool of 2**23 slots or more a kind (slots are packed with "
            "their ranks in 32 bits when the chosen keys are compacted)")
    slots, ok = _compact_chosen(chosen[:, 0], paged.read_idx, cfg.index_topk)
    return slots, ok[:, None]


def _read_chosen_rows(k_cache, v_cache, slots, dt):
    """The flat pools' rows at the chosen keys' slots [B, K] (decode):
    (c~ [B, K, r], k_r [B, K, lanes])."""
    return k_cache[slots].astype(dt), v_cache[slots].astype(dt)
