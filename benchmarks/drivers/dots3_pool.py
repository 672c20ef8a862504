"""Cache driver of a configuration whose kinds of layer store different rows
(dots3-note-prev: full layers a latent row, a rotary tile and the indexer's
key; sliding layers a wider latent row and a tile): `paged_step.py`'s prefill
chunk and decode step over the pools the ENGINE allocates
(`runtime/kv_cache.make_kv_pool_arrays`: a pool pair per kind under one page
table), with the prompt prefilled the way the engine prefills it, in chunks
of its largest bucket.  So the check crosses what serving crosses: the key
selection of a chunk over the chunks before it, the window across a chunk
boundary, and the indexer keys read back from their pages.

THE WINDOW'S EDGE is held here, exactly, because no tolerance on bfloat16
logits can hold it (one key in 513 moves them by 0.003-0.06:
`references/dots3.py`).  After the logits are taken, the last decode step
and a one-token prefill launch are run again over pools in which ONE
position's rows, in every sliding layer, are poisoned (`edge_probes`): the
newest key outside the window (`sliding_window` keys behind the query) must
leave the logits as they were, bit for bit; the oldest key inside must move
them.  A window one key longer or shorter fails one of the two, and
`served_logits` raises, which `serve.py` reports as `correct` false.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

import paged_step

CHUNK = 512  # the configuration's largest prefill bucket


# a poisoned row: +-POISON in a fixed pattern of signs.  Against an absorbed
# query of a few units a value the score is in the hundreds, of either sign
# by head; over 64 heads and three layers some head's softmax is taken over
# by the poisoned key wherever the key is attended at all.
POISON = 4.0
MOVED = 1e-3  # relative RMS over the vocabulary that counts as "moved"


def _poison(pool, slot):
    """`pool` [layers of the kind, slots, width] with row `slot` of every
    layer replaced by the poison pattern."""
    width = pool.shape[-1]
    signs = jnp.where(jnp.arange(width) % 3 == 0, -POISON, POISON)
    return pool.at[:, slot, :].set(signs.astype(pool.dtype))


def edge_probes(run, k_pool, v_pool, kind: str, slot_of, query: int,
                window: int):
    """`run(k_pool, v_pool) -> logits [V]` of the query at position `query`.
    Poisons, in every layer of `kind`, the rows of one position at a time:
    `query - window` (outside: the logits may not move at all) and
    `query - window + 1` (the window's oldest key: they must).  Returns the
    two relative RMS distances; raises on either failure."""
    base = np.asarray(run(k_pool, v_pool), np.float64)
    dist = {}
    for name, pos in (("outside", query - window),
                      ("oldest_inside", query - window + 1)):
        k, v = dict(k_pool), dict(v_pool)
        k[kind] = _poison(k_pool[kind], slot_of(pos))
        v[kind] = _poison(v_pool[kind], slot_of(pos))
        got = np.asarray(run(k, v), np.float64)
        if not np.isfinite(got).all():
            raise AssertionError(f"window edge: non-finite logits with "
                                 f"position {pos} poisoned ({name})")
        dist[name] = float(np.sqrt(np.mean((got - base) ** 2))
                           / np.sqrt(np.mean(base ** 2)))
    if dist["outside"] != 0.0:
        raise AssertionError(
            f"window edge: query {query} reads position {query - window}, "
            f"{window} keys behind it and outside a window of {window} "
            f"(logits moved by {dist['outside']:.3g})")
    if dist["oldest_inside"] < MOVED:
        raise AssertionError(
            f"window edge: query {query} does not read position "
            f"{query - window + 1}, the oldest key of a window of {window} "
            f"(logits moved by {dist['oldest_inside']:.3g})")
    return dist


def served_logits(params, cfg, token_ids, n_prefill: int, *,
                  page_size: int = 16, pages_per_seq: int = 8):
    """prefill(n_prefill) in chunks of CHUNK, then one decode step per
    remaining token; float32 logits [1 + n_decode, V], as
    paged_step.served_logits."""
    from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

    ids = np.asarray(token_ids, np.int32)
    k_pool, v_pool = make_kv_pool_arrays(cfg, pages_per_seq + 1, page_size)
    page_row = jnp.arange(1, pages_per_seq + 1, dtype=jnp.int32)
    pre = jax.jit(paged_step.prefill_chunk, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    dec = jax.jit(paged_step.decode_step, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    size = min(CHUNK, n_prefill)
    for start in range(0, n_prefill, size):
        n = min(size, n_prefill - start)
        chunk = np.zeros(size, np.int32)
        chunk[:n] = ids[start:start + n]
        logits, k_pool, v_pool = pre(
            params, cfg, k_pool, v_pool, page_row, jnp.asarray(chunk),
            jnp.int32(start), jnp.int32(n), page_size=page_size)
    out = [np.asarray(logits[n - 1])]
    for i in range(n_prefill, len(ids)):
        lg, k_pool, v_pool = dec(
            params, cfg, k_pool, v_pool, page_row[None, :],
            jnp.asarray(ids[i:i + 1]), jnp.asarray([i], jnp.int32),
            jnp.asarray([True]), page_size=page_size)
        out.append(np.asarray(lg[0]))
    _hold_the_window(pre, dec, params, cfg, ids, k_pool, v_pool, page_row,
                     size, page_size)
    return np.stack(out)


def _hold_the_window(pre, dec, params, cfg, ids, k_pool, v_pool, page_row,
                     size: int, page_size: int) -> None:
    """The module docstring's two probes, in decode and in prefill, at the
    last position (every row up to it is written; both launches write the
    row they wrote before).  `pre` / `dec` are `served_logits`' programs,
    which donate their pools: each run is given a copy."""
    from kafka_tpu.models.config import WINDOWED

    window, last = cfg.sliding_window, len(ids) - 1
    if not window or last < window:
        return
    chunk = np.zeros(size, np.int32)
    chunk[0] = ids[last]

    def copy(pool):
        return jax.tree.map(jnp.copy, pool)

    def decode(k, v):
        return dec(params, cfg, copy(k), copy(v), page_row[None, :],
                   jnp.asarray(ids[last:]), jnp.asarray([last], jnp.int32),
                   jnp.asarray([True]), page_size=page_size)[0][0]

    def prefill(k, v):
        return pre(params, cfg, copy(k), copy(v), page_row, jnp.asarray(chunk),
                   jnp.int32(last), jnp.int32(1), page_size=page_size)[0][0]

    def slot_of(pos: int):
        return page_row[pos // page_size] * page_size + pos % page_size

    report = {name: edge_probes(run, k_pool, v_pool, WINDOWED, slot_of, last,
                                window)
              for name, run in (("decode", decode), ("prefill", prefill))}
    print("dots3_pool: window edge", json.dumps(
        {"window": window, "query": last, **report}), flush=True)
