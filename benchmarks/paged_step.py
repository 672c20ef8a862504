"""Prefill and decode steps of `kafka_tpu.models.forward` through a paged KV
pool, with the index plan built the way `runtime/engine.py` builds it
(`_get_prefill_fn`, `_decode_step_body`).  Used by `serve.py`'s logit check
on the served weights and by `rehearse_v5e.py`'s described-chip compiles, so
both drive the attention path the engine resolved (Pallas decode + flash
prefill, or the XLA page gather) and not the cache-less `forward`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def prefill_chunk(params, cfg, k_pool, v_pool, page_row, chunk, start,
                  chunk_len, *, page_size: int):
    """One prefill chunk of one sequence.  page_row [P], chunk [S] int32,
    start / chunk_len scalars.  Returns (logits [S, V] f32, k_pool, v_pool).
    """
    from kafka_tpu.models.llama import KVCache, PagedView, forward

    ps = page_size
    S, C = chunk.shape[0], page_row.shape[0] * ps
    local = jnp.arange(S)
    positions = (start + local)[None, :]
    in_chunk = local < chunk_len
    write_page = page_row[(start + local) // ps]
    write_idx = jnp.where(
        in_chunk, write_page * ps + (start + local) % ps, local % ps)[None, :]
    read_idx = (page_row[:, None] * ps + jnp.arange(ps)[None, :]).reshape(1, C)
    kv_positions = jnp.arange(C)[None, :]
    kv_valid = kv_positions < (start + chunk_len)
    paged = PagedView(write_idx, read_idx, kv_positions, kv_valid,
                      page_table=page_row[None, :], page_size=ps,
                      start=start, chunk_len=chunk_len)
    logits, cache = forward(params, cfg, chunk[None, :], positions,
                            kv_cache=KVCache(k_pool, v_pool), paged=paged)
    return logits[0], cache.k, cache.v


def decode_step(params, cfg, k_pool, v_pool, page_table, last_tokens,
                seq_lens, active, *, page_size: int):
    """One decode step of B lanes.  page_table [B, P], last_tokens / seq_lens
    [B] int32, active [B] bool.  Returns (logits [B, V] f32, k_pool, v_pool).
    """
    from kafka_tpu.models.llama import KVCache, PagedView, forward

    ps = page_size
    B, C = page_table.shape[0], page_table.shape[1] * ps
    positions = seq_lens[:, None]
    write_page = page_table[jnp.arange(B), seq_lens // ps]
    write_idx = (write_page * ps + seq_lens % ps)[:, None]
    write_idx = jnp.where(active[:, None], write_idx, (seq_lens % ps)[:, None])
    read_idx = (page_table[:, :, None] * ps
                + jnp.arange(ps)[None, None, :]).reshape(B, C)
    kv_positions = jnp.broadcast_to(jnp.arange(C)[None, :], (B, C))
    kv_valid = (kv_positions <= seq_lens[:, None]) & active[:, None]
    paged = PagedView(write_idx, read_idx, kv_positions, kv_valid,
                      page_table=page_table, seq_lens=seq_lens, page_size=ps)
    logits, cache = forward(params, cfg, last_tokens[:, None], positions,
                            kv_cache=KVCache(k_pool, v_pool), paged=paged)
    return logits[:, 0], cache.k, cache.v


def empty_pool(cfg, num_pages: int, page_size: int):
    """k/v pools [L, num_pages * page_size, Hkv*D] in the served dtype; page 0
    is the trash page inactive lanes scribble on, as in the engine."""
    shape = (cfg.num_layers, num_pages * page_size,
             cfg.num_kv_heads * cfg.head_dim)
    return (jnp.zeros(shape, cfg.activation_dtype),
            jnp.zeros(shape, cfg.activation_dtype))


def served_logits(params, cfg, token_ids, n_prefill: int, *,
                  page_size: int = 16, pages_per_seq: int = 8):
    """prefill(n_prefill) then one decode step per remaining token, through a
    small paged pool.  Returns float32 logits [1 + n_decode, V]: the last
    prefill position, then each decode position."""
    import numpy as np

    ids = np.asarray(token_ids, np.int32)
    k_pool, v_pool = empty_pool(cfg, pages_per_seq + 1, page_size)
    page_row = jnp.arange(1, pages_per_seq + 1, dtype=jnp.int32)
    pre = jax.jit(prefill_chunk, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    dec = jax.jit(decode_step, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    logits, k_pool, v_pool = pre(
        params, cfg, k_pool, v_pool, page_row, jnp.asarray(ids[:n_prefill]),
        jnp.int32(0), jnp.int32(n_prefill), page_size=page_size)
    out = [np.asarray(logits[n_prefill - 1])]
    for i in range(n_prefill, len(ids)):
        lg, k_pool, v_pool = dec(
            params, cfg, k_pool, v_pool, page_row[None, :],
            jnp.asarray(ids[i:i + 1]), jnp.asarray([i], jnp.int32),
            jnp.asarray([True]), page_size=page_size)
        out.append(np.asarray(lg[0]))
    return np.stack(out)
