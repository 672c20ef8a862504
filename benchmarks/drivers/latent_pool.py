"""Cache driver of a latent-attention (MLA) configuration: `paged_step.py`'s
prefill chunk and decode step over the pool the ENGINE allocates for the
configuration (`runtime/kv_cache.make_kv_pool_arrays`: the latent row in one
pool, the roped key part in the other, of different widths).
`paged_step.empty_pool` builds two pools of Hkv*D lanes each, which is not
this model's row; everything else of that file is used as it is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import paged_step


def served_logits(params, cfg, token_ids, n_prefill: int, *,
                  page_size: int = 16, pages_per_seq: int = 8):
    """prefill(n_prefill) then one decode step per remaining token; float32
    logits [1 + n_decode, V], as paged_step.served_logits."""
    from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

    ids = np.asarray(token_ids, np.int32)
    k_pool, v_pool = make_kv_pool_arrays(cfg, pages_per_seq + 1, page_size)
    page_row = jnp.arange(1, pages_per_seq + 1, dtype=jnp.int32)
    pre = jax.jit(paged_step.prefill_chunk, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    dec = jax.jit(paged_step.decode_step, static_argnums=(1,),
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
