"""Cache driver of a latent-attention configuration whose attention leaves
and pools are per KIND of layer with ONE kind and no indexer (Xing4.0-29B-A4B:
a query low-rank makes the model `by_kind`): `paged_step.py`'s prefill chunk
and decode step over the pools the ENGINE allocates
(`runtime/kv_cache.make_kv_pool_arrays`: a dict of one pool pair), with the
prompt prefilled the way the engine prefills it, in chunks of its largest
bucket, so a chunk's queries walk the rows of the chunks before it through
`_latent_prefill_walk` and every compared decode step reads 4.6k rows through
the absorbed decode kernel, past the rotation's original context.

`drivers/dots3_pool.py` is that loop plus the window-edge probes; with no
windowed kind it returns from them without a word, and the check of a model
with nothing to probe should not print as if something had been held.  The
widened residual stream never leaves `forward`, so nothing here knows of it.

TEACHER-FORCED PICKS (`drivers/lfm2_pool.py`'s way; `references/xing4.py`
says why this model needs it).  All but the prompt's last RUN_IN + 1 rows go
out in launches of CHUNK rows on the program's own picks; from there on every
row is a launch of its own, the run-in and the first compared row as
one-row prefill launches and the rest as decode steps, and each is handed,
through the selection bias (a leaf of [routed layers, experts] that chooses
and does not weigh), the experts the reference's float32 pass takes at that
row: FORCE is added to their entries for that one launch.  The program, its
compiled steps, its scores and its weights are the served ones; only WHICH
four of 64 such a row takes is the reference's.  A program that did not read
the bias would not be forced and fails as `bias_ignored_in_choice` does.  In
float32 the program's own picks ARE the reference's and forcing changes
nothing (`tests/test_xing4.py`).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

import named
import paged_step

_reference = named.load(
    (os.path.dirname(os.path.dirname(os.path.abspath(__file__))),),
    "references", "xing4")

CHUNK = 512  # the configuration's largest prefill bucket
ROW = 64     # the bucket a launch of one row takes: its smallest
RUN_IN = _reference.RUN_IN  # rows a row a launch ahead of the compared one
# added to a picked expert's selection bias: sigma is at most 1 and the
# seeded bias N(0, 0.1^2), so a lifted expert outranks every other
FORCE = 4.0


def forced(params, picks):
    """`params` with the experts `picks` [routed layers, k] lifted by FORCE
    in every routed layer's selection bias: the tree of a launch whose one
    real row takes them."""
    bias = params["layers"]["router_bias"]
    lift = jnp.zeros_like(bias).at[
        jnp.arange(bias.shape[0])[:, None], jnp.asarray(picks)].set(FORCE)
    return dict(params, layers=dict(params["layers"],
                                    router_bias=bias + lift))


def served_logits(params, cfg, token_ids, n_prefill: int, *,
                  page_size: int = 16, pages_per_seq: int = 8,
                  force: bool = True, picks=None):
    """prefill(n_prefill): all but the last RUN_IN + 1 rows in chunks of
    CHUNK, then those a row a launch; then one decode step per remaining
    token.  Every launch of one row takes the experts `picks` [routed layers,
    S, k] names (the reference's own over these weights where None; `force`
    False: the program's).  float32 logits [1 + n_decode, V], as
    paged_step.served_logits."""
    from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

    ids = np.asarray(token_ids, np.int32)
    first = n_prefill - 1 - RUN_IN
    if first <= 0 or first % page_size:
        raise ValueError(f"the run-in starts at {first}: not a page boundary "
                         "behind a first launch")
    if force and picks is None:
        picks = _reference.reference_logits(
            params, _reference.hyper(cfg), ids, [n_prefill - 1])["picks"]

    def tree(row: int):
        return forced(params, picks[:, row]) if force else params

    k_pool, v_pool = make_kv_pool_arrays(cfg, pages_per_seq + 1, page_size)
    page_row = jnp.arange(1, pages_per_seq + 1, dtype=jnp.int32)
    pre = jax.jit(paged_step.prefill_chunk, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    dec = jax.jit(paged_step.decode_step, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    size = min(CHUNK, first)
    launches = [(start, min(size, first - start), size)
                for start in range(0, first, size)]
    launches += [(row, 1, min(ROW, size)) for row in range(first, n_prefill)]
    for start, n, rows in launches:
        chunk = np.zeros(rows, np.int32)
        chunk[:n] = ids[start:start + n]
        logits, k_pool, v_pool = pre(
            tree(start) if start >= first else params, cfg, k_pool, v_pool,
            page_row, jnp.asarray(chunk), jnp.int32(start), jnp.int32(n),
            page_size=page_size)
    out = [np.asarray(logits[0])]
    for i in range(n_prefill, len(ids)):
        lg, k_pool, v_pool = dec(
            tree(i), cfg, k_pool, v_pool, page_row[None, :],
            jnp.asarray(ids[i:i + 1]), jnp.asarray([i], jnp.int32),
            jnp.asarray([True]), page_size=page_size)
        out.append(np.asarray(lg[0]))
    return np.stack(out)
