"""Cache driver of a configuration with a recurrent state beside its pages
(phi-4-mini-flash-reasoning): `paged_step.py`'s prefill chunk and decode step
over the pools and the STATE SLOTS the engine allocates
(`runtime/kv_cache.make_kv_pool_arrays(..., state_slots=)`), with the index
plan AND the state plan built the way `runtime/step_programs.py` builds them.

The prompt is prefilled in TWO launches, both padded: all but its last TAIL
rows in one bucket, then those in a small one.  So the check crosses what
serving crosses: the state handed from one launch to the next through its
slot, the conv tail across the boundary, rows of a bucket past the chunk's
length, the sliding window across a chunk boundary and past its width, and
the second half run on the last real row only.  The second launch reads the
state from a SNAPSHOT slot the first wrote (as a prefix hit restores one)
and not from the lane's own; it starts on a page boundary, as every resumed
prefill of the engine does.  TAIL is short so that the state handed over is
48-95 rows old at the compared positions: a state 768 rows old has mostly
decayed and a wrong one would hide under bfloat16's error (my chip run 1,
PR 38: zeroed there it moved the logits by 0.017-0.029 against a served
error of 0.035-0.044).  Decode runs in the lane's slot.

The PRECISION the state is carried in is checked here, on the slot itself,
because the logits cannot show it: the configuration states a float32 state,
and the reference with a bfloat16 state moves the compared logits by
0.005-0.015 where the served bfloat16 activations move them by 0.035-0.044
(`references/phi4flash.py`), as it would move the state's own values by less
than those activations do.  After the last decode step the lane's slot must
hold float32 leaves, and most values of h must need float32 to be written
(`state_f32_share`): every step computes h in float32 from an exponential
and two products, so a value that bfloat16 could hold is a coincidence (one
in 65,536) unless something rounded the state on its way into the slot.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

LANE, TRASH, SNAPSHOT = 0, 1, 2  # state slots: [lanes | trash | snapshots]
N_SLOTS = 3
TAIL = 48  # rows of the second launch: three pages


class StatePrecisionError(ValueError):
    """The recurrent state is not carried in the float32 the configuration
    states: the logit check fails by name."""


def state_f32_share(v_pool, slot: int = LANE) -> float:
    """Of the nonzero values of h in state slot `slot`, all Mamba layers,
    the share that bfloat16 could NOT hold (some of the low 16 bits of the
    float32 set); 0.0 where a leaf is not float32 at all."""
    if any(v_pool[leaf].dtype != jnp.float32 for leaf in ("conv", "ssm")):
        return 0.0
    h = np.asarray(v_pool["ssm"][:, slot]).ravel()
    h = h[h != 0]
    return float(np.mean(h.view(np.uint32) & 0xFFFF != 0)) if h.size else 0.0


def _bucket(rows: int) -> int:
    """A bucket of whole 64-row q blocks (the flash kernel's) over `rows`,
    with at least one padded row."""
    return (rows // 64 + 1) * 64


def prefill_chunk(params, cfg, k_pool, v_pool, page_row, chunk, start,
                  chunk_len, src, dst, snap, *, page_size: int):
    """One prefill chunk of one sequence: `paged_step.prefill_chunk` with the
    state read from slot `src` (zeros at start 0) and written to `dst` and
    `snap`.  Returns (logits [V] of the last real row, k_pool, v_pool)."""
    from kafka_tpu.models.hybrid import StatePlan
    from kafka_tpu.models.llama import KVCache, forward
    from kafka_tpu.runtime.step_programs import prefill_plan

    positions, paged = prefill_plan(
        page_row, start, chunk_len, chunk.shape[0], page_size)
    paged = paged._replace(state=StatePlan(
        lens=chunk_len[None], src=src[None], dst=dst[None], snap=snap[None],
        fresh=(start == 0)[None]))
    logits, cache = forward(params, cfg, chunk[None, :], positions,
                            kv_cache=KVCache(k_pool, v_pool), paged=paged)
    return logits[0, 0], cache.k, cache.v


def decode_step(params, cfg, k_pool, v_pool, page_table, last_tokens,
                seq_lens, active, *, page_size: int):
    """One decode step of B lanes, lane i in state slot i."""
    from kafka_tpu.models.hybrid import StatePlan
    from kafka_tpu.models.llama import KVCache, forward
    from kafka_tpu.runtime.step_programs import decode_plan

    positions, paged = decode_plan(page_table, seq_lens, active, page_size)
    paged = paged._replace(state=StatePlan(lens=active.astype(jnp.int32)))
    logits, cache = forward(params, cfg, last_tokens[:, None], positions,
                            kv_cache=KVCache(k_pool, v_pool), paged=paged)
    return logits[:, 0], cache.k, cache.v


def served_logits(params, cfg, token_ids, n_prefill: int, *,
                  page_size: int = 16, pages_per_seq: int = 8,
                  tail: int = TAIL):
    """prefill(n_prefill) in two launches (all but the last `tail` rows, then
    those), then one decode step per remaining token; float32 logits
    [1 + n_decode, V], as paged_step.served_logits."""
    from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

    ids = np.asarray(token_ids, np.int32)
    k_pool, v_pool = make_kv_pool_arrays(
        cfg, pages_per_seq + 1, page_size, state_slots=N_SLOTS)
    page_row = jnp.arange(1, pages_per_seq + 1, dtype=jnp.int32)
    pre = jax.jit(prefill_chunk, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    dec = jax.jit(decode_step, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    if not 0 < tail < n_prefill or (n_prefill - tail) % page_size:
        raise ValueError(f"the second launch starts at {n_prefill - tail}: "
                         "not a page boundary inside the prompt")
    for start, n in ((0, n_prefill - tail), (n_prefill - tail, tail)):
        chunk = np.zeros(_bucket(n), np.int32)
        chunk[:n] = ids[start:start + n]
        last = start + n >= n_prefill
        # every launch but the last leaves a snapshot; the next resumes from
        # it, and the last writes the lane's own slot
        logits, k_pool, v_pool = pre(
            params, cfg, k_pool, v_pool, page_row, jnp.asarray(chunk),
            jnp.int32(start), jnp.int32(n),
            jnp.int32(SNAPSHOT if start else TRASH),
            jnp.int32(LANE if last else TRASH),
            jnp.int32(TRASH if last else SNAPSHOT), page_size=page_size)
    out = [np.asarray(logits)]
    for i in range(n_prefill, len(ids)):
        lg, k_pool, v_pool = dec(
            params, cfg, k_pool, v_pool, page_row[None, :],
            jnp.asarray(ids[i:i + 1]), jnp.asarray([i], jnp.int32),
            jnp.asarray([True]), page_size=page_size)
        out.append(np.asarray(lg[0]))
    share = state_f32_share(v_pool)
    print(f"phi4flash_pool: state_f32_share {share:.6f} after "
          f"{len(ids) - n_prefill} decode steps", file=sys.stderr, flush=True)
    if share < 0.5:
        raise StatePrecisionError(
            f"{share:.4f} of the lane's recurrent state needs float32 to be "
            "written: the configuration states a float32 state, and one "
            "carried in it reads 1.0000")
    return np.stack(out)
