"""Cache driver of Falcon-H1-34B's check: `drivers/phi4flash_pool.py`'s
prefill chunk and decode step (the program's `forward` over the pools and the
STATE SLOTS the engine allocates, with the index plan and the state plan
built the way `runtime/step_programs.py` builds them), over a model EVERY
layer of which holds pages AND a state slot: the attention rows of layer l
and the conv tail and SSD state of layer l ride one pool pytree under one
layer index.

The prompt is prefilled in TWO launches, both padded to whole 128-row SSD
chunks: all but its last TAIL rows in one bucket (16 idle rows at the check's
1,520), which leaves a SNAPSHOT on a page boundary and does not write the
lane's slot; then the TAIL rows (a page) in the smallest bucket (112 idle
rows), resumed from that snapshot (as a prefix hit restores one) into the
lane's slot.  Decode runs in the lane's slot.  So both state leaves cross a
launch boundary through a snapshot, padded chunks (the state after the last
REAL row is what must be written), and the prefill-to-decode boundary where
`ssd_chunk` hands over to `ssd_step`, in the same layers whose rows flash
prefill wrote and `paged_decode_attention` reads; a program that read the
lane's own slot at the second launch would read zeros
(`references/falconh1.py`'s variants `state_lost_at_chunk` /
`conv_tail_zeroed_at_chunk` are what the check must fail).

What the logits cannot show is checked on the slots themselves: after the
last decode step the lane's slot must hold float32 leaves, written in EVERY
layer, and most values of S must need float32 to be written
(`state_f32_share`): every step computes S in float32 from an exponential
and products, so a value that bfloat16 could hold is a coincidence unless
something rounded the state on its way into the slot.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _sibling(folder: str, name: str):
    """`benchmarks/<folder>/<name>.py`, loaded by its path."""
    spec = importlib.util.spec_from_file_location(
        f"{folder}_{name}", os.path.join(HERE, "..", folder, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_base = _sibling("drivers", "phi4flash_pool")
_reference = _sibling("references", "falconh1")

LANE, TRASH, SNAPSHOT, N_SLOTS = (_base.LANE, _base.TRASH, _base.SNAPSHOT,
                                  _base.N_SLOTS)
prefill_chunk, decode_step = _base.prefill_chunk, _base.decode_step
TAIL = _reference.TAIL  # rows of the second launch: a page
CHUNK = 128             # whole SSD chunks a launch (`mamba_chunk_size`)


class SsdStateError(ValueError):
    """A layer's SSD state is not in its slot as the configuration states it
    (float32, written in every layer, unrounded): the logit check fails by
    name."""


def state_report(v_pool) -> dict:
    """Of the lane's slot: whether both leaves are float32, how many layers
    hold a nonzero tail and a nonzero S, and of S's nonzero values the share
    that bfloat16 could NOT hold."""
    conv, ssd = v_pool["conv"], v_pool["ssd"]
    S = np.asarray(ssd[:, LANE], np.float32)
    values = S[S != 0]
    return {
        "float32": bool(conv.dtype == jnp.float32
                        and ssd.dtype == jnp.float32),
        "layers": int(ssd.shape[0]),
        "tails_written": int(np.sum(np.any(
            np.asarray(conv[:, LANE]) != 0, (1, 2)))),
        "states_written": int(np.sum(np.any(S != 0, (1, 2)))),
        "state_f32_share": float(np.mean(
            values.view(np.uint32) & 0xFFFF != 0)) if values.size else 0.0,
    }


def _bucket(rows: int) -> int:
    """A bucket of whole SSD chunks over `rows`, with at least one padded
    row."""
    return (rows // CHUNK + 1) * CHUNK


def served_logits(params, cfg, token_ids, n_prefill: int, *,
                  page_size: int = 16, pages_per_seq: int = 8,
                  tail: int = TAIL):
    """prefill(n_prefill) in two launches (all but the last `tail` rows, then
    those from the snapshot the first left), then one decode step per
    remaining token; float32 logits [1 + n_decode, V], as
    paged_step.served_logits."""
    from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

    ids = np.asarray(token_ids, np.int32)
    first = n_prefill - tail
    if first <= 0 or first % page_size:
        raise ValueError(f"the second launch starts at {first}: not a page "
                         "boundary inside the prompt")
    k_pool, v_pool = make_kv_pool_arrays(
        cfg, pages_per_seq + 1, page_size, state_slots=N_SLOTS)
    page_row = jnp.arange(1, pages_per_seq + 1, dtype=jnp.int32)
    pre = jax.jit(prefill_chunk, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    dec = jax.jit(decode_step, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    for start, n in ((0, first), (first, tail)):
        chunk = np.zeros(_bucket(n), np.int32)
        chunk[:n] = ids[start:start + n]
        # the first launch leaves a snapshot and NOT the lane's slot; the
        # second resumes from it into the lane's slot
        logits, k_pool, v_pool = pre(
            params, cfg, k_pool, v_pool, page_row, jnp.asarray(chunk),
            jnp.int32(start), jnp.int32(n),
            jnp.int32(SNAPSHOT if start else TRASH),
            jnp.int32(LANE if start else TRASH),
            jnp.int32(TRASH if start else SNAPSHOT), page_size=page_size)
    out = [np.asarray(logits)]
    for i in range(n_prefill, len(ids)):
        lg, k_pool, v_pool = dec(
            params, cfg, k_pool, v_pool, page_row[None, :],
            jnp.asarray(ids[i:i + 1]), jnp.asarray([i], jnp.int32),
            jnp.asarray([True]), page_size=page_size)
        out.append(np.asarray(lg[0]))
    report = state_report(v_pool)
    print(f"falconh1_pool: state {report} after {len(ids) - n_prefill} "
          "decode steps", file=sys.stderr, flush=True)
    if not (report["float32"]
            and report["tails_written"] == report["layers"]
            and report["states_written"] == report["layers"]
            and report["state_f32_share"] >= 0.5):
        raise SsdStateError(
            f"the SSD state is not what the configuration states (float32 "
            f"slots, every layer's tail and S written, S unrounded): "
            f"{report}")
    return np.stack(out)
