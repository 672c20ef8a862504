"""Cache driver of Olmo-Hybrid-7B's check: `drivers/phi4flash_pool.py`'s
prefill chunk and decode step (the program's `forward` over the pools and the
STATE SLOTS the engine allocates, with the index plan and the state plan
built the way `runtime/step_programs.py` builds them), over the Gated
DeltaNet slot: the three convolutions' tails and S itself, [d_k, heads x d_v].

The prompt is prefilled in launches of at most LAUNCH rows, as the engine
cuts a long suffix, each in a bucket of whole 64-row chunks: full launches
through the lane's slot (the first from zeros), then what is left but the
last LAST rows, PADDED where it does not fill its bucket, which writes a
SNAPSHOT on a page boundary and NOT the lane's slot; then the last LAST rows
(a page) in the smallest bucket, 48 of its 64 rows idle, resumed from that
snapshot into the lane's slot, as a prefix hit restores one.  At 1,536 rows:
512, 512, 496 of 512, 16 of 64.  Decode runs in the lane's slot.  So both
state leaves cross launch boundaries through the slot and through a snapshot,
padded chunks (the state after the last REAL row is what must be written)
and the prefill-to-decode boundary, the chunk kernel hands over to the step
kernel, and a program that read the lane's own slot at the last launch's
first row would read a state 496 rows old (`references/olmohybrid.py`'s
variants `state_lost_at_snapshot` / `conv_tail_zeroed_at_snapshot` /
`state_transposed_at_snapshot` are what the check must fail).  Nothing is
routed, so nothing is forced.

What the logits cannot show is checked on the slots themselves: after the
last decode step the lane's slot must hold float32 leaves, written in EVERY
linear layer, and most values of S must need float32 to be written
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
_reference = _sibling("references", "olmohybrid")

LANE, TRASH, SNAPSHOT, N_SLOTS = (_base.LANE, _base.TRASH, _base.SNAPSHOT,
                                  _base.N_SLOTS)
prefill_chunk, decode_step = _base.prefill_chunk, _base.decode_step
LAST = _reference.LAST  # rows of the launch that resumes from the snapshot
LAUNCH = 512            # the most rows a launch takes: the largest bucket


class GdnStateError(ValueError):
    """A Gated DeltaNet layer's state is not in its slot as the configuration
    states it: the logit check fails by name."""


# (the slot's report reads the leaves "conv" and "delta" by name: the delta
# layout's, whatever the matrix's shape)
state_report = _sibling("drivers", "solaropen2_pool").state_report


def launches(n_prefill: int, page_size: int):
    """[(start, rows, src, dst, snap)] of the prompt's prefill launches."""
    first = n_prefill - LAST
    if first <= 0 or first % page_size:
        raise ValueError(f"the last launch starts at {first}: not a page "
                         "boundary inside the prompt")
    starts = list(range(0, first, LAUNCH))
    out = []
    for start in starts:
        rows = min(LAUNCH, first - start)
        # the launch ahead of the last leaves a snapshot and NOT the lane's
        # slot; the last resumes from it into the lane's slot
        ahead = start == starts[-1]
        out.append((start, rows, LANE if start else TRASH,
                    TRASH if ahead else LANE, SNAPSHOT if ahead else TRASH))
    return out + [(first, LAST, SNAPSHOT, LANE, TRASH)]


def served_logits(params, cfg, token_ids, n_prefill: int, *,
                  page_size: int = 16, pages_per_seq: int = 8):
    """prefill(n_prefill) in `launches`, then one decode step per remaining
    token.  float32 logits [1 + n_decode, V], as paged_step.served_logits."""
    from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

    ids = np.asarray(token_ids, np.int32)
    k_pool, v_pool = make_kv_pool_arrays(
        cfg, pages_per_seq + 1, page_size, state_slots=N_SLOTS)
    page_row = jnp.arange(1, pages_per_seq + 1, dtype=jnp.int32)
    pre = jax.jit(prefill_chunk, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    dec = jax.jit(decode_step, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    for start, n, src, dst, snap in launches(n_prefill, page_size):
        chunk = np.zeros(-(-n // 64) * 64, np.int32)
        chunk[:n] = ids[start:start + n]
        logits, k_pool, v_pool = pre(
            params, cfg, k_pool, v_pool, page_row, jnp.asarray(chunk),
            jnp.int32(start), jnp.int32(n), jnp.int32(src), jnp.int32(dst),
            jnp.int32(snap), page_size=page_size)
    out = [np.asarray(logits)]
    for i in range(n_prefill, len(ids)):
        lg, k_pool, v_pool = dec(
            params, cfg, k_pool, v_pool, page_row[None, :],
            jnp.asarray(ids[i:i + 1]), jnp.asarray([i], jnp.int32),
            jnp.asarray([True]), page_size=page_size)
        out.append(np.asarray(lg[0]))
    report = state_report(v_pool)
    print(f"olmohybrid_pool: state {report} after {len(ids) - n_prefill} "
          "decode steps", file=sys.stderr, flush=True)
    if not (report["float32"]
            and report["tails_written"] == report["layers"]
            and report["states_written"] == report["layers"]
            and report["state_f32_share"] >= 0.5):
        raise GdnStateError(
            f"the Gated DeltaNet state is not what the configuration states "
            f"(float32 slots, every layer's tail and S written, S "
            f"unrounded): {report}")
    return np.stack(out)
