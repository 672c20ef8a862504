"""Cache driver of Solar-Open2-250B's check: `drivers/phi4flash_pool.py`'s
prefill chunk and decode step (the program's `forward` over the pools and the
STATE SLOTS the engine allocates, with the index plan and the state plan
built the way `runtime/step_programs.py` builds them), over a state slot of a
third shape: the three convolutions' tails and a matrix a head.

The prompt is prefilled in 1 + RUN_IN launches, all padded: all but its last
RUN_IN rows in one bucket, which leaves a SNAPSHOT on a page boundary and
does not write the lane's slot; then the run-in, RUN_IN rows (a page) a row a
launch in the smallest bucket, the first resumed from that snapshot (as a
prefix hit restores one) into the lane's slot and the others from the lane's
own slot, as a prompt's later chunks are.  Decode runs in the lane's slot.
So both state leaves cross a launch boundary through a snapshot, padded
chunks of 16 and of 63 idle rows (the state after the last REAL row is what
must be written) and the prefill-to-decode boundary, the chunk kernel hands
over to the step kernel, and a program that read the lane's own slot at the
run-in's first row would read zeros (`references/solaropen2.py`'s variants
`state_lost_at_chunk` / `conv_tail_zeroed_at_chunk` are what the check must
fail).

TEACHER-FORCED PICKS, as `drivers/lfm2_pool.py`: every launch from the run-in
on is ONE real row wide, so the selection bias it is handed names that row's
experts: the driver asks the reference which experts its float32 pass takes
at that row and adds FORCE to their entries of the bias for that one launch.
The program, its scores and its weights are the served ones; only WHICH
eight of 320 such a row takes is the reference's.  The first launch's rows
keep their own picks: their swaps reach the compared rows through attention
and through a state that the run-in's 16 rows let fade (unforced, the check
read 0.06 at the first decode step and 0.04 at the last: my chip run 1, PR
50).

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
_reference = _sibling("references", "solaropen2")

LANE, TRASH, SNAPSHOT, N_SLOTS = (_base.LANE, _base.TRASH, _base.SNAPSHOT,
                                  _base.N_SLOTS)
prefill_chunk, decode_step = _base.prefill_chunk, _base.decode_step
RUN_IN = _reference.RUN_IN  # rows a row a launch on forced picks: a page
# what the chosen experts' entries of the selection bias gain: past any
# sigmoid (0..1) plus any seeded bias (N(0, 0.1^2))
FORCE = 4.0


class DeltaStateError(ValueError):
    """A linear-attention layer's state is not in its slot as the
    configuration states it: the logit check fails by name."""


def state_report(v_pool) -> dict:
    """Of the lane's slot: whether both leaves are float32, how many linear
    layers hold a nonzero tail and a nonzero S, and of S's nonzero values
    the share that bfloat16 could NOT hold."""
    conv, delta = v_pool["conv"], v_pool["delta"]
    S = np.asarray(delta[:, LANE], np.float32)
    values = S[S != 0]
    return {
        "float32": bool(conv.dtype == jnp.float32
                        and delta.dtype == jnp.float32),
        "layers": int(delta.shape[0]),
        "tails_written": int(np.sum(np.any(
            np.asarray(conv[:, LANE]) != 0, (1, 2)))),
        "states_written": int(np.sum(np.any(S != 0, (1, 2)))),
        "state_f32_share": float(np.mean(
            values.view(np.uint32) & 0xFFFF != 0)) if values.size else 0.0,
    }


def forced(params, picks):
    """`params` with the experts `picks` [layers, k] lifted by FORCE in every
    layer's selection bias: the tree of a step whose one row takes them."""
    bias = params["layers"]["router_bias"]
    lift = jnp.zeros_like(bias).at[
        jnp.arange(bias.shape[0])[:, None], jnp.asarray(picks)].set(FORCE)
    return dict(params, layers=dict(params["layers"],
                                    router_bias=bias + lift))


def served_logits(params, cfg, token_ids, n_prefill: int, *,
                  page_size: int = 16, pages_per_seq: int = 8,
                  force: bool = True, picks=None):
    """prefill(n_prefill) as a first launch of all but RUN_IN rows, then
    those a row a launch (the first from the snapshot the first launch
    left), then one decode step per remaining token; every launch of one row
    takes the experts `picks` [layers, S, k] names (the reference's own over
    these weights where None; `force` False: the program's).  float32 logits
    [1 + n_decode, V], as paged_step.served_logits."""
    from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

    ids = np.asarray(token_ids, np.int32)
    first = n_prefill - RUN_IN
    if first <= 0 or first % page_size:
        raise ValueError(f"the run-in starts at {first}: not a page boundary "
                         "inside the prompt")
    if force and picks is None:
        picks = _reference.reference_logits(
            params, _reference.hyper(cfg), ids, [n_prefill - 1])["picks"]
    k_pool, v_pool = make_kv_pool_arrays(
        cfg, pages_per_seq + 1, page_size, state_slots=N_SLOTS)
    page_row = jnp.arange(1, pages_per_seq + 1, dtype=jnp.int32)
    pre = jax.jit(prefill_chunk, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    dec = jax.jit(decode_step, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))

    def tree(row: int):
        return forced(params, picks[:, row]) if force else params

    for start in [0] + list(range(first, n_prefill)):
        n = first if start == 0 else 1
        chunk = np.zeros(_base._bucket(n), np.int32)
        chunk[:n] = ids[start:start + n]
        # the first launch leaves a snapshot and NOT the lane's slot; the
        # run-in's first row resumes from it into the lane's slot
        logits, k_pool, v_pool = pre(
            tree(start) if start else params, cfg, k_pool, v_pool, page_row,
            jnp.asarray(chunk), jnp.int32(start), jnp.int32(n),
            jnp.int32(SNAPSHOT if start == first else
                      LANE if start else TRASH),
            jnp.int32(LANE if start else TRASH),
            jnp.int32(TRASH if start else SNAPSHOT), page_size=page_size)
    out = [np.asarray(logits)]
    for i in range(n_prefill, len(ids)):
        lg, k_pool, v_pool = dec(
            tree(i), cfg, k_pool,
            v_pool, page_row[None, :], jnp.asarray(ids[i:i + 1]),
            jnp.asarray([i], jnp.int32), jnp.asarray([True]),
            page_size=page_size)
        out.append(np.asarray(lg[0]))
    report = state_report(v_pool)
    print(f"solaropen2_pool: state {report} after {len(ids) - n_prefill} "
          "decode steps", file=sys.stderr, flush=True)
    if not (report["float32"]
            and report["tails_written"] == report["layers"]
            and report["states_written"] == report["layers"]
            and report["state_f32_share"] >= 0.5):
        raise DeltaStateError(
            f"the linear-attention state is not what the configuration "
            f"states (float32 slots, every layer's tail and S written, S "
            f"unrounded): {report}")
    return np.stack(out)
