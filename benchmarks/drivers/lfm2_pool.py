"""Cache driver of LFM2-8B-A1B's check: `drivers/phi4flash_pool.py`'s
prefill chunk and decode step (the program's `forward` over the pools and the
STATE SLOTS the engine allocates, with the index plan and the state plan
built the way `runtime/step_programs.py` builds them), over a state slot of
another shape.  That file's `served_logits` cannot drive this model: the
float32 share it reads off the slot is of a leaf (`ssm`) that a short
convolution's state does not have.

The prompt is prefilled in 1 + RUN_IN + TAIL launches, all padded: all but
its last RUN_IN + TAIL rows in one bucket, which leaves a SNAPSHOT; then the
run-in, RUN_IN rows a row a launch, the first resumed from that snapshot (as
a prefix hit restores one, on a page boundary) and the others from the lane's
own slot, as a prompt's later chunks are; then the ONE row the comparison
starts at, on a page boundary again, resumed from a snapshot the run-in's
last row left (which did NOT write the lane's slot: a program that read the
wrong slot would read a stale tail).  Decode runs in the lane's slot.  So the
conv tail crosses a launch boundary, a restore and the prefill-to-decode
boundary, each right at a compared position: what a tail carries fades within
a few rows (`references/lfm2moe.py`, which has the same TAIL and RUN_IN), and
the reference's variants `tail_lost_between_launches` / `tail_lost_at_decode`
are what the check must fail.

TEACHER-FORCED PICKS.  Every launch from the run-in on is ONE real row wide,
so the selection bias it is handed, a leaf of [routed layers, experts] that
"chooses and does not weigh", names that row's experts: the driver asks the
reference which experts its float32 pass takes at that row
(`reference_logits(...)["picks"]`) and adds FORCE to their entries of the
bias for that one launch.  The program, its compiled steps, its scores and
its weights are the served ones; only WHICH four of 32 such a row takes is
the reference's, where at ~6% of the (row, routed layer) pairs bfloat16 noise
in the scores would take another fourth expert, 12 routed layers deep, with
every conv layer handing a swapped row's difference to the rows behind it
(my chip runs B and I, PR 47: 0.05-0.40 at every position with free picks,
no precision separable; forced, 0.030-0.045).  The first launch's 1,504 rows
keep their own picks: their swaps reach the compared rows through attention,
one key of 1,500, and through two tails that the run-in's 16 rows wash out.
A program that did not read the bias would not be forced, and would fail as
`chosen_without_bias` does.  In float32 the program's own picks ARE the
reference's and forcing changes nothing (`tests/test_lfm2_moe.py`).

What the logits cannot show is checked on the slots themselves: after the
last decode step the lane's slot and the snapshot's must hold float32 rows
(the pool's one definition of a state slot), nonzero in EVERY conv layer (a
layer that never wrote its tail reads zeros), and most of their values must
need float32 to be written (`tail_f32_share`): a tail row is the exact
product of two gate values, 16 significant bits under bfloat16 activations,
so a value that bfloat16 could hold is a coincidence (one in 256) unless
something rounded the tail on its way into the slot.
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
_reference = _sibling("references", "lfm2moe")

LANE, TRASH, SNAPSHOT, N_SLOTS = (_base.LANE, _base.TRASH, _base.SNAPSHOT,
                                  _base.N_SLOTS)
prefill_chunk, decode_step = _base.prefill_chunk, _base.decode_step
TAIL = 1  # the last launch's one row, which starts on a page boundary
RUN_IN = 16  # rows ahead of it, a row a launch: a page
# what the chosen experts' entries of the selection bias gain: past any
# sigmoid (0..1) plus any seeded bias (N(0, 0.1^2))
FORCE = 4.0


class ConvTailError(ValueError):
    """A conv layer's tail is not in its slot as the configuration states it:
    the logit check fails by name."""


def tail_report(v_pool) -> dict:
    """Of the conv state: whether it is float32, how many conv layers hold a
    nonzero tail in the lane's and the snapshot's slot, and of their nonzero
    values the share that bfloat16 could NOT hold."""
    conv = v_pool["conv"]
    rows = np.asarray(conv[:, [LANE, SNAPSHOT]], np.float32)
    values = rows[rows != 0]
    return {
        "float32": conv.dtype == jnp.float32,
        "layers": int(conv.shape[0]),
        "lane_layers_written": int(np.sum(np.any(rows[:, 0] != 0, (1, 2)))),
        "snapshot_layers_written": int(
            np.sum(np.any(rows[:, 1] != 0, (1, 2)))),
        "tail_f32_share": float(np.mean(
            values.view(np.uint32) & 0xFFFF != 0)) if values.size else 0.0,
    }


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
    """prefill(n_prefill) as a first launch of all but RUN_IN + 1 rows, then
    those a row a launch, then one decode step per remaining token; every
    launch of one row takes the experts `picks` [routed layers, S, k] names
    (the reference's own over these weights where None; `force` False: the
    program's).  float32 logits [1 + n_decode, V], as
    paged_step.served_logits."""
    from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

    ids = np.asarray(token_ids, np.int32)
    if (n_prefill - TAIL) % page_size or n_prefill <= TAIL + RUN_IN:
        raise ValueError(f"the compared launch starts at {n_prefill - TAIL}: "
                         "not a page boundary behind a first launch and the "
                         "run-in")
    if force and picks is None:
        picks = _reference.reference_logits(
            params, _reference.hyper(cfg), ids, [n_prefill - 1])["picks"]

    def tree(row: int):
        return forced(params, picks[:, row]) if force else params

    k_pool, v_pool = make_kv_pool_arrays(
        cfg, pages_per_seq + 1, page_size, state_slots=N_SLOTS)
    page_row = jnp.arange(1, pages_per_seq + 1, dtype=jnp.int32)
    pre = jax.jit(prefill_chunk, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    dec = jax.jit(decode_step, static_argnums=(1,),
                  static_argnames=("page_size",), donate_argnums=(2, 3))
    first = n_prefill - TAIL - RUN_IN
    for start in [0] + list(range(first, n_prefill)):
        n = first if start == 0 else 1
        chunk = np.zeros(_base._bucket(n), np.int32)
        chunk[:n] = ids[start:start + n]
        eve, last = start == n_prefill - 2, start == n_prefill - 1
        # the first launch leaves a snapshot and the run-in resumes from it
        # in the lane's slot; the run-in's last row leaves the snapshot that
        # the compared row resumes from, and NOT the lane's slot, so that a
        # program that read the wrong one would read a stale tail
        logits, k_pool, v_pool = pre(
            tree(start) if start else params, cfg, k_pool, v_pool, page_row,
            jnp.asarray(chunk), jnp.int32(start), jnp.int32(n),
            jnp.int32(SNAPSHOT if start == first or last else
                      LANE if start else TRASH),
            jnp.int32(LANE if start and not eve else TRASH),
            jnp.int32(SNAPSHOT if eve or not start else TRASH),
            page_size=page_size)
    out = [np.asarray(logits)]
    for i in range(n_prefill, len(ids)):
        lg, k_pool, v_pool = dec(
            tree(i), cfg, k_pool, v_pool, page_row[None, :],
            jnp.asarray(ids[i:i + 1]), jnp.asarray([i], jnp.int32),
            jnp.asarray([True]), page_size=page_size)
        out.append(np.asarray(lg[0]))
    report = tail_report(v_pool)
    print(f"lfm2_pool: conv tail {report} after {len(ids) - n_prefill} "
          "decode steps", file=sys.stderr, flush=True)
    if not (report["float32"]
            and report["lane_layers_written"] == report["layers"]
            and report["snapshot_layers_written"] == report["layers"]
            and report["tail_f32_share"] >= 0.5):
        raise ConvTailError(
            f"the conv state is not what the configuration states (float32 "
            f"slots, every layer's tail written, the gates' product "
            f"unrounded): {report}")
    return np.stack(out)
