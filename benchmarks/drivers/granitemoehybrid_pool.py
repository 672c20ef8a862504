"""Cache driver of granite-4.0-h-small's check: `drivers/phi4flash_pool.py`'s
prefill chunk and decode step (the program's `forward` over the pools and the
STATE SLOTS the engine allocates, with the index plan and the state plan
built the way `runtime/step_programs.py` builds them), over a model whose
layers hold TWO sublayers each: the one attention layer's rows in the paged
pool, the nine Mamba-2 layers' conv tail and 128 heads' states in the slots,
and a softmax-routed feed-forward behind every one of them.
`drivers/nemotronh_pool.py` is its pattern (same slot leaves, same launches);
what differs is how a launch is handed its experts.

The prompt is prefilled in 1 + RUN_IN launches, all padded to whole 128-row
SSD chunks: all but its last RUN_IN rows in one bucket (48 idle rows at the
check's 1,488), which leaves a SNAPSHOT on a page boundary and does not write
the lane's slot; then the run-in, RUN_IN rows (three pages) a row a launch in
the smallest bucket (127 idle rows), the first resumed from that snapshot (as a
prefix hit restores one) into the lane's slot and the others from the lane's
own slot, as a prompt's later chunks are.  Decode runs in the lane's slot.
So both state leaves cross a launch boundary through a snapshot, padded
chunks (the state after the last REAL row is what must be written) and the
prefill-to-decode boundary where `ssd_chunk` hands over to `ssd_step`, and a
program that read the lane's own slot at the run-in's first row would read
zeros (`references/granitemoehybrid.py`'s variants `state_lost_at_chunk` /
`conv_tail_zeroed_at_chunk` are what the check must fail).

TEACHER-FORCED PICKS UNDER THE SOFTMAX RULE.  Every launch from the run-in on
is ONE real row wide, so it can be told that row's experts: the driver asks
the reference which ten of 72 its float32 pass takes at that row, in every
layer.  The softmax rule has no selection bias to lift them through, so the
tree of such a launch gains ONE leaf that no published tree holds,
"router_choice" [layers, routed] float32 beside the router: FORCE at the
row's experts, 0 elsewhere.  `models/ffn._routing_weights` adds it to the
logits for the CHOICE alone; the weights are the softmax over the chosen
experts' own logits, the program's scores and the served weights.  The first
launch's rows keep their own picks and a tree without the leaf: their swaps
reach the compared rows through attention and through a state that the
run-in's 48 rows let fade (`references/nemotronh.py` RUN_IN).

What the logits cannot show is checked on the slots themselves: after the
last decode step the lane's slot must hold float32 leaves, written in EVERY
Mamba-2 layer, and most values of S must need float32 to be written
(`state_f32_share`).
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
_falcon = _sibling("drivers", "falconh1_pool")
_reference = _sibling("references", "granitemoehybrid")

LANE, TRASH, SNAPSHOT, N_SLOTS = (_base.LANE, _base.TRASH, _base.SNAPSHOT,
                                  _base.N_SLOTS)
prefill_chunk, decode_step = _base.prefill_chunk, _base.decode_step
# the slot is Falcon-H1's (a conv tail and the heads' states a layer): so is
# the report on it, the bucket of whole SSD chunks and the error's name
state_report, _bucket = _falcon.state_report, _falcon._bucket
SsdStateError = _falcon.SsdStateError
RUN_IN = _reference.RUN_IN  # rows a row a launch on forced picks: three pages
# what the chosen experts' entries of the choice leaf hold: past any gap
# between two logits (a normed row through a router drawn at 1 / sqrt(H):
# logits of order 1)
FORCE = 1000.0


def forced(params, picks):
    """`params` with a choice leaf that names the experts `picks` [layers, k]
    in every layer's router: the tree of a step whose one row takes them."""
    layers = params["layers"]
    n, _, routed = layers["router"].shape
    choice = jnp.zeros((n, routed), jnp.float32).at[
        jnp.arange(n)[:, None], jnp.asarray(picks)].set(FORCE)
    return dict(params, layers=dict(layers, router_choice=choice))


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
        chunk = np.zeros(_bucket(n), np.int32)
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
            tree(i), cfg, k_pool, v_pool, page_row[None, :],
            jnp.asarray(ids[i:i + 1]), jnp.asarray([i], jnp.int32),
            jnp.asarray([True]), page_size=page_size)
        out.append(np.asarray(lg[0]))
    report = state_report(v_pool)
    print(f"granitemoehybrid_pool: state {report} after "
          f"{len(ids) - n_prefill} decode steps", file=sys.stderr, flush=True)
    if not (report["float32"]
            and report["tails_written"] == report["layers"]
            and report["states_written"] == report["layers"]
            and report["state_f32_share"] >= 0.5):
        raise SsdStateError(
            f"the SSD state is not what the configuration states (float32 "
            f"slots, every Mamba-2 layer's tail and S written, S "
            f"unrounded): {report}")
    return np.stack(out)
