#!/usr/bin/env python3
"""Decode's tail convolution of a state layer alone, on the chip, at the four
registered geometries (channels a row, state layers, lanes): Solar-Open2's
three convolutions side by side (24,576 / 6 / 32), Nemotron-3-Nano's (6,144 /
7 / 32), Falcon-H1's (5,120 / 7 / 32) and Granite-4.0-H-Small's (8,448 / 9 /
16), over a leaf of 129 slots laid out as `models/config._tail_layout` says,
one layer a call, the leaf donated (as the layer scan carries it).

    python scripts/tail_conv_bench.py                  # every geometry
    python scripts/tail_conv_bench.py --lanes 16 32    # at these lanes
    python scripts/tail_conv_bench.py --lanes-a-step 1 2 4 8
    python scripts/tail_conv_bench.py --ops 6          # + the longest ops
    python scripts/tail_conv_bench.py --rehearse       # CPU, tiny, no times

Through the chip tool, from the repo root.  Forms:

  xla       `models/mixers/state._tail_conv_silu`'s body (the slot re-tiled
            to [B, taps - 1, C], a gather a lane, re-tiled back)
  xla_s1    the same at S == 1 with the gather spelled as the static
            `seq[:, 1:]` (pure XLA: what ten lines buy)
  kernel    `ops/pallas/tail_conv.tail_conv_step` as installed (`kernel_n<N>`
            with `--lanes-a-step N`: N slots a grid step); where `tiles`
            declines the geometry the kernel is tried all the same and the
            line says what the chip's compiler answered

Times are the jitted programs' device durations in one profiler capture (`XLA
Modules`), us a layer, beside the bytes' time at the HBM peak (the slots read
and written once); every form's output and leaf are held to `xla`'s.  Prints
one JSON line a form and writes them all to `--out`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from unittest import mock

import numpy as np

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# name: (channels a row, state layers, lanes served, bias)
GEOMETRIES = {
    "solar-open2": (24576, 6, 32, False),
    "nemotron-3-nano": (6144, 7, 32, True),
    "falcon-h1": (5120, 7, 32, True),
    "granite-4.0-h": (8448, 9, 16, True),
}
TAPS, SLOTS, HBM_GBPS = 4, 129, 819.0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--geometries", nargs="+", default=list(GEOMETRIES),
                    choices=list(GEOMETRIES))
    ap.add_argument("--lanes", type=int, nargs="+", default=[16, 32])
    ap.add_argument("--lanes-a-step", type=int, nargs="+", default=[])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--ops", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "tail_conv_bench.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from kafka_tpu.models.cache import StatePlan, _read_state, _write_state
    from kafka_tpu.models.config import _tail_layout
    from kafka_tpu.models.mixers.state import _tail_conv_silu
    from kafka_tpu.ops.pallas import tail_conv
    from moe_dispatch_bench import module_events

    on_chip = jax.default_backend() == "tpu"
    if not (on_chip or args.rehearse):
        print("no TPU here: run through the chip tool, or --rehearse",
              file=sys.stderr)
        return 3
    geometries = {g: GEOMETRIES[g] for g in args.geometries}
    slots = SLOTS
    if args.rehearse:
        geometries = {g: (c // 8 if c % 1024 == 0 and c > 8192 else c, 2, b,
                          bias) for g, (c, _, b, bias) in geometries.items()}
        args.lanes, args.reps, slots = [4], 1, 9

    def xla(rows, w, bias, leaf, layer, lens):
        out, leaf = _tail_conv_silu(rows[:, None], w, bias, leaf, layer,
                                    StatePlan(lens=lens))
        return out[:, 0], leaf

    def xla_s1(rows, w, bias, leaf, layer, lens):
        b, c = rows.shape
        plan = StatePlan(lens=lens)
        old = _read_state(leaf, layer, plan, b)
        seq = jnp.concatenate(
            [old.reshape(b, TAPS - 1, c), rows[:, None]], axis=1)
        out = sum(w[j] * seq[:, j] for j in range(TAPS))
        out = jax.nn.silu(out if bias is None else out + bias)
        return out, _write_state(leaf, layer, plan,
                                 seq[:, 1:].reshape(old.shape), old)

    def kernel(rows, w, bias, leaf, layer, lens):
        # (not through its own jit: `LANES_A_STEP` is read as it is traced)
        return tail_conv.tail_conv_step.__wrapped__(
            leaf, layer, lens, rows, w, bias, interpret=not on_chip)

    rng = np.random.RandomState(args.seed % 2**31)
    result = {"device": jax.devices()[0].device_kind, "args": vars(args),
              "forms": []}
    forms = {}
    for name, (c, layers, _, has_bias) in geometries.items():
        slot = _tail_layout(TAPS - 1, c)
        tiled = tail_conv.tiles(TAPS, c, slot)
        w = jnp.asarray(rng.standard_normal((TAPS, c)), jnp.float32)
        bias = (jnp.asarray(rng.standard_normal(c), jnp.float32)
                if has_bias else None)
        for lanes in args.lanes:
            rows = jnp.asarray(rng.standard_normal((lanes, c)), jnp.float32)
            # every fourth lane idle: its slot goes back as it came
            lens = jnp.asarray(np.arange(lanes) % 4 != 3, jnp.int32)
            leaf0 = rng.standard_normal(
                (layers, slots) + slot).astype(np.float32)
            layer = jnp.int32(layers - 1)
            want = None
            todo = [("xla", xla, None), ("xla_s1", xla_s1, None),
                    ("kernel", kernel, None)]
            todo += [(f"kernel_n{n}", kernel, n) for n in args.lanes_a_step]
            for form, fn, n in todo:
                def named(leaf, *a, fn=fn, uid=len(forms)):
                    return fn(*a[:3], leaf, *a[3:]), jnp.int32(uid)
                named.__name__ = (f"{name}_{lanes}_{form}".replace("-", "_")
                                  .replace(".", "_"))
                row = {"geometry": name, "channels": c, "slot": list(slot),
                       "lanes": lanes, "form": form, "tiles": tiled,
                       "bytes_us": 2 * lanes * 4 * slot[0] * slot[1]
                       / HBM_GBPS / 1e3}
                jitted = jax.jit(named, donate_argnums=0)
                a = (rows, w, bias, layer, lens)
                try:
                    with mock.patch.object(
                            tail_conv, "LANES_A_STEP",
                            n or tail_conv.LANES_A_STEP):
                        if fn is kernel:
                            row["lanes_a_step"] = tail_conv.lanes_a_step(
                                lanes)
                        (out, leaf), _ = jax.block_until_ready(
                            jitted(jnp.asarray(leaf0), *a))
                except Exception as e:  # noqa: BLE001 - the compiler's word
                    if tiled:
                        raise
                    row["refused"] = f"{type(e).__name__}: {e}"[:400]
                    result["forms"].append(row)
                    print(json.dumps(row))
                    continue
                if want is None:
                    want = (np.asarray(out), np.asarray(leaf))
                row["max_abs_diff"] = float(np.abs(
                    np.asarray(out) - want[0]).max())
                row["leaf_equal"] = bool(
                    np.array_equal(np.asarray(leaf), want[1]))
                # (the chip's XLA and Mosaic round SiLU's exponential apart)
                assert row["leaf_equal"] and row["max_abs_diff"] < (
                    1e-4 if on_chip else 1e-7), row
                forms[named.__name__] = (jitted, leaf, a, row)
    if not on_chip:
        for *_, row in forms.values():
            print(json.dumps(row))
        print(json.dumps({"rehearsed": sorted(forms), "device": "cpu"}))
        return 0
    trace_dir = tempfile.mkdtemp(prefix="tail_conv_bench_")
    with jax.profiler.trace(trace_dir):
        for fname, (jitted, leaf, a, _) in forms.items():
            for _ in range(args.reps):
                (_, leaf), _ = jitted(leaf, *a)
            jax.block_until_ready(leaf)
    events, by_op = module_events(trace_dir, list(forms))
    for fname, (*_, row) in forms.items():
        durs = events[fname]
        if len(durs) != args.reps:
            print(f"{len(durs)} launches of {fname} in the capture, "
                  f"expected {args.reps}", file=sys.stderr)
            return 1
        row.update(us=float(np.median(durs)) / 1e3, min_us=min(durs) / 1e3,
                   max_us=max(durs) / 1e3)
        if args.ops:
            top = sorted(by_op[fname].items(), key=lambda kv: -kv[1])
            row["ops_us"] = {op: round(ns / 1e3, 1)
                             for op, ns in top[:args.ops]}
        result["forms"].append(row)
        print(json.dumps(row))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
