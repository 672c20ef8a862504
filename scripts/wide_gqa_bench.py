#!/usr/bin/env python3
"""The three attention kernels of a WIDE grouped-query model alone, on the
chip, at K-EXAONE's geometry (64 query / 8 KV heads x 128, a merged row of
1,024 lanes; PERF.md section 4): flash prefill and paged decode, each with
the 128-key window and without.

    python scripts/wide_gqa_bench.py               # the cell's shapes
    python scripts/wide_gqa_bench.py --rows 256    # another prefill bucket
    python scripts/wide_gqa_bench.py --parent DIR  # + DIR's flash prefill
    python scripts/wide_gqa_bench.py --takeapart   # + the chunk step's parts
    python scripts/wide_gqa_bench.py --rehearse    # CPU, tiny, no times

Through the chip tool, from the repo root.  Defaults are the cell's: 16-row
pages scattered in an 8,192-page bf16 pool under a 2,048-page table; prefill
is one lane's bucket of `--rows` rows (512, then 256 and 64) at positions
28,400.., so a full layer's q block walks ~222 chunks of 128 keys and a
sliding layer's one or two; decode is 32 lanes at contexts 28,500-30,000 over
a shared 28,192-token prefix.  Each form is the installed kernel
(`ops/pallas` `paged_prefill_attention`, `paged_decode_attention`,
`paged_decode_attention_window`) jitted under a name of its own; times are
the Pallas call's own events in one profiler capture (the host clock would
add the dispatch).  Beside each: the flops the MODEL needs (4 x pairs under
the mask x 64 x 128, against the bf16 peak) and the K / V bytes it needs
(against the HBM peak), whichever bounds the call, so the next `perf_opt` on
these kernels starts from a number.  Other geometries by option: Yi's and
Mellum2's is `--heads 32 --kv-heads 4 --window 1024`, Phi-4-mini-flash's
`--heads 40 --kv-heads 20 --head-dim 64 --window 512 --diff`.

`--parent DIR` (a `git archive` of another commit, unpacked) times that
tree's `paged_prefill_attention` on the same inputs in the same capture, as
`parent_prefill_*`, and gives each installed form's largest difference from
it.  `--takeapart` adds, for the first `--rows` bucket of the global form:
the operands in float32 (q and both pools cast up: the kernel multiplies in
the promoted dtype) with whether the bf16-operand output equals it bit for
bit, and other q blocks and KV chunks (TAKEAPART_Q_BLOCKS,
TAKEAPART_CHUNK_PAGES).  Prints one JSON line a form and writes them all,
with the q block and lane group the kernel chose (`plan`), to `--out`.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import re
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


# --takeapart's other sizes: q positions a block, pages a KV chunk
TAKEAPART_Q_BLOCKS = (32, 64, 128)
TAKEAPART_CHUNK_PAGES = (8, 16)


def pairs(rows: int, start: int, window) -> float:
    """(query, key) pairs of `rows` queries at positions start..; each
    attends itself and what is before it, a sliding layer the last
    `window`."""
    return float(sum(min(start + i + 1, window or start + i + 1)
                     for i in range(rows)))


def load_parent(tree):
    """`paged_prefill_attention` of the tree unpacked at `tree`, under a
    module name of its own (the installed one stays what it is)."""
    path = os.path.join(tree, "kafka_tpu", "ops", "pallas", "flash_prefill.py")
    spec = importlib.util.spec_from_file_location(
        "kafka_tpu.ops.pallas.parent_flash_prefill", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.paged_prefill_attention


def kernel_events(trace_dir, names):
    """({name: [device ns of the attention kernel's calls in each launch of
    jit_<name>]}, {name: [device ns of each whole jit_<name> program]},
    {name: the kernel calls' names}).  A kernel's events are under the name
    of its jitted entry (`%paged_prefill_attention.1 = ... custom-call(`);
    any other custom call XLA puts in the program counts under the whole
    program only."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans, calls = {n: [] for n in names}, []
    for plane in ProfileData.from_file(path).planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    m = re.match(r"jit_(\w+)", ev.name)
                    if m and m.group(1) in spans:
                        spans[m.group(1)].append((ev.start_ns, ev.duration_ns))
            elif line.name == "XLA Ops":
                calls += [(ev.start_ns, ev.duration_ns, ev.name)
                          for ev in line.events
                          if re.match(r"%paged_\w+_attention", ev.name)]

    def inside(t0, dur):
        return [c for c in calls if t0 <= c[0] < t0 + dur]

    return ({n: [sum(d for _, d, _ in inside(*sp)) for sp in sps]
             for n, sps in spans.items()},
            {n: [dur for _, dur in sps] for n, sps in spans.items()},
            {n: sorted({c[2].split(" = ")[0] for sp in sps
                        for c in inside(*sp)}) for n, sps in spans.items()})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--window", type=int, default=128)
    ap.add_argument("--rows", type=int, nargs="+", default=[512, 256, 64])
    ap.add_argument("--start", type=int, default=28400)
    ap.add_argument("--lanes", type=int, default=32)
    ap.add_argument("--min-len", type=int, default=28500)
    ap.add_argument("--max-len", type=int, default=30000)
    ap.add_argument("--shared-prefix", type=int, default=28192)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=8192)
    ap.add_argument("--max-pages", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=2147485003)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--diff", action="store_true",
                    help="differential attention's pairing (Phi-4)")
    ap.add_argument("--parent", help="an unpacked tree whose flash prefill "
                    "is timed beside the installed one")
    ap.add_argument("--takeapart", action="store_true")
    ap.add_argument("--out", default="chiprun_out/wide_gqa_bench.json")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny geometry, any backend, checks only")
    args = ap.parse_args()
    if args.rehearse:
        args.heads, args.kv_heads, args.head_dim, args.window = 8, 2, 64, 24
        args.rows, args.start, args.lanes = [64], 140, 3
        args.min_len, args.max_len, args.shared_prefix = 150, 220, 128
        args.page_size, args.num_pages, args.max_pages = 8, 240, 32

    import jax
    import jax.numpy as jnp

    from kafka_tpu.ops.attention import causal_attention
    from kafka_tpu.ops.pallas import (
        paged_decode_attention,
        paged_decode_attention_window,
        paged_prefill_attention,
    )
    from kafka_tpu.ops.pallas.flash_prefill import chunk_pages, prefill_plan
    from kafka_tpu.runtime.planner import device_peaks

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        print("no TPU: a device time comes only from the chip "
              "(--rehearse checks the command here)", file=sys.stderr)
        return 3
    interp = not on_chip
    rng = np.random.RandomState(args.seed % 2**31)
    ps, hq, hkv, d = args.page_size, args.heads, args.kv_heads, args.head_dim
    dt = jnp.bfloat16 if on_chip else jnp.float32
    pool = lambda: jnp.asarray(  # noqa: E731
        rng.randn(args.num_pages * ps, hkv * d).astype(np.float32), dt)
    k_pool, v_pool = pool(), pool()
    free = list(range(1, args.num_pages))  # page 0 is the trash page
    rng.shuffle(free)
    shared = [free.pop() for _ in range(args.shared_prefix // ps)]
    lens = rng.randint(args.min_len, args.max_len + 1,
                       size=args.lanes).astype(np.int32)
    lens[0] = args.max_len  # prefill reads lane 0's pages
    table = np.zeros((args.lanes, args.max_pages), np.int32)
    for b, n in enumerate(lens):
        table[b, :len(shared)] = shared
        for i in range(len(shared), -(-(int(n) + 1) // ps)):
            table[b, i] = free.pop()
    assert args.start + max(args.rows) <= int(lens[0]) + 1, "--start"

    forms, needs = {}, {}
    # scores over D lanes, the weighted sum over D (differential: 2 D)
    pair_flops = (6.0 if args.diff else 4.0) * hq * d
    kw = {"page_size": ps, "interpret": interp}
    if args.diff:
        kw["diff"] = True

    def add_prefill(name, kernel, rows, window, q=None, pools=None, **more):
        def prefill(q, k, v, row):
            return kernel(q, k, v, row, jnp.int32(args.start),
                          jnp.int32(q.shape[0]), window=window, **kw, **more)
        prefill.__name__ = name
        if q is None:
            q = jnp.asarray(rng.randn(rows, hq, d).astype(np.float32), dt)
        forms[name] = (jax.jit(prefill), (q, *(pools or (k_pool, v_pool)),
                                          jnp.asarray(table[0])))
        reach = window if window and window < args.start else None
        keys = min(args.start + rows, reach + rows if reach
                   else args.start + rows)
        needs[name] = (pair_flops * pairs(rows, args.start, reach),
                       2.0 * keys * hkv * d * 2)
        return q

    parent = load_parent(args.parent) if args.parent else None
    for window in (None, args.window):
        sfx = "window" if window else "global"
        for rows in args.rows:
            q = add_prefill(f"prefill_{rows}_{sfx}", paged_prefill_attention,
                            rows, window)
            if parent is not None:
                add_prefill(f"parent_prefill_{rows}_{sfx}", parent, rows,
                            window, q=q)
        name = f"decode_{sfx}"

        def decode(q, k, v, t, n, window=window):
            if window:
                return paged_decode_attention_window(
                    q, k, v, t, n, window=window, **kw)
            return paged_decode_attention(q, k, v, t, n, **kw)
        decode.__name__ = name
        q = jnp.asarray(rng.randn(args.lanes, hq, d).astype(np.float32), dt)
        forms[name] = (jax.jit(decode), (q, k_pool, v_pool,
                                         jnp.asarray(table), jnp.asarray(lens)))
        seen = [min(int(n) + 1, window or int(n) + 1) for n in lens]
        needs[name] = (pair_flops * sum(seen),
                       2.0 * sum(-(-s // ps) * ps for s in seen) * hkv * d * 2)
    if args.takeapart:
        rows = args.rows[0]
        base = f"prefill_{rows}_global"
        q = forms[base][1][0]
        plan = prefill_plan(rows, hq, hkv, d, jnp.dtype(dt).itemsize,
                            diff=args.diff)
        f32 = jnp.float32
        add_prefill(f"{base}_f32_operands", paged_prefill_attention, rows,
                    None, q=q.astype(f32),
                    pools=(k_pool.astype(f32), v_pool.astype(f32)),
                    q_block=plan["q_block"])
        # (a form that is the default under another name would be the same
        # program: the compile cache hands back the first one's, name and all)
        for qb in TAKEAPART_Q_BLOCKS:
            if qb != plan["q_block"]:
                add_prefill(f"{base}_q_block_{qb}", paged_prefill_attention,
                            rows, None, q=q, q_block=qb)
        for cp in TAKEAPART_CHUNK_PAGES:
            if cp != chunk_pages(ps):
                add_prefill(f"{base}_chunk_{cp * ps}",
                            paged_prefill_attention, rows, None, q=q,
                            pages_per_chunk=cp)

    outs = {n: np.asarray(fn(*a), np.float32) for n, (fn, a) in forms.items()}
    for name, out in outs.items():
        assert np.isfinite(out).all(), name
    diffs = {n: float(np.abs(outs[n] - outs["parent_" + n]).max())
             for n in outs if "parent_" + n in outs}
    if not on_chip:
        assert all(v < 1e-4 for v in diffs.values()), diffs
    equal = None
    if args.takeapart:
        # the f32-operand output, rounded as the bf16-operand call rounds its
        # own, against that call's
        base = f"prefill_{args.rows[0]}_global"
        want = np.asarray(jnp.asarray(outs[base + "_f32_operands"]
                                      ).astype(dt), np.float32)
        equal = bool((want == outs[base]).all())
        for n in outs:
            if n.startswith(base + "_") and not n.endswith("_f32_operands"):
                assert np.abs(outs[n] - outs[base]).max() < 2e-2, n
    if args.rehearse and not args.diff:
        # the prefill kernel against plain attention over the same rows
        rows = args.rows[0]
        total = args.start + rows
        idx = (np.asarray(table[0])[:, None] * ps
               + np.arange(ps)[None, :]).reshape(-1)[:total]
        for window in (None, args.window):
            sfx = "window" if window else "global"
            q = forms[f"prefill_{rows}_{sfx}"][1][0]
            want = causal_attention(
                q[None], k_pool[idx].reshape(1, total, hkv, d),
                v_pool[idx].reshape(1, total, hkv, d),
                q_positions=args.start + jnp.arange(rows)[None],
                kv_positions=jnp.arange(total)[None], window=window)[0]
            err = float(np.abs(outs[f"prefill_{rows}_{sfx}"]
                               - np.asarray(want, np.float32)).max())
            assert err < 1e-4, (sfx, err)
    if not on_chip:
        print(json.dumps({"rehearsed": sorted(forms), "device": "cpu"}))
        return 0

    trace_dir = tempfile.mkdtemp(prefix="wide_gqa_bench_")
    with jax.profiler.trace(trace_dir):
        for _ in range(args.reps):
            for fn, a in forms.values():
                fn(*a).block_until_ready()
    events, modules, called = kernel_events(trace_dir, list(forms))
    peak_flops, hbm_bytes_per_s, _ = device_peaks(jax.devices()[0])
    result = {"device": jax.devices()[0].device_kind, "args": vars(args),
              "plan": {str(rows): prefill_plan(rows, hq, hkv, d,
                                               jnp.dtype(dt).itemsize,
                                               diff=args.diff)
                       for rows in args.rows},
              "chunk_keys": chunk_pages(ps) * ps,
              "forms": {}}
    for name in forms:
        durs = events[name]
        if len(durs) != args.reps:
            print(f"{len(durs)} kernel calls of {name} in the capture, "
                  f"expected {args.reps}", file=sys.stderr)
            return 1
        us = float(np.median(durs)) / 1e3
        flops, nbytes = needs[name]
        row = {"calls": len(durs), "us_per_call": us,
               "min_us": min(durs) / 1e3, "max_us": max(durs) / 1e3,
               # the whole jitted call: the kernel and the XLA ops around it
               "module_us": float(np.median(modules[name])) / 1e3,
               "kernels": called[name],
               "model_flops": flops, "kv_bytes": nbytes,
               "mfu_pct": 100.0 * flops / peak_flops / (us / 1e6),
               "hbm_pct": 100.0 * nbytes / hbm_bytes_per_s / (us / 1e6)}
        if name in diffs:
            row["max_abs_diff_vs_parent"] = diffs[name]
        result["forms"][name] = row
        print(json.dumps({"form": name, **row}))
    if equal is not None:
        result["bf16_operands_equal_f32_bit_for_bit"] = equal
        print(json.dumps({"bf16_operands_equal_f32_bit_for_bit": equal}))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
