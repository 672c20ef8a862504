#!/usr/bin/env python3
"""The paged-decode kernel alone, on the chip: device time per call and per
chunk, global and windowed, beside the same walk with the softmax step taken
out (DMA starts, waits and the loop only).

    python scripts/paged_decode_bench.py                  # Yi's geometry
    python scripts/paged_decode_bench.py --window 0       # global form only
    python scripts/paged_decode_bench.py --window 128 513 1024   # a form each
    python scripts/paged_decode_bench.py --rehearse       # CPU, tiny, no times
    python scripts/paged_decode_bench.py --prefix-run     # + the run table
    python scripts/paged_decode_bench.py --latent --prefix-run --parent DIR
    python scripts/paged_decode_bench.py --backend xla    # the XLA decode read
    python scripts/paged_decode_bench.py --backend xla --shared-keys 0 7424

Through the chip tool, from the repo root.  Defaults are the registered
`chat-decode` cells' geometry (PERF.md section 4): 16 lanes, 32/4 x 128,
page 16, a 5,120-page bf16 pool, contexts 7.7k-8.9k over a 7,424-token
prefix whose pages every lane shares, the rest scattered.  Times are the
Pallas call's own events in one profiler capture (the host clock would add
the dispatch).  A chunk is `pages_per_chunk` (8) pages, the unit
`decode_chunk_range` counts and the rooflines of `benchmarks/` charge;
bytes are those chunks' K and V rows.  Prints one JSON line a form and writes
them all to chiprun_out/paged_decode_bench.json.  Each line also lays the call
out a LANE: `us_per_lane`, the time its bytes take at the chip's HBM peak
(`bytes_us_per_lane`) and what is left (`excess_us_per_lane`): the lane's
fixed cost, whatever it read (`--window 128 513 1024`: forms `window128`,
`window513`, `window1024`, lanes of one to three softmax steps, beside the
global form's ~17).

`--prefix-run` times every form on a second table too: the shared prefix's
pages ONE ascending run of physical pages from page 1 (what the engine's
first allocation on a fresh pool lays down: `PagePool` pops 1, 2, 3, ...),
the tails scattered as before.  The kernel fetches a softmax step whose pages
are one run as one copy a pool (`decode_step_runs` counts them; each row
carries `steps_whole` / `steps_run`).  `--latent`: the MLA form
(`paged_decode_attention_latent`) at Kanana-2's geometry, 32 lanes, 32 heads,
rows of 512 latent + 128 rotary lanes (`--latent-rank 1024 --heads 128
--window 513 --max-pages 2048`: dots3's sliding layers).  `--parent DIR
[DIR ...]`: another tree's `kafka_tpu/ops/pallas/paged_attention.py` timed
beside the installed one on the same inputs (forms `parent_*`, a second
tree's `parent2_*`, ...), its output required to equal the installed
kernel's bit for bit on every table.

`--backend xla`: the XLA decode read alone (models/mixers/gqa.py `_decode_walk`)
at Mixtral's geometry (32/8 x 128, the rest as above), once for each
candidate of `ops.attention.DECODE_WALK_KEYS` (--walk-keys), beside the read
it replaced: the gather of every lane's static `max_pages` window and
`causal_attention` over it.  Times are each jitted form's module events in
one capture, with its costliest ops named (a `copy` of a window- or
chunk-shaped K/V would head the list); bytes are the walked keys' K and V
rows, read once.  `--shared-keys` (one or more): the page tables' common
leading keys, a table each over the same contexts (0: no two lanes share a
page, the per-lane walk; 7,424: the cells' system prompt, read once a trip
since PR 51), every walk form timed on every table with the trips it shared
beside it; the pool grows to hold what the lanes do not share.  Writes
chiprun_out/paged_decode_bench_xla.json (--out).
"""

from __future__ import annotations

import argparse
import bisect
import functools
import glob
import json
import os
import re
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

PAGES_PER_CHUNK = 8


def make_case(args, jnp, shared_keys=None, pools=None, prefix_run=False):
    """Inputs from --seed: a scattered page table over a shared prefix
    (`shared_keys`, default --shared-prefix), the pools drawn here or
    `pools` (k, v) as handed in (then args.num_pages says their pages).
    `prefix_run`: the prefix's pages are 1, 2, 3, ... (one ascending run),
    the same draws otherwise."""
    rng = np.random.RandomState(args.seed % 2**31)
    ps, hd = args.page_size, args.kv_heads * args.head_dim
    dtype = jnp.dtype(args.dtype)
    if shared_keys is None:
        shared_keys = args.shared_prefix
    if pools is None:
        total = args.num_pages * ps
        k = jnp.asarray(rng.randn(total, hd).astype(np.float32), dtype)
        v = jnp.asarray(rng.randn(total, hd).astype(np.float32), dtype)
    else:
        k, v = pools
    q = jnp.asarray(
        rng.randn(args.lanes, args.heads, args.head_dim).astype(np.float32),
        dtype)
    lens = rng.randint(args.min_len, args.max_len + 1,
                       size=args.lanes).astype(np.int32)
    free = list(range(1, args.num_pages))  # page 0 is the trash page
    rng.shuffle(free)
    shared = [free.pop() for _ in range(shared_keys // ps)]
    if prefix_run:
        # the prefix takes pages 1..n; a tail page among them becomes one of
        # the pages the shuffled prefix held instead
        spare = [p for p in shared if p > len(shared)]
        free = [p if p > len(shared) else spare.pop() for p in free]
        shared = list(range(1, len(shared) + 1))
    table = np.zeros((args.lanes, args.max_pages), np.int32)
    for b, n in enumerate(lens):
        need = -(-(int(n) + 1) // ps)
        table[b, :len(shared)] = shared
        for i in range(len(shared), need):
            table[b, i] = free.pop()
    return (q, k, v, jnp.asarray(table), jnp.asarray(lens)), lens


def load_parent(tree):
    """Another tree's paged_attention module (it imports nothing of its own
    package), under a name of its own."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "parent_paged_attention", os.path.join(
            tree, "kafka_tpu", "ops", "pallas", "paged_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def forms(args, jax, modules):
    """{name: (jitted fn, window)}: for each tree of `modules` {prefix:
    paged_attention module} the kernel, and the same walk with `_attend` (the
    softmax step) replaced by nothing; a tree whose kernel has no such
    function (before PR 30) gets the first form only."""
    interpret = jax.default_backend() != "tpu"

    def build(pa, name, window, walk_only):
        def fn(q, k, v, table, lens):
            if walk_only:
                attend, pa._attend = pa._attend, lambda *a, **kw: None
            try:
                if args.latent:
                    r = args.latent_rank
                    return pa.paged_decode_attention_latent.__wrapped__(
                        q[..., :r], q[..., r:], k, v, table, lens,
                        scale=(r // 4 + q.shape[-1] - r) ** -0.5,
                        page_size=args.page_size,
                        pages_per_chunk=PAGES_PER_CHUNK, interpret=interpret,
                        window=window)
                return pa._paged_decode(
                    q, k, v, table, lens, args.page_size, PAGES_PER_CHUNK,
                    None, interpret, window)
            finally:
                if walk_only:
                    pa._attend = attend
        fn.__name__ = name
        return jax.jit(fn)

    out = {}
    for prefix, pa in modules.items():
        for window in [None] + args.window:
            name = prefix + (f"window{window}" if window else "global")
            out[name] = (build(pa, name, window, False), window)
            if hasattr(pa, "_attend"):
                out[name + "_walk"] = (
                    build(pa, name + "_walk", window, True), window)
    return out


def xla_forms(args, jax, jnp):
    """{name: jitted fn(q, k, v, table, lens) -> [B, Hq, D]}: the static
    window read, then the walk at each candidate chunk size."""
    from kafka_tpu.models.mixers import gqa
    from kafka_tpu.ops import attention
    from kafka_tpu.runtime.step_programs import decode_plan

    ps, hkv, d = args.page_size, args.kv_heads, args.head_dim

    def plan(table, lens):
        return decode_plan(table, lens, jnp.ones(lens.shape, bool), ps)

    def window_read(q, k, v, table, lens):
        positions, paged = plan(table, lens)
        b = q.shape[0]
        return attention.causal_attention(
            q[:, None],
            gqa._kv_read_pages(k, table, ps, q.dtype).reshape(b, -1, hkv, d),
            gqa._kv_read_pages(v, table, ps, q.dtype).reshape(b, -1, hkv, d),
            q_positions=positions, kv_positions=paged.kv_positions,
            kv_valid=paged.kv_valid)[:, 0]

    def walk(keys):
        def fn(q, k, v, table, lens):
            _, paged = plan(table, lens)
            installed = attention.DECODE_WALK_KEYS
            attention.DECODE_WALK_KEYS = keys  # read when the walk is traced
            try:
                return gqa._decode_walk(
                    q[:, None], k, v, paged, hkv, None, None)[:, 0]
            finally:
                attention.DECODE_WALK_KEYS = installed
        return fn

    out = {} if args.no_window_form else {"bench_xla_window": window_read}
    for keys in args.walk_keys:
        out[f"bench_xla_walk_{keys}"] = walk(keys)
    for name, fn in out.items():
        fn.__name__ = name
        out[name] = jax.jit(fn)
    return out, jax.jit(window_read)


def module_events(trace_dir, names):
    """{name: [(device ns, {op: ns}) of each launch of jit_<name>, in launch
    order]} from the capture's `XLA Modules` and `XLA Ops` lines."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    launches = {n: [] for n in names}
    ops = []
    for plane in ProfileData.from_file(path).planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    m = re.match(r"jit_(\w+)", ev.name)
                    if m and m.group(1) in launches:
                        launches[m.group(1)].append(
                            (ev.start_ns, ev.duration_ns))
            elif line.name == "XLA Ops":
                for ev in line.events:
                    # "%copy.3 = bf16[32768,8,8,128]{...} copy(...)"
                    m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])",
                                 ev.name)
                    op = " ".join(m.groups()) if m else ev.name[:80]
                    if not op.startswith("while"):
                        ops.append((ev.start_ns, ev.duration_ns, op))
    ops.sort()
    starts = [s0 for s0, _, _ in ops]
    out = {}
    for name, spans in launches.items():
        out[name] = []
        for t0, dur in sorted(spans):
            by_op = {}
            for _, d, op in ops[bisect.bisect_left(starts, t0):
                                bisect.bisect_left(starts, t0 + dur)]:
                by_op[op] = by_op.get(op, 0) + d
            out[name].append((dur, by_op))
    return out


def bench_xla(args, jax, jnp) -> int:
    from kafka_tpu.ops import attention

    on_chip = jax.default_backend() == "tpu"
    ps, hd = args.page_size, args.kv_heads * args.head_dim
    # one pool for every table: room for the least shared of them
    least = min(args.shared_keys)
    args.num_pages = max(args.num_pages, 2 + least // ps + args.lanes * (
        args.max_len // ps + 1 - least // ps))
    kk, kv = jax.random.split(jax.random.PRNGKey(args.seed % 2**31))
    pools = tuple(
        jax.random.normal(key, (args.num_pages * ps, hd), jnp.dtype(args.dtype))
        for key in (kk, kv))
    fns, window_read = xla_forms(args, jax, jnp)
    tol = 1e-5 if args.dtype == "float32" else 2e-2
    runs = {}   # name: (fn, case, shared keys, shared trips, max diff)
    for shared in args.shared_keys:
        case, lens = make_case(args, jnp, shared, pools)
        q, k, v, table, dlens = case
        # the reference four lanes at a time: 32 lanes' 32k windows at once
        # would not fit beside the pools
        ref = np.concatenate([
            np.asarray(window_read(q[i:i + 4], k, v, table[i:i + 4],
                                   dlens[i:i + 4]), np.float32)
            for i in range(0, args.lanes, 4)])
        for name, fn in fns.items():
            if name == "bench_xla_window" and shared != args.shared_keys[0]:
                continue  # the materialised window reads what it reads
            out = np.asarray(fn(*case), np.float32)
            assert np.isfinite(out).all(), (name, shared)
            err = float(np.abs(out - ref).max())
            assert err <= tol, (name, shared, err)
            own = 0
            if "walk" in name:
                cp = min(int(name.rsplit("_", 1)[1]) // ps, args.max_pages)
                own = min(int(attention.common_pages(
                    case[3], jnp.ones(args.lanes, bool))[1]) // cp,
                    -(-(int(lens.max()) + 1) // (cp * ps)))
            runs[f"{name}.s{shared}"] = (fn, case, shared, own, err)
    if not on_chip:
        print(json.dumps({"rehearsed": {n: r[3] for n, r in runs.items()},
                          "device": "cpu"}))
        return 0
    trace_dir = tempfile.mkdtemp(prefix="paged_decode_bench_")
    # a form's launches follow one another table by table, rep by rep
    with jax.profiler.trace(trace_dir):
        for _ in range(args.reps):
            for fn, case, *_ in runs.values():
                fn(*case).block_until_ready()
    events = module_events(trace_dir, list(fns))
    from kafka_tpu.runtime.planner import device_peaks

    _, hbm_bytes_per_s, _ = device_peaks(jax.devices()[0])  # unknown: raises
    row_bytes = hd * jnp.dtype(args.dtype).itemsize
    window_keys = args.max_pages * ps
    result = {"device": jax.devices()[0].device_kind, "args": vars(args),
              "contexts": [int(n) for n in lens],
              "installed_walk_keys": attention.DECODE_WALK_KEYS, "forms": {}}
    for name in fns:
        tables = [r for r in runs if r.startswith(name + ".")]
        launches = events[name]
        if len(launches) != args.reps * len(tables):
            print(f"{len(launches)} launches of {name} in the capture, "
                  f"expected {args.reps} x {len(tables)}", file=sys.stderr)
            return 1
        ck, trips = window_keys, 1   # the window form: one read of it all
        if "walk" in name:
            ck = min(int(name.rsplit("_", 1)[1]), window_keys)
            trips = -(-(int(lens.max()) + 1) // ck)
        for i, run in enumerate(tables):
            _, _, shared, own, err = runs[run]
            mine = [d for d, _ in launches[i::len(tables)]]
            by_op = {}
            for _, ops in launches[i::len(tables)]:
                for op, ns in ops.items():
                    by_op[op] = by_op.get(op, 0) + ns
            us = float(np.median(mine)) / 1e3
            # K and V: a shared trip's keys once, the rest once a lane
            kv_bytes = 2 * row_bytes * ck * (own + args.lanes * (trips - own))
            row = {
                "lanes": args.lanes, "shared_keys": shared,
                "trips": trips, "shared_trips": own,
                "calls": len(mine), "us_per_call": us,
                "min_us": min(mine) / 1e3, "max_us": max(mine) / 1e3,
                "keys_per_lane": trips * ck, "kv_bytes": kv_bytes,
                "max_abs_diff_vs_window": err,
                "hbm_share": 100.0 * kv_bytes / hbm_bytes_per_s / (us / 1e6),
                "top_ops_us_per_call": {
                    op: ns / 1e3 / len(mine) for op, ns in sorted(
                        by_op.items(), key=lambda kv: -kv[1])[:8]},
            }
            result["forms"][run] = row
            print(json.dumps({"form": run, **row}))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


def kernel_events(trace_dir):
    """Device ns of every Pallas call in the capture, in launch order, and
    the events' names."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                # the kernel's calls: their first operand is the page table
                # (XLA's own custom calls, a sliced pool's copy, take none)
                events += [(ev.start_ns, ev.duration_ns, ev.name)
                           for ev in line.events
                           if "custom-call(s32[" in ev.name]
    return [d for _, d, _ in sorted(events)], {n[:120] for _, _, n in events}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--lanes", type=int, default=None,
                    help="default 16, 32 under --latent")
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--backend", choices=("pallas", "xla"), default="pallas",
                    help="xla: the XLA decode read at Mixtral's geometry")
    ap.add_argument("--walk-keys", type=int, nargs="+",
                    default=[512, 1024, 2048],
                    help="--backend xla: candidate chunk sizes of the walk")
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="default 4 (Yi), 8 under --backend xla (Mixtral)")
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=5120)
    ap.add_argument("--max-pages", type=int, default=1024,
                    help="page-table width (the cells' 16k window)")
    ap.add_argument("--min-len", type=int, default=7700)
    ap.add_argument("--max-len", type=int, default=8900)
    ap.add_argument("--shared-prefix", type=int, default=7424)
    ap.add_argument("--shared-keys", type=int, nargs="+", default=None,
                    help="--backend xla: the page tables' common leading "
                    "keys, a table each (default: --shared-prefix alone)")
    ap.add_argument("--no-window-form", action="store_true",
                    help="--backend xla: do not time the static-window read "
                    "(long windows x many lanes do not fit)")
    ap.add_argument("--out", default=None,
                    help="default chiprun_out/paged_decode_bench[_xla].json")
    ap.add_argument("--window", type=int, nargs="+", default=[1024],
                    help="the windowed forms, one a window; 0: none")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--seed", type=int, default=2147485003)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny geometry, any backend, checks only")
    ap.add_argument("--prefix-run", action="store_true",
                    help="time every form on a second table too, the shared "
                    "prefix one ascending run of physical pages")
    ap.add_argument("--latent", action="store_true",
                    help="the latent (MLA) form at Kanana-2's geometry: 32 "
                    "lanes, 32 heads, rows of 512 + 128 lanes")
    ap.add_argument("--latent-rank", type=int, default=512,
                    help="--latent: the latent row's lanes (dots3's sliding "
                    "layers hold 1,024)")
    ap.add_argument("--parent", nargs="+", default=[],
                    help="other trees, their kernels timed beside this "
                    "one's (forms parent_*, parent2_*, ...)")
    args = ap.parse_args()
    args.window = [w for w in args.window if w]
    if args.kv_heads is None:
        args.kv_heads = 8 if args.backend == "xla" else 4
    args.rope_dim, args.rope_lanes = 64, 128
    if args.lanes is None:
        args.lanes = 32 if args.latent else 16
    if args.out is None:
        args.out = "chiprun_out/paged_decode_bench{}.json".format(
            "_xla" if args.backend == "xla" else "")
    if args.rehearse:
        args.lanes, args.heads, args.kv_heads, args.head_dim = 3, 8, 2, 16
        args.page_size, args.num_pages, args.max_pages = 4, 400, 160
        args.min_len, args.max_len, args.shared_prefix = 300, 600, 280
        args.window = args.window and [100]
        args.walk_keys = [32, 64, 256]
        args.shared_keys = args.shared_keys and [0, 280]
        args.latent_rank, args.rope_dim, args.rope_lanes = 128, 16, 128
        if args.backend == "pallas":
            # two whole softmax steps of shared prefix, so that the run
            # table's run copies are taken here too
            args.page_size, args.num_pages, args.max_pages = 16, 240, 96
            args.min_len, args.max_len, args.shared_prefix = 1100, 1500, 1040
            args.window = [100, 400, 700][-len(args.window):]
    if args.shared_keys is None:
        args.shared_keys = [args.shared_prefix]

    import jax
    import jax.numpy as jnp

    from kafka_tpu.ops.pallas import paged_attention as pa

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        print("no TPU: a device time comes only from the chip "
              "(--rehearse checks the command here)", file=sys.stderr)
        return 3
    if args.backend == "xla":
        return bench_xla(args, jax, jnp)
    return bench_pallas(args, jax, jnp, pa)


def bench_pallas(args, jax, jnp, pa) -> int:
    from kafka_tpu.runtime.planner import device_peaks

    on_chip = jax.default_backend() == "tpu"
    modules = {"bench_": pa}
    for i, tree in enumerate(args.parent):
        modules[f"parent{i + 1 if i else ''}_"] = load_parent(tree)
    fns = forms(args, jax, modules)
    tables = {"shuffled": make_case(args, jnp)}
    if args.prefix_run:
        tables["run"] = make_case(args, jnp, prefix_run=True)
    lens = tables["shuffled"][1]
    if args.latent:
        # c~ rows in the K pool's place, k_r rows (padded to a lane tile) in
        # V's; q = [q^ | q_rope], split again in `forms`
        rng = np.random.RandomState(args.seed % 2**31 + 1)
        r, lanes, dt = args.latent_rank, args.rope_lanes, jnp.dtype(args.dtype)
        slots = args.num_pages * args.page_size
        c = jnp.asarray(rng.randn(slots, r).astype(np.float32), dt)
        kr = jnp.asarray(rng.randn(slots, lanes).astype(np.float32), dt)
        q = jnp.asarray(rng.randn(args.lanes, args.heads, r + args.rope_dim)
                        .astype(np.float32), dt)
        tables = {t: ((q, c, kr) + case[3:], ln)
                  for t, (case, ln) in tables.items()}
    runs = {}   # "<form>.<table>": (fn, case, window, table)
    for tname, (case, _) in tables.items():
        outs = {}
        for name, (fn, window) in fns.items():
            outs[name] = np.asarray(fn(*case), np.float32)
            runs[f"{name}.{tname}"] = (fn, case, window, tname)
        for name, (_, window) in fns.items():
            if name.endswith("_walk"):
                continue
            assert np.isfinite(outs[name]).all(), (name, tname)
            # every tree's kernel, bit for bit the installed one's
            twin = "bench_" + name.split("_", 1)[1]
            assert np.array_equal(outs[name], outs[twin]), (name, tname)
            if args.latent or name != twin:
                continue
            call = (pa.paged_decode_attention if window is None else
                    functools.partial(pa.paged_decode_attention_window,
                                      window=window))
            ref = np.asarray(call(*case, page_size=args.page_size,
                                  interpret=not on_chip), np.float32)
            assert np.array_equal(outs[name], ref), (name, tname)
    def step_counts(table, window):
        """(whole steps, run steps) of the walk, summed over the lanes."""
        rows = np.asarray(table)
        return tuple(map(sum, zip(*(
            pa.decode_step_runs(rows[b].tolist(), int(n), window,
                                args.page_size, args.max_pages,
                                PAGES_PER_CHUNK)
            for b, n in enumerate(lens)))))

    steps = {tname: {window: step_counts(case[3], window)
                     for window in {w for _, w in fns.values()}}
             for tname, (case, _) in tables.items()}
    if not on_chip:
        print(json.dumps({"rehearsed": sorted(runs), "steps": {
            t: {str(w): v for w, v in d.items()} for t, d in steps.items()},
            "device": "cpu"}))
        return 0

    trace_dir = tempfile.mkdtemp(prefix="paged_decode_bench_")
    with jax.profiler.trace(trace_dir):
        for _ in range(args.reps):
            for fn, case, *_ in runs.values():
                fn(*case).block_until_ready()
    # one Pallas call a launch, launched form after form, rep after rep
    durations, names = kernel_events(trace_dir)
    if len(durations) != args.reps * len(runs):
        print(f"{len(durations)} Pallas events in the capture, expected "
              f"{args.reps} x {len(runs)}: {sorted(names)}", file=sys.stderr)
        return 1
    _, hbm_bytes_per_s, _ = device_peaks(jax.devices()[0])  # unknown: raises
    item = jnp.dtype(args.dtype).itemsize
    row_bytes = ((args.latent_rank + args.rope_lanes) * item if args.latent
                 else 2 * args.kv_heads * args.head_dim * item)  # K and V
    chunk_bytes = PAGES_PER_CHUNK * args.page_size * row_bytes
    result = {"device": jax.devices()[0].device_kind, "args": vars(args),
              "contexts": [int(n) for n in lens], "forms": {}}
    for i, (name, (_, _, window, tname)) in enumerate(runs.items()):
        durs = durations[i::len(runs)]
        chunks = 0
        for n in lens:
            first, end = pa.decode_chunk_range(
                int(n), window, args.page_size, PAGES_PER_CHUNK)
            chunks += end - first
        us = float(np.median(durs)) / 1e3
        whole, run = steps[tname][window]
        bytes_us = 1e6 * chunks * chunk_bytes / hbm_bytes_per_s
        row = {
            "calls": len(durs), "us_per_call": us,
            "min_us": min(durs) / 1e3, "max_us": max(durs) / 1e3,
            "chunks": chunks, "us_per_chunk": us / chunks,
            "chunk_bytes": chunk_bytes,
            "steps_whole": whole, "steps_run": run,
            "hbm_share": 100.0 * bytes_us / us,
            "us_per_lane": us / args.lanes,
            "bytes_us_per_lane": bytes_us / args.lanes,
            "excess_us_per_lane": (us - bytes_us) / args.lanes,
        }
        result["forms"][name] = row
        print(json.dumps({"form": name, **row}))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
