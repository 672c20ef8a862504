#!/usr/bin/env python3
"""Latent prefill's key walk alone, on the chip: one full and one sliding
layer of dots3-note-prev at the registered shapes, or (`--heads 32`) one
layer of kanana-2-30b-a3b at its three buckets; the Pallas fold against the
XLA fold, and the kernel taken apart.

    python scripts/latent_prefill_bench.py               # both geometries
    python scripts/latent_prefill_bench.py --rows 256    # another bucket
    python scripts/latent_prefill_bench.py --heads 32    # Kanana-2's layer
    python scripts/latent_prefill_bench.py --rehearse    # CPU, tiny, no times

Through the chip tool, from the repo root.  Defaults are the cell's
(PERF.md section 4): one lane, a 512-row bucket at positions 28,400.. over
a 32,768-key window of 16-row pages scattered in an 8,192-page bf16 pool,
so a full layer walks 29 trips of 1,024 keys with ~2,048 chosen keys a row
and a sliding layer (window 513) the last one or two.  What is timed is
`models/mixers/latent.py::_latent_prefill_walk` itself, jitted once a form:

    materialised (`--heads 32` only) what Kanana-2's prefill ran until PR
                 37, kept here alone: every page of the 16k static window
                 gathered and expanded, one softmax over [heads, rows, 16384]
    xla          the XLA fold (the `xla` backend's form, the parent's)
    kernel       the fold as `ops/pallas/latent_prefill.latent_prefill_fold`
    k_matmuls    the kernel with the softmax taken out (three dots a head)
    k_softmax    the kernel with the dots taken out (the VPU's work a tile)
    k_carries    the kernel moving its blocks and carries only (DMA floor)

Times are each form's module events in one profiler capture (per launch of
the walk) and, for the kernel forms, the Pallas call's own events (per
trip); the rest of a launch is the chunk's gather, its expansion through
W_kvb and the mask, in XLA.  `--heads 32`: 32 heads, no window, no chosen
keys, 8,320 live keys (9 trips) in a 16,384-key window, buckets of 512, 256
and 64 rows in turn (PERF.md section 4).  MXU work is what the calls execute, 2 x lanes
x heads x rows x keys x (d_qk + d_v) a trip, against the bf16 peak.  Prints
one JSON line a form and writes them all to
chiprun_out/latent_prefill_bench.json.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# (heads, d_nope, d_rope, d_v, latent rank, window, chosen keys a row)
GEOMETRY = {"full": (128, 128, 64, 128, 512, None, 2048),
            "sliding": (64, 192, 64, 128, 1024, 513, None),
            "uniform": (32, 128, 64, 128, 512, None, None)}
TINY = {"full": (4, 16, 8, 16, 32, None, 8),
        "sliding": (2, 24, 8, 16, 48, 5, None),
        "uniform": (8, 16, 8, 16, 32, None, None)}
UNIFORM_KEYS, UNIFORM_ROWS, UNIFORM_PAGES = 8320, (512, 256, 64), 1024


def make_case(args, geometry, jnp, latent):
    """One layer's pools, a scattered page table and a bucket of queries,
    from --seed."""
    n, dn, dr, dv, rank, window, topk = geometry
    rng = np.random.RandomState(args.seed % 2**31)
    ps, dt = args.page_size, jnp.dtype(args.dtype)
    slots = args.num_pages * ps
    b, s, C = args.lanes, args.rows, args.max_pages * ps
    live = args.start + s  # keys written when the chunk attends
    lanes = -(-dr // 128) * 128
    k_pool = jnp.asarray(rng.randn(slots, rank).astype(np.float32), dt)
    v_pool = jnp.asarray(rng.randn(slots, lanes).astype(np.float32), dt)
    table = np.zeros((b, args.max_pages), np.int32)
    for i in range(b):
        need = -(-live // ps)
        table[i, :need] = rng.permutation(np.arange(1, args.num_pages))[:need]
    positions = np.broadcast_to(args.start + np.arange(s), (b, s))
    kv_pos = np.broadcast_to(np.arange(C), (b, C))
    paged = latent.PagedView(
        write_idx=jnp.zeros((b, s), jnp.int32),
        read_idx=jnp.zeros((b, C), jnp.int32),
        kv_positions=jnp.asarray(kv_pos, jnp.int32),
        kv_valid=jnp.asarray(kv_pos < live),
        page_table=jnp.asarray(table), page_size=ps)
    chosen = None
    if topk is not None:
        # about topk causal keys a row, as the indexer would leave
        keep = rng.rand(b, s, C) < topk / live
        chosen = jnp.asarray(keep & (kv_pos[:, None] <= positions[..., None]))
    q_nope = jnp.asarray(rng.randn(b, s, n, dn).astype(np.float32), dt)
    q_rope = jnp.asarray(rng.randn(b, s, n, dr).astype(np.float32), dt)
    wkvb = jnp.asarray(
        rng.randn(n, rank, dn + dv).astype(np.float32) * rank ** -0.5, dt)
    scale = float((dn + dr) ** -0.5)
    return dict(q_nope=q_nope, q_rope=q_rope, wkvb=wkvb, k_cache=k_pool,
                v_cache=v_pool, paged=paged,
                positions=jnp.asarray(positions, jnp.int32), scale=scale,
                dn=dn, dr=dr, window=window, chosen_of=chosen)


def take_apart(lp):
    """{name: kernel body}: `_fold_kernel` and three partial forms of it,
    same refs, same blocks."""
    import jax
    import jax.numpy as jnp

    def heads_loop(one_head):
        def body(qn, qr, kn, kr, vt, bias, m, l, acc, m_o, l_o, acc_o, *,
                 scale, heads):
            def head(h, carry):
                one_head(h, qn, qr, kn, kr, vt, bias, m, l, acc, m_o, l_o,
                         acc_o, scale)
                return carry
            jax.lax.fori_loop(0, heads, head, 0)
        return body

    def matmuls(h, qn, qr, kn, kr, vt, bias, m, l, acc, m_o, l_o, acc_o, _):
        sc = jnp.dot(kn[0, h], qn[0, h], preferred_element_type=jnp.float32)
        sc = sc + jnp.dot(kr[0], qr[0, h], preferred_element_type=jnp.float32)
        m_o[0, h], l_o[0, h] = m[0, h], l[0, h]
        acc_o[0, h] = acc[0, h] + jnp.dot(
            vt[0, h], sc.astype(vt.dtype), preferred_element_type=jnp.float32)

    def softmax(h, qn, qr, kn, kr, vt, bias, m, l, acc, m_o, l_o, acc_o,
                scale):
        sc = bias[0] * scale + bias[0]
        m_new = jnp.maximum(m[0, h], jnp.max(sc, axis=0, keepdims=True))
        alpha = jnp.exp(m[0, h] - m_new)
        p = jnp.exp(sc - jnp.where(m_new > lp.NEG_INF, m_new, 0.0))
        l_o[0, h] = alpha * l[0, h] + jnp.sum(p, axis=0, keepdims=True)
        dv = acc.shape[2]
        acc_o[0, h] = alpha * acc[0, h] + p.astype(vt.dtype)[:dv].astype(
            jnp.float32)
        m_o[0, h] = m_new

    def carries(h, qn, qr, kn, kr, vt, bias, m, l, acc, m_o, l_o, acc_o, _):
        m_o[0, h], l_o[0, h], acc_o[0, h] = m[0, h], l[0, h], acc[0, h]

    return {"kernel": lp._fold_kernel, "k_matmuls": heads_loop(matmuls),
            "k_softmax": heads_loop(softmax), "k_carries": heads_loop(carries)}


def forms(case, jax, latent, lp, pallas_pkg):
    """{name: jitted walk}; a kernel form traces the walk with
    `_fold_kernel` swapped for its body."""
    def build(name, body):
        def fn(q_nope, q_rope, wkvb, k_cache, v_cache):
            kw = dict(case, q_nope=q_nope, q_rope=q_rope, wkvb=wkvb,
                      k_cache=k_cache, v_cache=v_cache)
            if body is None:
                return latent._latent_prefill_walk(**kw, kernel=False)
            installed, fold = lp._fold_kernel, lp.latent_prefill_fold
            # the jitted wrapper caches its trace: trace the plain function
            lp._fold_kernel = body
            pallas_pkg.latent_prefill_fold = fold.__wrapped__
            try:
                return latent._latent_prefill_walk(**kw, kernel=True)
            finally:
                lp._fold_kernel = installed
                pallas_pkg.latent_prefill_fold = fold
        fn.__name__ = f"bench_{name}"
        return jax.jit(fn)

    out = {"xla": build("xla", None)}
    out.update({n: build(n, b) for n, b in take_apart(lp).items()})
    if case["window"] is None and case["chosen_of"] is None:
        out["materialised"] = jax.jit(materialised(case, latent))
    return out


def materialised(case, latent):
    """The form `_latent_attention_block` ran for a model of one kind of
    layer until PR 37: the page table's every page, expanded, one softmax."""
    import jax.numpy as jnp

    paged, positions, dn = case["paged"], case["positions"], case["dn"]

    def bench_materialised(q_nope, q_rope, wkvb, k_cache, v_cache):
        dt, ps = q_nope.dtype, paged.page_size
        c_win = latent._kv_read_pages(k_cache, paged.page_table, ps, dt)
        r_win = latent._kv_read_pages(
            v_cache, paged.page_table, ps, dt)[..., :case["dr"]]
        mask = ((positions[:, :, None] >= paged.kv_positions[:, None, :])
                & paged.kv_valid[:, None, :])
        kv = jnp.einsum("btr,nrd->btnd", c_win, wkvb)
        return latent._latent_attend(q_nope, q_rope, kv[..., :dn], r_win,
                                    kv[..., dn:], mask, case["scale"],
                                    shared=False)

    return bench_materialised


def events(trace_dir, names):
    """({form: [device ns of each launch]}, {form: [ns of each Pallas call
    inside its launches]}) from the capture's module and op lines."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    launches = {n: [] for n in names}
    calls = []
    for plane in ProfileData.from_file(path).planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    m = re.match(r"jit_bench_(\w+)\(", ev.name)
                    if m and m.group(1) in launches:
                        launches[m.group(1)].append(
                            (ev.start_ns, ev.duration_ns))
            elif line.name == "XLA Ops":
                calls += [(ev.start_ns, ev.duration_ns) for ev in line.events
                          if "latent_prefill_fold" in ev.name]
    inside = {n: [d for t0, dur in spans for s0, d in calls
                  if t0 <= s0 < t0 + dur] for n, spans in launches.items()}
    return {n: [d for _, d in sorted(v)] for n, v in launches.items()}, inside


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--lanes", type=int, default=1)
    ap.add_argument("--rows", type=int, default=512, help="the bucket")
    ap.add_argument("--start", type=int, default=28400,
                    help="position of the bucket's first row")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=8192)
    ap.add_argument("--max-pages", type=int, default=2048)
    ap.add_argument("--kinds", nargs="+", default=["full", "sliding"],
                    choices=["full", "sliding"])
    ap.add_argument("--heads", type=int, default=128, choices=[128, 32],
                    help="128: dots3's kinds; 32: Kanana-2's one kind at its "
                    "buckets and live context (--rows / --start ignored)")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--seed", type=int, default=2147485003)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny geometry, any backend, checks only")
    args = ap.parse_args()
    geometries = GEOMETRY
    uniform_keys, uniform_rows = UNIFORM_KEYS, UNIFORM_ROWS
    if args.rehearse:
        geometries = TINY
        args.rows, args.start, args.page_size = 8, 40, 4
        args.num_pages, args.max_pages, args.dtype = 64, 16, "float32"
        uniform_keys, uniform_rows = 48, (8,)
    elif args.heads == 32:
        args.max_pages = UNIFORM_PAGES
    # (result key, geometry, bucket, position of its first row)
    cases = [(kind, kind, args.rows, args.start) for kind in args.kinds]
    if args.heads == 32:
        cases = [(f"uniform.{r}", "uniform", r, uniform_keys - r)
                 for r in uniform_rows]

    import jax
    import jax.numpy as jnp

    import kafka_tpu.ops.pallas as pallas_pkg
    from kafka_tpu.models.mixers import latent
    from kafka_tpu.ops.pallas import latent_prefill as lp
    from kafka_tpu.runtime.planner import device_peaks

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        print("no TPU: a device time comes only from the chip "
              "(--rehearse checks the command here)", file=sys.stderr)
        return 3
    tol = 1e-5 if args.dtype == "float32" else 2e-2
    result = {"device": jax.devices()[0].device_kind, "args": vars(args),
              "kinds": {}}
    for kind, shape, bucket, start in cases:
        args.rows, args.start = bucket, start
        geometry = geometries[shape]
        n, dn, dr, dv = geometry[:4]
        case = make_case(args, geometry, jnp, latent)
        arrays = [case[k] for k in
                  ("q_nope", "q_rope", "wkvb", "k_cache", "v_cache")]
        fns = forms(case, jax, latent, lp, pallas_pkg)
        outs = {name: np.asarray(fn(*arrays), np.float32)
                for name, fn in fns.items()}
        err = float(np.abs(outs["kernel"] - outs["xla"]).max())
        assert np.isfinite(outs["xla"]).all() and err <= tol, (kind, err)
        if "materialised" in outs:
            was = float(np.abs(outs["materialised"] - outs["xla"]).max())
            assert was <= tol, (kind, "materialised", was)
        if not on_chip:
            result["kinds"][kind] = {"rehearsed": sorted(fns),
                                     "max_abs_diff_kernel_vs_xla": err}
            continue
        trace_dir = tempfile.mkdtemp(prefix="latent_prefill_bench_")
        with jax.profiler.trace(trace_dir):
            for _ in range(args.reps):
                for fn in fns.values():
                    fn(*arrays).block_until_ready()
        launches, calls = events(trace_dir, list(fns))
        peak_flops, _, _ = device_peaks(jax.devices()[0])  # unknown: raises
        ck = min(latent.PREFILL_WALK_KEYS, args.max_pages * args.page_size)
        rows = args.rows + -args.rows % 128
        flop_trip = 2.0 * args.lanes * n * rows * ck * (dn + dr + dv)
        forms_out = {}
        for name in fns:
            durs = launches[name]
            if len(durs) != args.reps:
                print(f"{len(durs)} launches of {name}, expected {args.reps}",
                      file=sys.stderr)
                return 1
            row = {"ms_per_launch": float(np.median(durs)) / 1e6,
                   "min_ms": min(durs) / 1e6, "max_ms": max(durs) / 1e6}
            if calls[name]:
                trips = len(calls[name]) // args.reps
                us = float(np.median(calls[name])) / 1e3
                row.update(
                    trips_per_launch=trips, us_per_trip=us,
                    kernel_ms_per_launch=sum(calls[name]) / args.reps / 1e6,
                    mxu_share=100.0 * flop_trip / peak_flops / (us / 1e6))
            forms_out[name] = row
            print(json.dumps({"kind": kind, "form": name, **row}))
        result["kinds"][kind] = {
            "forms": forms_out, "max_abs_diff_kernel_vs_xla": err,
            "flop_per_trip": flop_trip,
            "blocks": lp.fold_blocks(n, rows, ck, dn, dr, dv,
                                     jnp.dtype(args.dtype).itemsize)}
    if not on_chip:
        print(json.dumps({"device": "cpu", **result["kinds"]}))
        return 0
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/latent_prefill_bench.json", "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
