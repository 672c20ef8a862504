#!/usr/bin/env python3
"""The indexer's selection step at decode alone, on the chip, at dots3's
shapes (`benchmarks/configs/dots3-note-prev.json`: 32 lanes, a page table
2,048 wide, pages of 16, 64 index heads of 128, a bf16 pool of 8,192 pages,
index_topk 2,048): `models/mixers/index._paged_index_choice` over one layer's
indexer rows, under page tables that share more or less of their leading
columns.

    python scripts/index_walk_bench.py                # every table
    python scripts/index_walk_bench.py --parent DIR   # + DIR's function
    python scripts/index_walk_bench.py --ops 8        # + the longest ops
    python scripts/index_walk_bench.py --pass-bits 2 4    # + `mask` at these
    python scripts/index_walk_bench.py --rehearse     # CPU, tiny, no times

Through the chip tool, from the repo root.  Tables (`--tables`):

  shared     the cell: every lane holds the same `--shared-keys` leading
             keys' pages (28,208: the system prompt) and 300-1,800 keys of
             its own behind them
  distinct   no two lanes share a page (two lanes alias no column): what a
             batch of distinct prompts costs, the parent's time
  halves     two groups of lanes on two prefixes: nothing is common to all
  idle       `shared` with every fourth lane idle on the trash page
  one        one lane, which shares everything with itself

Forms: `installed` (this tree's function), `parent` (--parent DIR: the
function of the tree unpacked at DIR), and this tree's parts alone:
`walk` (`_paged_index_scores`: both loops), `common` (`_common_pages`),
`mask` (`_chosen_mask` over the walk's scores; its line also gives the
dependent passes the installed form makes, key search and position search;
`mask_r<R>` with `--pass-bits R`: the same at R bits a pass), `compact`
(`_compact_chosen`).  Times
are the jitted programs' device durations in one profiler capture (`XLA
Modules`); beside them whether the installed form chose the parent's slots.
Prints one JSON line a form and writes them all to `--out`.
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

TABLES = ("shared", "distinct", "halves", "idle", "one")


def page_tables(kind, rng, lanes, P, ps, num_pages, shared_keys, own_keys):
    """(table [B, P] int32, seq_lens [B], active [B]) of one `kind`."""
    if kind == "one":
        lanes = 1
    lens = shared_keys + rng.randint(own_keys[0], own_keys[1] + 1, size=lanes)
    lens = np.minimum(lens, P * ps - 1).astype(np.int32)
    n_shared = shared_keys // ps
    table = np.zeros((lanes, P), np.int32)
    active = np.ones(lanes, bool)
    free = iter(range(1, num_pages))
    if kind == "distinct":
        # lane b's column j: no two lanes alike in any column (the pool holds
        # a quarter of 32 lanes' pages; a timing reads what it reads)
        cols = np.arange(P)[None, :] * lanes + np.arange(lanes)[:, None]
        live = np.arange(P)[None, :] * ps <= lens[:, None]
        return (np.where(live, 1 + cols % (num_pages - 1), 0).astype(np.int32),
                lens, active)
    prefixes = [[next(free) for _ in range(n_shared)]
                for _ in range(2 if kind == "halves" else 1)]
    for b in range(lanes):
        pages = list(prefixes[b * len(prefixes) // lanes])
        pages += [next(free) for _ in range(lens[b] // ps + 1 - n_shared)]
        table[b, :len(pages)] = pages
    if kind == "idle":
        active[::4] = False
        table[::4] = 0
        lens[::4] = 0
    return table, lens, active


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tables", nargs="+", default=list(TABLES),
                    choices=TABLES)
    ap.add_argument("--parent", help="an unpacked tree whose function is "
                    "timed beside the installed one")
    ap.add_argument("--shared-keys", type=int, default=28208)
    ap.add_argument("--own-keys", type=int, nargs=2, default=[300, 1800])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--ops", type=int, default=0)
    ap.add_argument("--pass-bits", type=int, nargs="+", default=[],
                    help="also time `mask` with `index.PASS_BITS` at these")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "index_walk_bench.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from kafka_tpu.models import config as model_config
    from kafka_tpu.models.mixers import index
    from kafka_tpu.runtime.step_programs import decode_plan
    from moe_dispatch_bench import load_parent, module_events

    on_chip = jax.default_backend() == "tpu"
    if not (on_chip or args.rehearse):
        print("no TPU here: run through the chip tool, or --rehearse",
              file=sys.stderr)
        return 3
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "dots3-note-prev.json")) as f:
        serving = json.load(f)["serving"]
    cfg = model_config.config_from_hf_json(os.path.join(
        ROOT, "benchmarks", "configs", "dots3-note-prev.json"))
    lanes, ps = serving["max_batch"], serving["page_size"]
    P, num_pages = serving["max_pages_per_seq"], serving["num_pages"]
    dt = jnp.bfloat16
    if args.rehearse:
        lanes, ps, P, num_pages, dt = 4, 4, 64, 256, jnp.float32
        cfg = cfg.replace(index_topk=16, index_n_heads=4)
        args.shared_keys, args.own_keys, args.reps = 150, [5, 60], 1
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    parent = (load_parent(args.parent, "mixers/index.py") if args.parent
              else None)

    def mask_passes(r):
        """Dependent passes of `_chosen_mask` at r bits a pass: [the key
        search, the position search] (the parent's loops: 32 + 15)."""
        return [-(-n // r) for n in (32, (P * ps - 1).bit_length())]

    rng = np.random.RandomState(args.seed % 2**31)
    # one layer's indexer rows (`_flat_pool` of a stack one layer deep)
    pool = jnp.asarray(rng.standard_normal((num_pages * ps, di)), dt)
    result = {"device": jax.devices()[0].device_kind, "args": vars(args),
              "forms": []}
    forms = {}
    for kind in args.tables:
        table, lens, active = page_tables(
            kind, rng, lanes, P, ps, num_pages, args.shared_keys,
            args.own_keys)
        b = table.shape[0]
        q = jnp.asarray(rng.standard_normal((b, 1, hi, di)), dt)
        w = jnp.asarray(rng.standard_normal((b, 1, hi)) * (hi * di) ** -0.5,
                        jnp.float32)
        lane_state = tuple(map(jnp.asarray, (table, lens, active)))

        # (the plan is built inside each program, as a decode step builds it)
        def choice(mod):
            def fn(q, w, pool, lane_state):
                positions, paged = decode_plan(*lane_state, ps)
                return mod._paged_index_choice(q, w, pool, paged, positions,
                                               cfg, dt)
            return fn

        def walk(q, w, pool, lane_state):
            return index._paged_index_scores(
                q, w, pool, decode_plan(*lane_state, ps)[1], dt)

        def top_k(scores, lane_state):
            positions, paged = decode_plan(*lane_state, ps)
            causal = (paged.kv_valid[:, None, :] & (
                paged.kv_positions[:, None, :] <= positions[:, :, None]))
            return index._chosen_mask(scores, causal, cfg.index_topk)

        outs = {}

        def run(form, fn, a):
            """`fn` jitted under the name the capture shows.  (The form's
            number rides out as a constant: two tables or two trees that
            lower to one text would share one cached executable, and its
            name: moe_dispatch_bench.py.)"""
            def named(*a, uid=len(forms)):
                return fn(*a), jnp.int32(uid)
            named.__name__ = f"{kind}_{form}"
            jitted = jax.jit(named)
            outs[form] = jax.block_until_ready(jitted(*a))[0]
            forms[named.__name__] = (jitted, a, kind, form)

        a = (q, w, pool, lane_state)
        run("installed", choice(index), a)
        run("walk", walk, a)
        if parent is not None:
            run("parent", choice(parent), a)
        run("mask", top_k, (outs["walk"], lane_state))
        for r in args.pass_bits:  # traced inside `run`, under this constant
            with mock.patch.object(index, "PASS_BITS", r):
                run(f"mask_r{r}", top_k, (outs["walk"], lane_state))
            assert (outs[f"mask_r{r}"] == outs["mask"]).all(), (kind, r)
        run("compact", lambda c, r: index._compact_chosen(
            c[:, 0], r, cfg.index_topk),
            (outs["mask"], decode_plan(*lane_state, ps)[1].read_idx))
        run("common", lambda lane_state: index._common_pages(
            decode_plan(*lane_state, ps)[1])[1], (lane_state,))
        row = {"table": kind, "lanes": int(b), "live_keys": int(lens.sum()),
               "common_pages": int(outs["common"]),
               "mask_passes": mask_passes(index.PASS_BITS)}
        slots, ok = (np.asarray(x) for x in outs["installed"])
        assert ok[active].any() and not ok[~active].any(), kind
        if parent is not None:
            p_slots, p_ok = (np.asarray(x) for x in outs["parent"])
            row["ok_equal"] = bool((ok == p_ok).all())
            row["slots_differ"] = int(((slots != p_slots) & ok[:, 0]).sum())
            # f32 on the CPU: one sum order a dot, the same set
            assert on_chip or (row["ok_equal"] and not row["slots_differ"])
        result["forms"].append(row)
        print(json.dumps(row))
    if not on_chip:
        print(json.dumps({"rehearsed": sorted(forms), "device": "cpu"}))
        return 0
    trace_dir = tempfile.mkdtemp(prefix="index_walk_bench_")
    with jax.profiler.trace(trace_dir):
        for _ in range(args.reps):
            for jitted, a, *_ in forms.values():
                jax.block_until_ready(jitted(*a))
    events, by_op = module_events(trace_dir, list(forms))
    for fname, (_, _, kind, form) in forms.items():
        durs = events[fname]
        if len(durs) != args.reps:
            print(f"{len(durs)} launches of {fname} in the capture, "
                  f"expected {args.reps}", file=sys.stderr)
            return 1
        row = {"table": kind, "form": form,
               "us": float(np.median(durs)) / 1e3,
               "min_us": min(durs) / 1e3, "max_us": max(durs) / 1e3}
        if form.startswith("mask"):
            row["passes"] = mask_passes(
                int(form.partition("_r")[2] or index.PASS_BITS))
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
