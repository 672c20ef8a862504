#!/usr/bin/env python3
"""The routed feed-forward block alone, on the chip, in its two forms and
with each candidate grouped matmul, at every routed configuration's widths
(`benchmarks/configs/`: Mixtral 8 experts top-2, Mellum2 64 top-8, Kanana-2
128 top-6, K-EXAONE 16 held of 128 top-8, dots3 32 held of 256 top-8, LFM2 32
top-4) and at the row counts one pass can have (decode lanes, the prefill
buckets, a batched prefill's lanes x bucket).

    python scripts/moe_dispatch_bench.py                 # every config
    python scripts/moe_dispatch_bench.py --configs kanana-2-30b-a3b --rows 512
    python scripts/moe_dispatch_bench.py --gmm           # + megablox gmm
    python scripts/moe_dispatch_bench.py --parent DIR    # + DIR's block
    python scripts/moe_dispatch_bench.py --rehearse      # CPU, tiny, no times

Through the chip tool, from the repo root.  One routed layer of each
configuration (random bf16 weights and rows from `--seed`, routed by the
model's own rule, so groups are as uneven as random routing makes them,
some empty, most not a multiple of any row tile), each form jitted under a
name of its own; times are the jitted programs' device durations in one
profiler capture (`XLA Modules`), routing included, the shared expert left
out.  Forms:

  installed      `models/ffn._moe_block` as `moe_dispatch_form` chooses
  dense          every row through every held expert (the block where
                 the token form is not chosen, and on meshes)
  token          `_experts_token` at every row count: picks sorted by expert,
                 `ops/pallas/grouped_matmul.py` (megablox `gmm`, its tiling):
                 an expert no row picked is never fetched
  ragged_dot     the same with `jax.lax.ragged_dot` as XLA lowers it
  gmm_<m>x<k>x<n>  (--gmm) the same with `gmm` at other tiles: rows, most of
                 the contraction, most of the output
  parent         (--parent DIR) the `_moe_block` of the tree unpacked at DIR

`--ops N` adds each form's N longest device ops (self time inside the
program, a launch) to its line.

Beside each time: the FLOPs the chosen rows need (2 x 3 x picks on held
experts x H x F, against the bf16 peak) and the bytes of the held experts'
weights read once (against the HBM peak), whichever bounds the block: the
floor a form can reach; and `picked_floor_us`, the same for the weights of
the experts some row picked alone (`experts_read` of `experts_held`, as the
token form counts them), the floor of a form that skips the rest.  Prints
one JSON line a form and writes them all to `--out`; `--table` prints the
markdown table of PERF.md section 6.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import importlib.util
import json
import os
import re
import sys
import tempfile

import numpy as np

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

CONFIGS = ("mixtral-8x7b", "mellum2-12b-a2.5b", "kanana-2-30b-a3b",
           "k-exaone-236b-a23b", "dots3-note-prev", "lfm2-8b-a1b")
# decode lanes | the prefill buckets | a batched prefill's lanes x bucket
ROWS = (16, 32, 64, 128, 256, 512, 1024, 2048)
# (rows, most of the contraction, most of the output) a tile: each width is
# cut to its largest multiple of 128 lanes that divides the matrix's
GMM_TILINGS = ((128, 512, 512), (128, 1024, 1024), (256, 1024, 1024),
               (512, 1024, 1024), (128, 2048, 1024))
GMM_MIN_ROWS = 256


def load_parent(tree, home="ffn.py"):
    """The module `home` (under `kafka_tpu/models/`) of the tree unpacked at
    `tree` (its `models/llama.py` where the tree has no such file: that held
    every block before PR 58), under a module name of its own inside the
    installed package (its relative imports resolve there; the installed
    module stays what it is)."""
    models = os.path.join(tree, "kafka_tpu", "models")
    if not os.path.exists(os.path.join(models, home)):
        home = "llama.py"
    package = ".".join(["kafka_tpu", "models"] + home.split("/")[:-1])
    spec = importlib.util.spec_from_file_location(
        f"{package}.parent_{os.path.basename(home)[:-3]}",
        os.path.join(models, home))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def module_events(trace_dir, names):
    """({name: [device ns of each launch of jit_<name>]}, {name: {op: ns a
    launch}}) of a capture; an op's time is its own events' (the `while` of
    a loop beside its body's ops: read the leaves)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans, ops = {n: [] for n in names}, []
    for plane in ProfileData.from_file(path).planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    m = re.match(r"jit_(\w+)", ev.name)
                    if m and m.group(1) in spans:
                        spans[m.group(1)].append(
                            (ev.start_ns, ev.duration_ns))
            elif line.name == "XLA Ops":
                ops += [(ev.start_ns, ev.duration_ns,
                         ev.name.split(" = ")[0].lstrip("%"))
                        for ev in line.events]
    ops.sort()
    starts = [o[0] for o in ops]
    by_op = {}
    for name, sps in spans.items():
        acc = by_op.setdefault(name, {})
        for t0, dur in sps:
            lo, hi = bisect.bisect_left(starts, t0), bisect.bisect_left(
                starts, t0 + dur)
            for _, d, op in ops[lo:hi]:
                acc[op] = acc.get(op, 0.0) + d / len(sps)
    return {n: [d for _, d in sps] for n, sps in spans.items()}, by_op


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--configs", nargs="+", default=list(CONFIGS))
    ap.add_argument("--rows", type=int, nargs="+", default=list(ROWS))
    ap.add_argument("--gmm", action="store_true")
    ap.add_argument("--forms", nargs="+", help="these forms only")
    ap.add_argument("--parent", help="an unpacked tree whose routed block "
                    "is timed beside the installed one")
    ap.add_argument("--pad-share", type=float, default=0.0,
                    help="share of the rows past chunk_len (token forms "
                    "leave them out of every group)")
    ap.add_argument("--seed", type=int, default=2147485003)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/moe_dispatch_bench.json")
    ap.add_argument("--table", action="store_true")
    ap.add_argument("--ops", type=int, default=0,
                    help="list each form's N longest device ops")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths, any backend, checks only")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from kafka_tpu.models import config as model_config
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from kafka_tpu.models import ffn
    import kafka_tpu.ops.pallas.grouped_matmul as gm
    from kafka_tpu.runtime.planner import device_peaks

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        print("no TPU: a device time comes only from the chip "
              "(--rehearse checks the command here)", file=sys.stderr)
        return 3
    dt = jnp.bfloat16 if on_chip else jnp.float32
    tilings = GMM_TILINGS if args.gmm else ()
    if args.rehearse:
        args.rows, args.reps = [16, 96], 1
        tilings = ((128, 128, 128),) if args.gmm else ()
    parent = load_parent(args.parent)._moe_block if args.parent else None

    def block_with(min_rows, matmul=None, rows_a_tile=None):
        """`_moe_block` taking the token form from `min_rows` rows (and at
        no fewer, whatever share the pass is expected to leave unread), with
        `matmul` in `grouped_matmul`'s place and `rows_a_tile` rows a tile
        (the installed ones where None); -> (output, experts read)."""
        def block(x, lp, cfg, chunk_len):
            saved = (ffn.TOKEN_DISPATCH_MIN_ROWS, gm.grouped_matmul,
                     gm.tile_rows, ffn.TOKEN_DISPATCH_MIN_UNREAD)
            ffn.TOKEN_DISPATCH_MIN_ROWS = min_rows
            ffn.TOKEN_DISPATCH_MIN_UNREAD = 2.0
            gm.grouped_matmul = matmul or saved[1]
            if rows_a_tile:
                gm.tile_rows = lambda rows, groups: rows_a_tile
            try:
                return ffn._moe_block(x, lp, cfg, chunk_len)
            finally:
                (ffn.TOKEN_DISPATCH_MIN_ROWS, gm.grouped_matmul,
                 gm.tile_rows, ffn.TOKEN_DISPATCH_MIN_UNREAD) = saved
        return block

    # (the alternatives are handed the layer's own matrices, `rhs[layer]`:
    # alone, the block's stack is one layer deep and the slice is a view)
    def gmm_at(most_k, most_n):
        def matmul(lhs, rhs, sizes, layer, rows_a_tile):
            return gmm(lhs, rhs[layer], sizes,
                       preferred_element_type=lhs.dtype,
                       tiling=(rows_a_tile,
                               gm.whole_tile(rhs.shape[2], most_k),
                               gm.whole_tile(rhs.shape[3], most_n)),
                       interpret=not on_chip)
        return matmul

    def ragged_dot(lhs, rhs, sizes, layer, rows_a_tile):
        return jax.lax.ragged_dot(lhs, rhs[layer], sizes)

    blocks = {
        "installed": ffn._moe_block,
        "dense": block_with(sys.maxsize),
        "token": block_with(0),
        "ragged_dot": block_with(0, ragged_dot),
    }
    for tm, tk, tn in tilings:
        blocks[f"gmm_{tm}x{tk}x{tn}"] = block_with(0, gmm_at(tk, tn), tm)
    if parent is not None:
        blocks["parent"] = lambda x, lp, cfg, chunk_len: (
            parent(x, lp, cfg), cfg.num_experts)
    if args.forms:
        blocks = {form: block for form, block in blocks.items()
                  if form == "dense" or form.startswith(tuple(args.forms))}

    peak_flops = hbm_bytes_per_s = None
    if on_chip:
        peak_flops, hbm_bytes_per_s, _ = device_peaks(jax.devices()[0])
    result = {"device": jax.devices()[0].device_kind, "args": vars(args),
              "min_rows": ffn.TOKEN_DISPATCH_MIN_ROWS,
              "min_unread": ffn.TOKEN_DISPATCH_MIN_UNREAD, "forms": []}
    rng = np.random.RandomState(args.seed % 2**31)
    for name in args.configs:
        cfg = model_config.config_from_hf_json(
            os.path.join(ROOT, "benchmarks", "configs", name + ".json"))
        if args.rehearse:
            cfg = cfg.replace(hidden_size=128, intermediate_size=128)
        # the routed experts alone: the shared branch is the same in every form
        cfg = cfg.replace(shared_intermediate_size=0)
        h, f, held = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
        k = cfg.num_experts_per_tok

        def normal(*shape, scale):
            return jnp.asarray(
                rng.standard_normal(shape).astype(np.float32) * scale, dt)
        lp = {"router": normal(h, cfg.num_router_experts, scale=h ** -0.5),
              "wg": normal(held, h, f, scale=h ** -0.5),
              "wu": normal(held, h, f, scale=h ** -0.5),
              "wd": normal(held, f, h, scale=f ** -0.5)}
        if cfg.moe_scoring == "sigmoid":
            lp["router_bias"] = jnp.zeros((cfg.num_router_experts,),
                                          jnp.float32)
        tag = re.sub(r"\W", "_", name)
        forms, outs = {}, {}
        for rows in args.rows:
            x = normal(1, rows, h, scale=1.0)
            chunk_len = jnp.int32(round(rows * (1.0 - args.pad_share)))
            for form, block in blocks.items():
                if on_chip and form.startswith("gmm_") and rows < GMM_MIN_ROWS:
                    continue

                # (the form's number rides out as a constant: two forms that
                # lower to one text, `installed` and the form it chose, would
                # share one cached executable, and its name in the capture)
                def fn(x, lp, chunk_len, block=block,
                       uid=list(blocks).index(form)):
                    out, read = block(x, lp, cfg, chunk_len)
                    return out, jnp.int32(read), jnp.int32(uid)
                fn.__name__ = f"{tag}_{rows}_{form}"
                jitted = jax.jit(fn)
                try:
                    out, read, _ = jitted(x, lp, chunk_len)
                    out.block_until_ready()
                except Exception as e:  # a tiling the compiler refuses
                    print(f"{fn.__name__}: {type(e).__name__}: "
                          f"{str(e)[:300]}", file=sys.stderr)
                    continue
                forms[fn.__name__] = (jitted, (x, lp, chunk_len), rows, form,
                                      int(read))
                outs[(rows, form)] = np.asarray(out, np.float32)
        for (rows, form), out in outs.items():
            assert np.isfinite(out).all(), (name, rows, form)
            real = int(round(rows * (1.0 - args.pad_share)))
            err = float(np.abs(out[0, :real]
                               - outs[(rows, "dense")][0, :real]).max())
            # f32 on the CPU: the forms differ by summation order alone
            assert on_chip or err < 1e-4, (name, rows, form, err)
            forms[f"{tag}_{rows}_{form}"] += (err,)
        if not on_chip:
            print(json.dumps({"rehearsed": sorted(forms), "device": "cpu"}))
            continue
        trace_dir = tempfile.mkdtemp(prefix="moe_dispatch_bench_")
        with jax.profiler.trace(trace_dir):
            for _ in range(args.reps):
                for jitted, a, *_ in forms.values():
                    jitted(*a)[0].block_until_ready()
        events, by_op = module_events(trace_dir, list(forms))
        # the experts some row picked, as the token form counted them
        picked = {rows: read for _, _, rows, form, read, _ in forms.values()
                  if form == "token"}
        for fname, (_, (x, _, _), rows, form, read, err) in forms.items():
            durs = events[fname]
            if len(durs) != args.reps:
                print(f"{len(durs)} launches of {fname} in the capture, "
                      f"expected {args.reps}", file=sys.stderr)
                return 1
            us = float(np.median(durs)) / 1e3
            # a row's picks land on a held expert with the held share's odds
            real = round(rows * (1.0 - args.pad_share))
            flops = (2.0 * 3 * real * k * held / cfg.num_router_experts
                     * h * f)
            nbytes = 3.0 * held * h * f * 2
            floor_us = 1e6 * max(flops / peak_flops, nbytes / hbm_bytes_per_s)
            row = {"config": name, "rows": rows, "form": form,
                   "chosen": ffn.moe_dispatch_form(
                       rows, held, k, False, cfg.num_router_experts),
                   "us": us, "min_us": min(durs) / 1e3,
                   "max_us": max(durs) / 1e3, "floor_us": floor_us,
                   "needed_flops": flops, "weight_bytes": nbytes,
                   "experts_read": read, "experts_held": held,
                   "max_abs_diff_vs_dense": err}
            if rows in picked:
                row["picked_floor_us"] = 1e6 * max(
                    flops / peak_flops,
                    nbytes * picked[rows] / held / hbm_bytes_per_s)
            if args.ops:
                top = sorted(by_op[fname].items(), key=lambda kv: -kv[1])
                row["ops_us"] = {op: round(ns / 1e3, 1)
                                 for op, ns in top[:args.ops]}
            result["forms"].append(row)
            print(json.dumps(row))
        del lp, forms, outs
        jax.clear_caches()
    if on_chip:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
        if args.table:
            print_table(result["forms"])
    return 0


def print_table(rows) -> None:
    """us a layer by (config, rows) x form; beside them the floor of
    reading every held expert and (read / held) that of the picked ones."""
    forms = list(dict.fromkeys(r["form"] for r in rows))
    print("| config | rows | chosen | floor | picked floor (read / held) | "
          + " | ".join(forms) + " |")
    print("|---|---|---|---|---|" + "---|" * len(forms))
    cells = {}
    for r in rows:
        cells.setdefault((r["config"], r["rows"]), {})[r["form"]] = r
    for (config, n), by in cells.items():
        any_row = next(iter(by.values()))
        least = "-"
        if "token" in by:
            least = (f"{by['token']['picked_floor_us']:.0f} "
                     f"({by['token']['experts_read']} / "
                     f"{any_row['experts_held']})")
        print(f"| {config} | {n} | {any_row['chosen']} | "
              f"{any_row['floor_us']:.0f} | {least} | " + " | ".join(
                  f"{by[f]['us']:.0f}" if f in by else "-" for f in forms)
              + " |")


if __name__ == "__main__":
    sys.exit(main())
