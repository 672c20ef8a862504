#!/usr/bin/env python3
"""The selective-scan kernel alone, on the chip: one Mamba layer's recurrence
of phi-4-mini-flash-reasoning (d_inner 5,120, d_state 16) over the cell's
three prefill buckets, and decode's one-step update at 32 lanes.

    python scripts/ssm_scan_bench.py                 # buckets 64 512 2048
    python scripts/ssm_scan_bench.py --rows 2048     # one bucket
    python scripts/ssm_scan_bench.py --rehearse      # CPU, tiny, no times

Through the chip tool, from the repo root.  What is timed is
`ops/pallas/selective_scan.selective_scan` itself, jitted once a form:

    kernel    the Pallas kernel (state resident in VMEM over the time loop)
    xla_scan  the same recurrence as a `lax.scan` over rows (the `xla`
              backend's form): S dependent steps, each an HBM round trip
    assoc     an `associative_scan` over rows (the form the kernel replaces
              at the widths where it fits in HBM at all: --assoc, rows <= 512)
    decode    S = 1 at --lanes lanes: the closed-form step decode runs, with
              the state read and written

Each form is checked against `xla_scan` first (float32: 1e-3), then timed as
--reps launches enqueued back to back and awaited once, the median of three
such trains over --reps: a lone launch awaited by the host reads ~0.9 ms
whatever it holds (my chip run 1, PR 38: decode's one step 0.97 ms), the
train amortises that.  The roofline
share is `benchmarks/ssm_roofline.scan_call`'s bytes over the device's peak
bandwidth (`benchmarks/roofline.py`) over the time; the exponentials a call makes are printed beside it.  Prints
one JSON line a form and writes them all to chiprun_out/ssm_scan_bench.json.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, nargs="+", default=[64, 512, 2048])
    ap.add_argument("--d-inner", type=int, default=5120)
    ap.add_argument("--d-state", type=int, default=16)
    ap.add_argument("--lanes", type=int, default=32, help="decode's lanes")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=2147485003)
    ap.add_argument("--assoc", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny widths, interpreted kernel, no times")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        args.rows, args.d_inner, args.lanes, args.reps = [16, 64], 256, 2, 1

    import jax
    import jax.numpy as jnp

    import roofline
    import ssm_roofline
    from kafka_tpu.ops.pallas.selective_scan import selective_scan

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        print("ssm_scan_bench.py: no TPU (--rehearse checks the command "
              "here)", file=sys.stderr)
        return 3
    di, ds = args.d_inner, args.d_state
    a = -jnp.exp(jnp.broadcast_to(
        jnp.log(jnp.arange(1, ds + 1.0))[:, None], (ds, di)))
    d = jnp.ones(di)

    def operands(lanes, rows):
        ks = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 5)
        x = jax.random.normal(ks[0], (lanes, rows, di))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (lanes, rows, di)) - 4)
        b, c = (jax.random.normal(k, (lanes, rows, ds)) for k in ks[2:4])
        h0 = jax.random.normal(ks[4], (lanes, ds, di))
        return x, dt, b, c, h0, jnp.full((lanes,), rows, jnp.int32)

    def assoc(x, dt, b, c, h0, lens):
        da = jnp.exp(dt[:, :, None, :] * a[None, None])
        dbx = (dt * x)[:, :, None, :] * b[..., None]
        dbx = dbx.at[:, 0].add(da[:, 0] * h0)

        def combine(l, r):
            return l[0] * r[0], r[0] * l[1] + r[1]

        _, h = jax.lax.associative_scan(combine, (da, dbx), axis=1)
        return jnp.einsum("wtsd,wts->wtd", h, c) + d * x, h[:, -1]

    forms = {
        "kernel": lambda *o: selective_scan(
            o[0], o[1], a, o[2], o[3], d, o[4], o[5], kernel=True),
        "xla_scan": lambda *o: selective_scan(
            o[0], o[1], a, o[2], o[3], d, o[4], o[5], kernel=False),
    }
    out = {"device": jax.devices()[0].device_kind, "d_inner": di,
           "d_state": ds, "runs": []}

    def timed(fn, ops):
        fn(*ops)[0].block_until_ready()
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = fn(*ops)
            out[0].block_until_ready()
            ts.append((time.perf_counter() - t0) / args.reps)
        return statistics.median(ts)

    def report(form, lanes, rows, seconds, err):
        exps, nbytes = ssm_roofline.scan_call(lanes, rows, di, ds)
        line = {"form": form, "lanes": lanes, "rows": rows,
                "max_err_vs_xla_scan": err, "exps": exps, "bytes": nbytes}
        if on_chip:
            line.update(ms=seconds * 1e3, roofline_pct=roofline.roofline_share(
                            0.0, nbytes, seconds, out["device"])[0],
                        exps_per_s=exps / seconds)
        out["runs"].append(line)
        print(json.dumps(line), flush=True)

    for rows in args.rows:
        ops = operands(1, rows)
        want = jax.jit(forms["xla_scan"])(*ops)
        todo = dict(forms)
        if args.assoc and rows <= 512:
            todo["assoc"] = assoc
        for form, fn in todo.items():
            fn = jax.jit(fn)
            got = fn(*ops)
            err = max(float(jnp.max(jnp.abs(g - w)))
                      for g, w in zip(got, want))
            if err > 1e-3:
                print(f"ssm_scan_bench.py: {form} at {rows} rows differs "
                      f"from xla_scan by {err}", file=sys.stderr)
                return 1
            report(form, 1, rows, timed(fn, ops) if on_chip else None, err)
    ops = operands(args.lanes, 1)
    fn = jax.jit(forms["xla_scan"])
    report("decode", args.lanes, 1, timed(fn, ops) if on_chip else None, 0.0)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "ssm_scan_bench.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
