"""The two Gated DeltaNet kernels alone on the chip (ops/pallas/gdn.py): each
against the row-by-row XLA scan on the same inputs (largest absolute
difference of the outputs and of the state), then timed at Olmo-Hybrid's
served shapes (decode: 16 lanes x 30 heads x 96 x 192; prefill: 1 x 512, 1 x
64 and 4 x 64 rows), with the share of the chip's bandwidth the step kernel
reaches, at each number of heads a grid step that `--step-heads` /
`--chunk-heads` name (default: what the kernels choose).

    python scripts/gdn_bench.py [--step-heads 2 6 30] [--chunk-heads 2 6]
                                [--rehearse]

`--rehearse`: tiny shapes, interpreted, on the CPU: checks the command.
Prints one JSON line a measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from kafka_tpu.models.cache import (  # noqa: E402
    StatePlan, _read_state, _write_state)
from kafka_tpu.ops.pallas import gdn  # noqa: E402


def inputs(B, S, H, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, S, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return q, k, v, g, beta


def timed(fn, leaf, *args, n=20):
    leaf = fn(leaf, *args)[1]
    jax.block_until_ready(leaf)
    t0 = time.perf_counter()
    for _ in range(n):
        o, leaf = fn(leaf, *args)
    jax.block_until_ready((o, leaf))
    return (time.perf_counter() - t0) / n


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--step-heads", type=int, nargs="*", default=[0])
    ap.add_argument("--chunk-heads", type=int, nargs="*", default=[0])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    H, dk, dv, slots, lanes = ((2, 24, 64, 9, 4) if args.rehearse
                               else (30, 96, 192, 65, 16))
    shapes = [(lanes, 1), (1, 512), (1, 64), (4, 64)]
    if args.rehearse:
        shapes = [(lanes, 1), (1, 64), (2, 32)]
    leaf0 = 0.1 * jax.random.normal(jax.random.PRNGKey(7),
                                    (2, slots, dk, H * dv), jnp.float32)
    step_heads, chunk_heads = gdn.step_heads, gdn.head_groups

    programs = {}

    def run(leaf, plan, q, k, v, g, beta, kernel, heads):
        # (the number of heads a grid step is the kernels' own choice: the
        # bench swaps the two functions that make it while a form is traced;
        # one jitted program a form, so a timed call compiles nothing)
        if (kernel, heads) not in programs:
            programs[kernel, heads] = jax.jit(
                lambda leaf, plan, *a: gdn.gdn(
                    leaf, 1, plan, *a, kernel=kernel,
                    read_state=_read_state, write_state=_write_state),
                donate_argnums=(0,))
        if heads:
            gdn.step_heads = lambda *a, **kw: heads
            gdn.head_groups = lambda *a, **kw: [heads]
        try:
            return programs[kernel, heads](leaf, plan, q, k, v, g, beta)
        finally:
            gdn.step_heads, gdn.head_groups = step_heads, chunk_heads

    for B, S in shapes:
        q, k, v, g, beta = inputs(B, S, H, dk, dv, seed=S)
        lens = jnp.full((B,), S, jnp.int32).at[-1].set(max(S - 3, 1))
        plan = StatePlan(lens=lens) if S == 1 else StatePlan(
            lens=lens, src=jnp.arange(B) + 1, dst=jnp.arange(B) + 1,
            snap=jnp.arange(B) + B + 1, fresh=jnp.zeros((B,), bool))
        o_x, l_x = run(jnp.copy(leaf0), plan, q, k, v, g, beta, False, 0)
        real = np.arange(S)[None, :] < np.asarray(lens)[:, None]
        for heads in (args.step_heads if S == 1 else args.chunk_heads):
            o_k, l_k = run(jnp.copy(leaf0), plan, q, k, v, g, beta, True,
                           heads)
            out = {"lanes": B, "rows": S, "heads": H, "d_k": dk, "d_v": dv,
                   "heads_a_step": heads or "default",
                   "out_max_abs_diff": float(np.abs(
                       np.asarray(o_x) - np.asarray(o_k))[real].max()),
                   "state_max_abs_diff": float(jnp.abs(l_x - l_k).max()),
                   "out_max_abs": float(np.abs(np.asarray(o_x))[real].max())}
            del o_k, l_k
            forms = [("kernel", True)]
            if heads == (args.step_heads if S == 1
                         else args.chunk_heads)[0] and (
                    S <= 64 or args.rehearse):
                forms.append(("xla_scan", False))
            for name, kernel in forms:
                fn = lambda leaf, *a, _k=kernel: run(  # noqa: E731
                    leaf, *a, _k, heads)
                out[name + "_ms"] = 1e3 * timed(
                    fn, jnp.copy(leaf0), plan, q, k, v, g, beta,
                    n=3 if args.rehearse else 20)
            if S == 1 and not args.rehearse:
                moved = 2 * 4 * B * H * dk * dv
                out["state_gb_s"] = moved / out["kernel_ms"] / 1e6
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
