"""The two gated-delta kernels alone on the chip (ops/pallas/gated_delta.py):
each against the row-by-row XLA scan on the same inputs (largest absolute
difference of the outputs and of the state), then timed at the served shapes
(decode: 32 lanes x 64 heads x 128 x 128; prefill: 1 x 512, 1 x 64 and 4 x 64
rows), with the share of the chip's bandwidth the step kernel reaches.

    python scripts/gated_delta_bench.py [--heads-a-step N] [--rehearse]

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
from kafka_tpu.ops.pallas import gated_delta as gd  # noqa: E402


def inputs(B, S, H, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, S, H, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, D)))
    v = jax.random.normal(ks[2], (B, S, H, D))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (B, S, H, D)))
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
    ap.add_argument("--heads-a-step", type=int, default=gd.HEADS_A_STEP)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    gd.HEADS_A_STEP = args.heads_a_step
    H, D, slots, lanes = (4, 16, 9, 4) if args.rehearse else (64, 128, 129, 32)
    shapes = [(lanes, 1), (1, 512), (1, 64), (4, 64)]
    if args.rehearse:
        shapes = [(lanes, 1), (1, 64), (2, 32)]
    leaf0 = 0.1 * jax.random.normal(jax.random.PRNGKey(7),
                                    (2, slots, H * D, D), jnp.float32)
    run = jax.jit(
        lambda leaf, plan, q, k, v, g, beta, kernel: gd.gated_delta(
            leaf, 1, plan, q, k, v, g, beta, kernel=kernel,
            read_state=_read_state, write_state=_write_state),
        static_argnums=(7,), donate_argnums=(0,))
    for B, S in shapes:
        q, k, v, g, beta = inputs(B, S, H, D, seed=S)
        lens = jnp.full((B,), S, jnp.int32).at[-1].set(max(S - 3, 1))
        plan = StatePlan(lens=lens) if S == 1 else StatePlan(
            lens=lens, src=jnp.arange(B) + 1, dst=jnp.arange(B) + 1,
            snap=jnp.arange(B) + B + 1, fresh=jnp.zeros((B,), bool))
        o_x, l_x = run(jnp.copy(leaf0), plan, q, k, v, g, beta, False)
        o_k, l_k = run(jnp.copy(leaf0), plan, q, k, v, g, beta, True)
        real = np.arange(S)[None, :] < np.asarray(lens)[:, None]
        out = {"lanes": B, "rows": S, "heads": H, "head_dim": D,
               "heads_a_step": gd.HEADS_A_STEP,
               "out_max_abs_diff": float(np.abs(
                   np.asarray(o_x) - np.asarray(o_k))[real].max()),
               "state_max_abs_diff": float(jnp.abs(l_x - l_k).max()),
               "out_max_abs": float(np.abs(np.asarray(o_x))[real].max())}
        del o_x, l_x, o_k, l_k
        for name, kernel in (("kernel", True), ("xla_scan", False)):
            if S > 64 and not kernel and not args.rehearse:
                continue
            fn = lambda leaf, *a, _k=kernel: run(leaf, *a, _k)  # noqa: E731
            out[name + "_ms"] = 1e3 * timed(
                fn, jnp.copy(leaf0), plan, q, k, v, g, beta,
                n=3 if args.rehearse else 20)
        if S == 1 and not args.rehearse:
            moved = 2 * 4 * B * H * D * D
            out["state_gb_s"] = moved / out["kernel_ms"] / 1e6
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
