"""The two SSD kernels alone on the chip (ops/pallas/ssd.py): each against the
row-by-row XLA scan on the same inputs (largest absolute difference of the
outputs and of the state), then timed at the served shapes (decode: 32 lanes x
32 heads x 128 x 256; prefill: 1 x 512, 1 x 128 and 4 x 128 rows), with the
share of the chip's bandwidth the step kernel reaches.

    python scripts/ssd_bench.py [--state-block-mb N] [--attention] [--rehearse]

`--attention`: first the two attention kernels of the SAME layer at the
model's 20 query / 4 KV heads x 128 (a group of FIVE query rows a KV head,
which no other configuration has): `paged_decode_attention` for 32 ragged
lanes up to 8.9k keys and `paged_prefill_attention` at the three prefill
buckets, each against ops/attention.py's XLA formulation over the gathered
window in float32 (chip_smoke.py's check at another geometry).
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
from kafka_tpu.ops.pallas import ssd as sk  # noqa: E402


def inputs(B, S, H, P, G, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    step = jnp.exp(jax.random.uniform(ks[3], (B, S, H), jnp.float32,
                                      jnp.log(0.001), jnp.log(0.3)))
    x = jax.nn.silu(jax.random.normal(ks[0], (B, S, H, P))) * step[..., None]
    Bm = jax.nn.silu(jax.random.normal(ks[1], (B, S, G, N)))
    Cm = jax.nn.silu(jax.random.normal(ks[2], (B, S, G, N)))
    g = -step * jax.random.uniform(ks[4], (H,), jnp.float32, 1.0, 16.0)
    return x, Bm, Cm, g


def timed(fn, leaf, *args, n=20):
    leaf = fn(leaf, *args)[1]
    jax.block_until_ready(leaf)
    t0 = time.perf_counter()
    for _ in range(n):
        o, leaf = fn(leaf, *args)
    jax.block_until_ready((o, leaf))
    return (time.perf_counter() - t0) / n


def attention_leg(rehearse: bool) -> None:
    """Both attention kernels at 20 / 4 x 128 against the XLA formulation."""
    from kafka_tpu.ops.attention import causal_attention
    from kafka_tpu.ops.pallas import (
        paged_decode_attention, paged_prefill_attention)

    interpret = jax.default_backend() != "tpu"
    Hq, Hkv, D, ps = (5, 1, 16, 16) if rehearse else (20, 4, 128, 16)
    B, P, num_pages = (4, 16, 80) if rehearse else (32, 1024, 5120)
    buckets = (64,) if rehearse else (128, 256, 512)
    HD, C = Hkv * D, P * ps
    rng = np.random.RandomState(0)
    dt = jnp.bfloat16
    k_pool = jnp.asarray(rng.randn(num_pages * ps, HD), dt)
    v_pool = jnp.asarray(rng.randn(num_pages * ps, HD), dt)
    lens = [C - 6, 100, 17, 0] if rehearse else (
        [8900, 8317, 7700, 1234, 517, 100, 15, 0]
        + list(rng.randint(7700, 8900, B - 8)))
    seq_lens = np.asarray(lens[:B], np.int32)
    # a shared prefix (the same physical pages in every long lane's table),
    # then every lane's own shuffled pages; page 0 is the trash page
    free = list(range(1, num_pages))
    rng.shuffle(free)
    shared = [free.pop() for _ in range(0 if rehearse else 7424 // ps)]
    table = np.zeros((B, P), np.int32)
    for b in range(B):
        need = -(-(int(seq_lens[b]) + 1) // ps)
        own = shared[:need] if need > len(shared) else []
        table[b, :need] = own + [free.pop() for _ in range(need - len(own))]

    def window(pool, rows):
        idx = (rows[:, :, None] * ps + np.arange(ps)[None, None, :])
        return pool.astype(jnp.float32)[jnp.asarray(
            idx.reshape(len(rows), C))].reshape(len(rows), C, Hkv, D)

    def reference(q, rows, q_pos, n_valid):
        kv_pos = np.broadcast_to(np.arange(C)[None, :], (len(rows), C))
        with jax.default_matmul_precision("highest"):
            return np.asarray(causal_attention(
                q.astype(jnp.float32), window(k_pool, rows),
                window(v_pool, rows), q_positions=jnp.asarray(q_pos),
                kv_positions=jnp.asarray(kv_pos),
                kv_valid=jnp.asarray(kv_pos < n_valid[:, None])))

    def report(name, got, want):
        got = np.asarray(got, np.float32)
        print(json.dumps({
            "kernel": name, "heads": Hq, "kv_heads": Hkv, "head_dim": D,
            "finite": bool(np.all(np.isfinite(got))),
            "max_abs_diff_vs_xla": float(np.max(np.abs(got - want))),
            "agree_at_2e-2": bool(np.allclose(got, want, atol=2e-2,
                                              rtol=2e-2)),
            "interpret": interpret}), flush=True)

    q = jnp.asarray(rng.randn(B, Hq, D), dt)
    out = paged_decode_attention(q, k_pool, v_pool, jnp.asarray(table),
                                 jnp.asarray(seq_lens), page_size=ps,
                                 interpret=interpret)
    # (8 lanes are enough of a float32 [lanes, 16k, 4, 128] window)
    n = min(B, 8)
    report("paged_decode_attention", out[:n],
           reference(q[:n, None], table[:n], seq_lens[:n, None],
                     seq_lens[:n] + 1)[:, 0])
    row = table[:1]
    for bucket in buckets:
        start = min(ps * 3 + 5 if rehearse else 7424, C - bucket)
        chunk_len = bucket - 3
        qp = jnp.asarray(rng.randn(bucket, Hq, D), dt)
        out = np.asarray(paged_prefill_attention(
            qp, k_pool, v_pool, jnp.asarray(row[0]), jnp.int32(start),
            jnp.int32(chunk_len), page_size=ps, interpret=interpret),
            np.float32)
        want = reference(qp[None], row, (start + np.arange(bucket))[None, :],
                         np.asarray([start + chunk_len]))[0]
        report(f"paged_prefill_attention[{bucket}]", out[:chunk_len],
               want[:chunk_len])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--state-block-mb", type=float,
                    default=sk.STATE_BLOCK_BYTES / 2**20)
    ap.add_argument("--attention", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.attention:
        attention_leg(args.rehearse)
    sk.STATE_BLOCK_BYTES = int(args.state_block_mb * 2**20)
    H, P, G, N, slots, lanes = ((4, 16, 2, 32, 9, 4) if args.rehearse
                                else (32, 128, 2, 256, 129, 32))
    shapes = [(lanes, 1), (1, 512), (1, 128), (4, 128)]
    if args.rehearse:
        shapes = [(lanes, 1), (1, 128), (2, 32)]
    leaf0 = 0.1 * jax.random.normal(jax.random.PRNGKey(7),
                                    (2, slots, H * P, N), jnp.float32)
    run = jax.jit(
        lambda leaf, plan, x, Bm, Cm, g, kernel: sk.ssd(
            leaf, 1, plan, x, Bm, Cm, g, kernel=kernel,
            read_state=_read_state, write_state=_write_state),
        static_argnums=(6,), donate_argnums=(0,))
    for B, S in shapes:
        x, Bm, Cm, g = inputs(B, S, H, P, G, N, seed=S)
        lens = jnp.full((B,), S, jnp.int32).at[-1].set(max(S - 3, 1))
        plan = StatePlan(lens=lens) if S == 1 else StatePlan(
            lens=lens, src=jnp.arange(B) + 1, dst=jnp.arange(B) + 1,
            snap=jnp.arange(B) + B + 1, fresh=jnp.zeros((B,), bool))
        o_x, l_x = run(jnp.copy(leaf0), plan, x, Bm, Cm, g, False)
        o_k, l_k = run(jnp.copy(leaf0), plan, x, Bm, Cm, g, True)
        real = np.arange(S)[None, :] < np.asarray(lens)[:, None]
        out = {"lanes": B, "rows": S, "heads": H, "head_dim": P, "groups": G,
               "d_state": N, "heads_a_step": sk.heads_a_step(H, G, P, N),
               "out_max_abs_diff": float(np.abs(
                   np.asarray(o_x) - np.asarray(o_k))[real].max()),
               "state_max_abs_diff": float(jnp.abs(l_x - l_k).max()),
               "out_max_abs": float(np.abs(np.asarray(o_x))[real].max())}
        del o_x, l_x, o_k, l_k
        for name, kernel in (("kernel", True), ("xla_scan", False)):
            if S > 128 and not kernel and not args.rehearse:
                continue
            fn = lambda leaf, *a, _k=kernel: run(leaf, *a, _k)  # noqa: E731
            out[name + "_ms"] = 1e3 * timed(
                fn, jnp.copy(leaf0), plan, x, Bm, Cm, g,
                n=3 if args.rehearse else 20)
        if S == 1 and not args.rehearse:
            moved = 2 * 4 * B * H * P * N
            out["state_gb_s"] = moved / out["kernel_ms"] / 1e6
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
