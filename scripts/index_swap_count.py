#!/usr/bin/env python
"""How many of a query's top-2048 keys change when the indexer's arithmetic
is rounded?  Counts for `benchmarks/references/dots3.py` (THE SELECTION).

    JAX_PLATFORMS=cpu python scripts/index_swap_count.py

Random weights of dots3-note-prev's published index shapes (hidden 5120, query
rank 1024, 64 heads x 128, rotary on 64, top-2048) at layer 0, where the
input is exact: 3,119 byte-tokens, the last 48 as queries (the logit check's
positions).  The float32 scores give each query's reference set; each case
below gives another, and a line says how many of its 2,048 keys are NOT in
the reference set (`swaps`) and how far past the reference's 2,048th place
its lowest-ranked key sits (`reach`): min / median / max over the 48 queries.
Sets, not times: this runs on the CPU and says nothing about the device.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np

H, RQ, HI, DI, DR, TOPK, S, V, QUERIES = 5120, 1024, 64, 128, 64, 2048, \
    3119, 19008, 48


def main() -> None:
    jax.config.update("jax_default_matmul_precision", "highest")
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    bf = jnp.bfloat16

    def n01(k, shape, fan):
        return (jax.random.normal(k, shape) / np.sqrt(fan)).astype(bf)

    def f32(a):
        return jnp.asarray(a).astype(jnp.float32)

    def to_bf16(x):
        return x.astype(bf).astype(jnp.float32)

    embed = n01(ks[0], (V, H), H)
    wqa, wiq = n01(ks[1], (H, RQ), H), n01(ks[2], (RQ, HI * DI), RQ)
    wik, wiw = n01(ks[3], (H, DI), H), n01(ks[4], (H, HI), H)
    ln_w = 1 + 0.2 * jax.random.normal(ks[5], (DI,))
    ln_b = 0.1 * jax.random.normal(ks[6], (DI,))
    ids = np.random.RandomState(0).randint(0, V, size=S)

    def rms(x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)

    def layer_norm(x):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * ln_w + ln_b

    def rope(x, theta=8e7):
        d = x.shape[-1]
        inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
        ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
               * jnp.asarray(inv, jnp.float32)[None])
        ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (d // 2,))
        c, s = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    def scores(operands_bf16=False, scores_bf16=False, noise=0.0):
        r = to_bf16 if operands_bf16 else (lambda x: x)
        x = f32(embed[ids])
        if noise:
            x = x * (1 + noise * jax.random.normal(ks[7], x.shape))
        h = r(rms(x))
        c_q = r(r(rms(h @ f32(wqa))) * np.sqrt(H / RQ))
        q = r(c_q @ f32(wiq)).reshape(S, HI, DI)
        k = r(layer_norm(h @ f32(wik)))
        q = r(jnp.concatenate([rope(q[..., :DR]), q[..., DR:]], -1))
        k = r(jnp.concatenate([rope(k[:, :DR]), k[:, DR:]], -1))
        w = (h @ f32(wiw))[-QUERIES:] * (HI ** -0.5 * DI ** -0.5)
        dots = jnp.einsum("snd,td->snt", q[-QUERIES:], k)
        if not scores_bf16:
            return np.asarray(jnp.einsum("sn,snt->st", w, jax.nn.relu(dots)))
        total = jnp.zeros((QUERIES, S))
        for j in range(HI):  # as references/dots3.py's `index_scores_bf16`
            total = to_bf16(
                total + w[:, j, None] * jax.nn.relu(to_bf16(dots[:, j])))
        return np.asarray(total)

    def orders(sc):
        t = np.arange(S - QUERIES, S)[:, None]
        row = np.where(np.arange(S)[None, :] <= t, sc, -np.inf)
        return np.argsort(-row, axis=-1, kind="stable")

    ref = orders(scores())
    for name, case in (
            ("operands_bf16_as_served", dict(operands_bf16=True)),
            ("scores_bf16_the_variant", dict(scores_bf16=True)),
            ("hidden_state_2pct_off", dict(noise=0.02)),
            ("hidden_state_5pct_off", dict(noise=0.05))):
        swaps, reach = [], []
        for a, b in zip(ref, orders(scores(**case))):
            rank = np.empty(S, int)
            rank[a] = np.arange(S)
            chosen = rank[b[:TOPK]]
            swaps.append(int((chosen >= TOPK).sum()))
            reach.append(int(chosen.max() - TOPK + 1))

        def three(v):
            return [min(v), int(np.median(v)), max(v)]

        print(json.dumps({"case": name, "swaps": three(swaps),
                          "reach": three(reach)}), flush=True)


if __name__ == "__main__":
    main()
