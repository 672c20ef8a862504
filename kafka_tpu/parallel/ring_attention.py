"""Context parallelism for long sequences: ring attention and Ulysses.

Two standard strategies for attention over a sequence sharded across an
"sp" mesh axis (SURVEY §2.2; required for 32k-context prefill where one
chip's HBM can't hold the KV):

* **Ring attention** (`ring_attention`): every device keeps its local Q
  shard and processes the K/V shards of all devices as they rotate around
  the ring via `lax.ppermute` (ICI neighbor exchange — bandwidth-optimal,
  compute/comm overlapped by XLA). Softmax is accumulated online
  (flash-style running max / sum), so no device ever materializes the full
  [Sq, Skv] score matrix.

* **Ulysses** (`ulysses_attention`): `all_to_all` re-shards activations
  from sequence-sharded to head-sharded, runs ordinary full-sequence
  attention locally on each device's head subset, and re-shards back.
  Cheaper compute bookkeeping than the ring, but needs heads % sp == 0 and
  all-to-all bandwidth.

Both are written as plain per-shard functions meant to run inside
`shard_map` over the "sp" axis; `*_sharded` wrappers apply the shard_map
over a mesh. Numerics are validated against ops.causal_attention on a
virtual 8-device mesh (tests/test_parallel.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import pcast
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import NEG_INF, repeat_kv


def _block_scores(q, k, q_pos, kv_pos, scale, mask_value=NEG_INF, kv_valid=None):
    """Masked attention scores for one block pair. q:[B,Sq,H,D] k:[B,Sk,H,D]."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )
    mask = q_pos[:, None, :, None] >= kv_pos[:, None, None, :]
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, None, :]
    return jnp.where(mask, s, mask_value)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    axis_name: str = "sp",
    k_ctx: Optional[jnp.ndarray] = None,
    v_ctx: Optional[jnp.ndarray] = None,
    ctx_positions: Optional[jnp.ndarray] = None,
    ctx_valid: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Causal attention with K/V ring-rotated across `axis_name`.

    Call inside shard_map. Shapes per shard: q/k/v [B, S_local, H(kv), D],
    positions [B, S_local] (absolute). GQA handled via repeat. Returns
    attention output [B, S_local, H, D] in q.dtype.

    The optional context block (k_ctx/v_ctx [B, C, Hkv, D], replicated on
    every rank, masked by ctx_valid) is attended before the ring starts —
    this is how the engine's chunked prefill composes: the chunk's own KV
    rides the ring sequence-sharded, while the paged window written by
    earlier chunks/turns is read locally (SURVEY §2.2 CP; BASELINE config
    5's 32k prefill tier).
    """
    # GQA expansion happens per-block inside the loop: the ring rotates the
    # compact Hkv tensors and each device re-expands locally, so ppermute
    # (ICI) traffic is 1/n_rep of rotating the expanded heads.
    n_rep = q.shape[2] // k.shape[2]
    scale = q.shape[-1] ** -0.5
    n = lax.psum(1, axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    B, Sq, H, D = q.shape
    acc = jnp.zeros((B, H, Sq, D), jnp.float32)
    m = jnp.full((B, H, Sq, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, H, Sq, 1), jnp.float32)

    if k_ctx is not None:
        # accumulators become ring-varying through q, no pcast needed
        s = _block_scores(
            q, repeat_kv(k_ctx, q.shape[2] // k_ctx.shape[2]),
            q_positions, ctx_positions, scale, -jnp.inf, kv_valid=ctx_valid,
        )
        m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe_m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum(
            "bhqk,bkhd->bhqd", p,
            repeat_kv(v_ctx, q.shape[2] // v_ctx.shape[2]).astype(jnp.float32),
        )
    else:
        # mark the accumulators as varying over the ring axis so the scan
        # carry type matches its output (JAX >= 0.9 shard_map vma tracking)
        acc, m, l = (
            pcast(x, (axis_name,), to="varying") for x in (acc, m, l)
        )

    def body(carry, _):
        k_blk, v_blk, kv_pos, acc, m, l = carry
        # -inf masking + where-guarded exponentials: a block whose every
        # entry is masked for some query row (common in the causal ring —
        # early queries vs late kv blocks) must contribute exactly zero,
        # and the running max must stay -inf until a real score arrives.
        k_rep = repeat_kv(k_blk, n_rep)
        v_rep = repeat_kv(v_blk, n_rep)
        s = _block_scores(q, k_rep, q_positions, kv_pos, scale, -jnp.inf)
        blk_max = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, blk_max)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe_m), 0.0)
        correction = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l = l * correction + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p, v_rep.astype(jnp.float32))
        acc = acc * correction + pv
        m = m_new
        # rotate kv block (and its positions) to the next ring neighbor
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        kv_pos = lax.ppermute(kv_pos, axis_name, perm)
        return (k_blk, v_blk, kv_pos, acc, m, l), None

    (k, v, kv_positions, acc, m, l), _ = lax.scan(
        body, (k, v, kv_positions, acc, m, l), None, length=n
    )
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B,Sq,H,D]


def ring_attention_sharded(
    mesh: Mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    axis_name: str = "sp",
) -> jnp.ndarray:
    """shard_map wrapper: global [B, S, H, D] inputs sharded on S over sp."""
    spec_a = P(None, axis_name, None, None)
    spec_p = P(None, axis_name)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec_a, spec_a, spec_a, spec_p, spec_p),
        out_specs=spec_a,
    )
    return fn(q, k, v, q_positions, kv_positions)


def _prefill_sharded(
    per_shard,
    mesh: Mesh,
    q: jnp.ndarray,
    k_chunk: jnp.ndarray,
    v_chunk: jnp.ndarray,
    q_positions: jnp.ndarray,
    k_ctx: jnp.ndarray,
    v_ctx: jnp.ndarray,
    ctx_positions: jnp.ndarray,
    ctx_valid: jnp.ndarray,
    axis_name: str,
) -> jnp.ndarray:
    """Shared CP layout contract for both prefill strategies: the chunk
    tensors are sequence-sharded over sp, the paged context is replicated
    over sp, and heads additionally shard over the mesh's tp axis when it
    divides BOTH head counts (the same rule as sharding.py's
    kv_pool_spec) — so on a tp x sp mesh each device holds 1/(tp*sp) of
    the chunk and 1/tp of the context window.

    Grouped-GQA meshes (parallel/mesh.py: tensor degree factorized into
    tp*tq with tp | Hkv) shard q heads over BOTH ("tp","tq") and kv heads
    over "tp" alone — each shard then sees Hq/(tp*tq) queries against its
    Hkv/tp kv heads, and the per-shard GQA repeat factor stays an integer
    because contiguous q-head blocks map onto their own kv head (the same
    head-order invariant sharding.py's decode path relies on)."""
    tp = mesh.shape.get("tp", 1)
    tq = mesh.shape.get("tq", 1)
    hq, hkv = q.shape[2], k_chunk.shape[2]
    kv_ax = "tp" if (tp > 1 and hkv % tp == 0 and hq % tp == 0) else None
    # The grouped split is sound only when each shard holds exactly ONE kv
    # head: ring_attention's local q->kv map is m // n_rep, which assumes
    # the shard's q heads all share its first kv head — true for one local
    # kv head, wrong for several (shard (i,j>0) would need an offset).
    # factor_tp_for_kv picks tp == Hkv whenever Hkv | degree, so real
    # grouped meshes hit this branch; odd gcd splits fall back to the
    # plain tp head split (q and kv both over "tp", replicated over tq).
    if kv_ax is not None and tq > 1 and hkv // tp == 1 \
            and hq % (tp * tq) == 0 and (hq // hkv) % tq == 0:
        q_ax = ("tp", "tq")
    else:
        q_ax = kv_ax
    spec_q = P(None, axis_name, q_ax, None)
    spec_kv = P(None, axis_name, kv_ax, None)
    spec_p = P(None, axis_name)
    rep_kv = P(None, None, kv_ax, None)
    rep_p = P(None, None)

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(spec_q, spec_kv, spec_kv, spec_p,
                  rep_kv, rep_kv, rep_p, rep_p),
        out_specs=spec_q,
    )
    return fn(q, k_chunk, v_chunk, q_positions,
              k_ctx, v_ctx, ctx_positions, ctx_valid)


def ring_prefill_sharded(
    mesh: Mesh,
    q: jnp.ndarray,            # [B, S, Hq, D] — the chunk's queries
    k_chunk: jnp.ndarray,      # [B, S, Hkv, D] — the chunk's fresh KV
    v_chunk: jnp.ndarray,
    q_positions: jnp.ndarray,  # [B, S] absolute
    k_ctx: jnp.ndarray,        # [B, C, Hkv, D] — paged window (prior chunks)
    v_ctx: jnp.ndarray,
    ctx_positions: jnp.ndarray,  # [B, C]
    ctx_valid: jnp.ndarray,      # [B, C] — True only for pre-chunk positions
    axis_name: str = "sp",
) -> jnp.ndarray:
    """Chunked-prefill attention with the chunk ring-sharded over sp.

    The chunk's q/kv rotate in a ring; the already-materialized paged
    context is read locally by every sp rank (layout per _prefill_sharded).
    S must divide by the sp size (the engine guarantees this by choosing
    prefill buckets divisible by sp).
    """
    def per_shard(q_, kc_, vc_, qp_, kx_, vx_, cp_, cv_):
        return ring_attention(
            q_, kc_, vc_, qp_, qp_, axis_name=axis_name,
            k_ctx=kx_, v_ctx=vx_, ctx_positions=cp_, ctx_valid=cv_,
        )

    return _prefill_sharded(
        per_shard, mesh, q, k_chunk, v_chunk, q_positions,
        k_ctx, v_ctx, ctx_positions, ctx_valid, axis_name,
    )


def _a2a_seq_to_heads(x, axis_name):  # [B,S_loc,H,D] -> [B,S_glob,H_loc,D]
    return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def _a2a_heads_to_seq(x, axis_name):  # inverse
    return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_prefill(
    q: jnp.ndarray,            # [B, S_loc, Hq, D] — chunk queries (seq shard)
    k_chunk: jnp.ndarray,      # [B, S_loc, Hkv, D]
    v_chunk: jnp.ndarray,
    q_positions: jnp.ndarray,  # [B, S_loc] absolute
    k_ctx: jnp.ndarray,        # [B, C, Hkv, D] — paged window, replicated
    v_ctx: jnp.ndarray,
    ctx_positions: jnp.ndarray,  # [B, C]
    ctx_valid: jnp.ndarray,      # [B, C]
    axis_name: str = "sp",
) -> jnp.ndarray:
    """Per-shard Ulysses chunked-prefill attention (call inside shard_map).

    The alternative CP strategy to `ring_attention`'s context form: instead
    of rotating KV shards, one all_to_all re-shards the chunk from
    sequence-sharded to head-sharded, each rank runs ordinary attention for
    its head subset over [paged context + full chunk], and a second
    all_to_all restores sequence sharding.  The replicated context is
    sliced to the rank's heads (it is already materialized in the pool, so
    it never rides a collective).  Requires H % sp == 0 (heads here are the
    per-tp-shard count when composed with TP).  GQA: kv heads repeat to Hq
    before the swap — simple and always-valid; a kv-head-aware layout could
    cut all_to_all traffic by n_rep.
    """
    n_rep = q.shape[2] // k_chunk.shape[2]
    k_chunk = repeat_kv(k_chunk, n_rep)
    v_chunk = repeat_kv(v_chunk, n_rep)
    sp = lax.psum(1, axis_name)
    rank = lax.axis_index(axis_name)
    h_loc = q.shape[2] // sp

    qh = _a2a_seq_to_heads(q, axis_name)
    kh = _a2a_seq_to_heads(k_chunk, axis_name)
    vh = _a2a_seq_to_heads(v_chunk, axis_name)
    pos_full = lax.all_gather(q_positions, axis_name, axis=1, tiled=True)
    if h_loc % n_rep == 0:
        # GQA fast path: the rank's head block spans whole kv-head groups
        # (repeat_kv repeats consecutively, so repeated head h maps to kv
        # head h // n_rep) — slice the kv heads first and repeat only the
        # local block, materializing 1/n_rep of the context per rank
        kv_loc = h_loc // n_rep
        k_ctx_loc = repeat_kv(lax.dynamic_slice_in_dim(
            k_ctx, rank * kv_loc, kv_loc, axis=2), n_rep)
        v_ctx_loc = repeat_kv(lax.dynamic_slice_in_dim(
            v_ctx, rank * kv_loc, kv_loc, axis=2), n_rep)
    else:
        k_ctx_loc = lax.dynamic_slice_in_dim(
            repeat_kv(k_ctx, n_rep), rank * h_loc, h_loc, axis=2
        )
        v_ctx_loc = lax.dynamic_slice_in_dim(
            repeat_kv(v_ctx, n_rep), rank * h_loc, h_loc, axis=2
        )
    k_all = jnp.concatenate([k_ctx_loc, kh], axis=1)
    v_all = jnp.concatenate([v_ctx_loc, vh], axis=1)
    kv_pos = jnp.concatenate([ctx_positions, pos_full], axis=1)
    kv_valid = jnp.concatenate(
        [ctx_valid, jnp.ones(pos_full.shape, bool)], axis=1
    )
    scale = q.shape[-1] ** -0.5
    s = _block_scores(qh, k_all, pos_full, kv_pos, scale, kv_valid=kv_valid)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p, v_all.astype(jnp.float32)
    ).astype(q.dtype)
    return _a2a_heads_to_seq(out, axis_name)


def ulysses_prefill_sharded(
    mesh: Mesh,
    q: jnp.ndarray,
    k_chunk: jnp.ndarray,
    v_chunk: jnp.ndarray,
    q_positions: jnp.ndarray,
    k_ctx: jnp.ndarray,
    v_ctx: jnp.ndarray,
    ctx_positions: jnp.ndarray,
    ctx_valid: jnp.ndarray,
    axis_name: str = "sp",
) -> jnp.ndarray:
    """shard_map wrapper over ulysses_prefill (layout per _prefill_sharded:
    identical contract to ring_prefill_sharded, so the engine swaps
    strategies without relayout)."""
    return _prefill_sharded(
        functools.partial(ulysses_prefill, axis_name=axis_name),
        mesh, q, k_chunk, v_chunk, q_positions,
        k_ctx, v_ctx, ctx_positions, ctx_valid, axis_name,
    )


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    axis_name: str = "sp",
) -> jnp.ndarray:
    """All-to-all head-scatter attention (per-shard; call inside shard_map).

    Incoming: seq-sharded [B, S_local, H, D] with H full. all_to_all swaps
    to head-sharded [B, S_global, H_local, D], runs ordinary causal
    attention over the full sequence, swaps back. Requires H % sp == 0 and
    equal S shards. GQA: kv heads are repeated up to H before the swap (the
    simple, always-valid layout; kv-head-aware variants can halve traffic).
    """
    n_rep = q.shape[2] // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)

    qh = _a2a_seq_to_heads(q, axis_name)
    kh = _a2a_seq_to_heads(k, axis_name)
    vh = _a2a_seq_to_heads(v, axis_name)
    pos_full = lax.all_gather(q_positions, axis_name, axis=1, tiled=True)
    scale = qh.shape[-1] ** -0.5
    s = _block_scores(qh, kh, pos_full, pos_full, scale)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh.astype(jnp.float32)).astype(q.dtype)
    return _a2a_heads_to_seq(out, axis_name)


def ulysses_attention_sharded(
    mesh: Mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    axis_name: str = "sp",
) -> jnp.ndarray:
    spec_a = P(None, axis_name, None, None)
    spec_p = P(None, axis_name)
    fn = shard_map(
        functools.partial(ulysses_attention, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec_a, spec_a, spec_a, spec_p),
        out_specs=spec_a,
    )
    return fn(q, k, v, q_positions)
