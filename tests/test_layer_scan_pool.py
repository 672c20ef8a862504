"""The paged KV pool stays where it is (ISSUE 25): the layer scan carries the
stacked pool and a layer addresses its part by index (models/llama.py).

Two guards, both CPU and deterministic:

* structure: in the engine's own step programs the layer scan has every
  pool-shaped value among its carries and none among its scanned inputs or
  stacked outputs, and nothing slices one layer of the pool out or writes one
  back (the parent's data flow, a third of a decode step's device time);
* addressing: with the layer's offset folded into flat indices, a write or a
  page id must never land in the neighbouring layer.  One decode step and one
  prefill chunk through `forward` against a plain per-layer loop that slices
  each layer's pool out and writes it back.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.models.cache import KVCache, PagedView
from kafka_tpu.models.ffn import _mlp_block
from kafka_tpu.models.llama import _logits_head, forward
from kafka_tpu.models.mixers.gqa import _attention_block
from kafka_tpu.models.quant import QTensor
from kafka_tpu.ops.norms import rms_norm
from kafka_tpu.ops.rope import rope_cos_sin, rope_frequencies
from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

from test_device_scopes import _build, _traffic

BACKENDS = ["xla", "pallas"]  # pallas runs interpreted off the chip
POOLS = ["dense", "int8"]
PS = 16


def _cfg(name, num_layers, backend="xla"):
    return ModelConfig(name=name, vocab_size=128, hidden_size=64,
                       intermediate_size=128, num_layers=num_layers,
                       num_heads=4, num_kv_heads=2, head_dim=16,
                       dtype="float32", attention_backend=backend)


# ---------------------------------------------------------------------------
# structure
# ---------------------------------------------------------------------------

LAYERS, PAGES = 3, 64  # 3 layers: no other scan of these programs has 3 steps


def _subjaxprs(params):
    for v in params.values():
        for j in (v if isinstance(v, (list, tuple)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _scans(jaxpr):
    """Every scan equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from _scans(sub)


def _pool_shapes(num_layers, slots, widths):
    """Shapes a whole pool leaf can take, stacked or flat.  widths: the
    rows' Hkv*D and, for an int8 pool, the per-slot scales' 1."""
    return {s for w in widths
            for s in ((num_layers, slots, w), (num_layers * slots, w))}


def _layer_slice_ops(lowered_text, slots, widths):
    """Lines of a lowered (StableHLO) program that slice one layer of the
    pool out of the stacked array or write one back."""
    layer = [f"tensor<{lead}{slots}x{w}x" for w in widths
             for lead in ("", "1x")]
    return [ln.strip()[:160] for ln in lowered_text.splitlines()
            if re.search(r"dynamic_(update_)?slice", ln)
            and any(t in ln for t in layer)]


@pytest.fixture(scope="module")
def programs():
    cache = {}

    def get(backend, pool):
        if (backend, pool) not in cache:
            cache[backend, pool] = _build(
                _cfg(f"poolscan-{backend}-{pool}", LAYERS), _traffic,
                page_size=PS, num_pages=PAGES, prefill_buckets=(64,),
                attention_backend=backend,
                kv_quantize="int8" if pool == "int8" else "")
        return cache[backend, pool]

    return get


@pytest.mark.parametrize("label", ["decode", "multi_decode[4]", "prefill[64]"])
@pytest.mark.parametrize("pool", POOLS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_layer_scan_carries_the_pool(programs, backend, pool, label):
    rec = programs(backend, pool)[label]
    assert rec["args"] is not None, f"{label} was built but never ran"
    cfg = _cfg("shape-only", LAYERS)
    slots, hd = PAGES * PS, cfg.num_kv_heads * cfg.head_dim
    widths = (hd, 1) if pool == "int8" else (hd,)
    shapes = _pool_shapes(LAYERS, slots, widths)
    leaves = 2 * len(widths)  # k and v; rows and (int8) scales

    def pool_shaped(vs):
        return [v.aval.shape for v in vs if v.aval.shape in shapes]

    wq = (LAYERS, cfg.hidden_size, cfg.num_heads, cfg.head_dim)
    layer_scans = 0
    for eqn in _scans(jax.make_jaxpr(rec["jit"])(*rec["args"]).jaxpr):
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        xs, ys = eqn.invars[nc + nk:], eqn.outvars[nk:]
        assert not pool_shaped(xs), (label, "pool scanned in")
        assert not pool_shaped(ys), (label, "pool stacked out")
        if any(v.aval.shape == wq for v in xs):  # the scan over layers
            layer_scans += 1
            assert eqn.params["length"] == LAYERS
            assert len(pool_shaped(eqn.invars[nc:nc + nk])) == leaves
            assert len(pool_shaped(eqn.outvars[:nk])) == leaves
            assert not pool_shaped(eqn.invars[:nc])
    assert layer_scans == 1, layer_scans

    text = rec["jit"].lower(*rec["args"]).as_text()
    assert _layer_slice_ops(text, slots, widths) == []


def test_layer_slice_detector_sees_a_scanned_pool():
    """The detector fires on the parent's data flow: the pool as the scan's
    xs and ys."""
    pool = jnp.zeros((LAYERS, PAGES * PS, 32), jnp.float32)

    def scanned(p):
        return jax.lax.scan(lambda c, kc: (c, kc.at[0].set(1.0)), 0, p)[1]

    eqn, = _scans(jax.make_jaxpr(scanned)(pool).jaxpr)
    assert [v.aval.shape for v in eqn.invars[1:]] == [pool.shape]
    text = jax.jit(scanned).lower(pool).as_text()
    assert len(_layer_slice_ops(text, PAGES * PS, (32,))) >= 2


# ---------------------------------------------------------------------------
# addressing
# ---------------------------------------------------------------------------

NUM_PAGES, P = 12, 4  # pages a layer, pages a sequence (window 64)
C = P * PS


def _sentinel_pools(cfg, pool, key):
    """Pools in which every row of every layer differs (random rows plus
    10 x the layer's index), so a row read from or written to the
    neighbouring layer shows in the logits and in the pool."""
    k, v = make_kv_pool_arrays(cfg, NUM_PAGES, PS, jnp.float32,
                               quantize="int8" if pool == "int8" else "")
    out = []
    for i, leafs in enumerate((k, v)):
        kk = jax.random.fold_in(key, i)
        lay = jnp.arange(cfg.num_layers)[:, None, None]
        if pool == "int8":
            q = jax.random.randint(kk, leafs.q.shape, -100, 100, jnp.int8)
            s = jax.random.uniform(kk, leafs.s.shape, jnp.float32, 0.01, 0.02)
            out.append(QTensor(q=q, s=s + 0.1 * lay))
        else:
            out.append(jax.random.normal(kk, leafs.shape, jnp.float32)
                       + 10.0 * lay)
    return out


def _decode_plan():
    """Three lanes, the middle one inactive; the engine's index plan
    (runtime/engine.py _decode_step_body)."""
    page_table = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0], [8, 7, 6, 5]],
                             jnp.int32)
    seq_lens = jnp.asarray([5, 3, 37], jnp.int32)
    active = jnp.asarray([True, False, True])
    B = 3
    write_page = page_table[jnp.arange(B), seq_lens // PS]
    write_idx = (write_page * PS + seq_lens % PS)[:, None]
    write_idx = jnp.where(active[:, None], write_idx,
                          (seq_lens % PS)[:, None])
    read_idx = (page_table[:, :, None] * PS
                + jnp.arange(PS)[None, None, :]).reshape(B, C)
    kv_positions = jnp.broadcast_to(jnp.arange(C)[None, :], (B, C))
    kv_valid = (kv_positions <= seq_lens[:, None]) & active[:, None]
    paged = PagedView(write_idx, read_idx, kv_positions, kv_valid,
                      page_table=page_table, seq_lens=seq_lens, page_size=PS)
    tokens = jnp.asarray([[7], [9], [11]], jnp.int32)
    real = {int(write_idx[0, 0]), int(write_idx[2, 0])}
    trash = {int(write_idx[1, 0])}
    return tokens, seq_lens[:, None], paged, real, trash, (np.array([0, 2]),)


def _prefill_plan():
    """One chunk of 32 resuming at position 16 with 20 real tokens
    (_get_prefill_fn): the 12 padding rows go to the trash page."""
    page_row = jnp.asarray([9, 2, 11, 5], jnp.int32)
    start, chunk_len, S = jnp.int32(16), jnp.int32(20), 32
    local = jnp.arange(S)
    positions = (start + local)[None, :]
    in_chunk = local < chunk_len
    write_page = page_row[(start + local) // PS]
    write_idx = jnp.where(in_chunk, write_page * PS + (start + local) % PS,
                          local % PS)[None, :]
    read_idx = (page_row[:, None] * PS + jnp.arange(PS)[None, :]).reshape(1, C)
    kv_positions = jnp.arange(C)[None, :]
    kv_valid = kv_positions < (start + chunk_len)
    paged = PagedView(write_idx, read_idx, kv_positions, kv_valid,
                      page_table=page_row[None, :], page_size=PS,
                      start=start, chunk_len=chunk_len)
    tokens = (jnp.arange(S, dtype=jnp.int32) * 3 % 128)[None, :]
    idx = np.asarray(write_idx[0])
    real = {int(i) for i in idx[:20]}
    trash = {int(i) for i in idx[20:]}
    return tokens, positions, paged, real, trash, (0, np.arange(20))


def _per_layer_loop(params, cfg, tokens, positions, k_pool, v_pool, paged):
    """The parent's formulation: for each layer, slice the layer's whole pool
    out of the stacked array, write the new rows into the copy, attend over
    it, and write the copy back."""
    x = params["embed"][tokens].astype(cfg.activation_dtype)
    cos, sin = rope_cos_sin(positions, rope_frequencies(cfg))
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        kc, vc = jax.tree.map(lambda a: a[i:i + 1], (k_pool, v_pool))
        attn_out, kc, vc = _attention_block(
            rms_norm(x, lp["ln_attn"], cfg.rms_norm_eps), lp, cfg, cos, sin,
            positions, kc, vc, None, None, paged, None, 0)
        x = x + attn_out
        x = x + _mlp_block(rms_norm(x, lp["ln_mlp"], cfg.rms_norm_eps), lp)
        k_pool, v_pool = jax.tree.map(
            lambda a, b: a.at[i].set(b[0]), (k_pool, v_pool), (kc, vc))
    return _logits_head(x, params, cfg), k_pool, v_pool


def _rows_changed(before, after):
    """{layer: set of slots whose row differs bit for bit}, over every leaf
    of a pool."""
    diff = np.zeros(np.asarray(jax.tree.leaves(before)[0]).shape[:2], bool)
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        diff |= (np.asarray(b) != np.asarray(a)).any(axis=-1)
    return {i: set(np.flatnonzero(diff[i])) for i in range(diff.shape[0])}


@pytest.mark.parametrize("pool", POOLS)
@pytest.mark.parametrize("backend", BACKENDS + ["slots"])
@pytest.mark.parametrize("num_layers", [1, 3])
def test_writes_and_reads_stay_in_their_layer(num_layers, backend, pool):
    """`slots` is the view parallel/pipeline.py builds: no page table, so
    the window is gathered slot by slot through `read_idx`."""
    cfg = _cfg(f"pooladdr-{num_layers}", num_layers,
               "xla" if backend == "slots" else backend)
    params = init_params(cfg, jax.random.PRNGKey(3))
    tol = dict(rtol=2e-5, atol=2e-5)
    for step, plan in (("decode", _decode_plan), ("prefill", _prefill_plan)):
        tokens, positions, paged, real, trash, rows = plan()
        if backend == "slots":
            paged = PagedView(*paged[:4])
        k0, v0 = _sentinel_pools(cfg, pool, jax.random.PRNGKey(11))
        logits, cache = jax.jit(
            lambda p, k, v: forward(p, cfg, tokens, positions,
                                    kv_cache=KVCache(k, v), paged=paged)
        )(params, k0, v0)
        want_logits, want_k, want_v = jax.jit(
            lambda p, k, v: _per_layer_loop(p, cfg, tokens, positions, k, v,
                                            paged)
        )(params, k0, v0)
        for before, after in ((k0, cache.k), (v0, cache.v)):
            changed = _rows_changed(before, after)
            for i in range(num_layers):
                # every real row was written, in its own layer; whatever
                # else changed is on that layer's trash page (inactive
                # lanes, padding rows); every other row is bit-identical
                assert real <= changed[i], (step, i)
                assert changed[i] - real <= trash, (step, i, changed[i])
        # logits of the real rows (active lanes, in-chunk positions)
        np.testing.assert_allclose(
            np.asarray(logits)[rows], np.asarray(want_logits)[rows], **tol)
        for got, want in zip(jax.tree.leaves((cache.k, cache.v)),
                             jax.tree.leaves((want_k, want_v))):
            if got.dtype == jnp.int8:
                assert np.abs(np.asarray(got, np.int32)
                              - np.asarray(want, np.int32)).max() <= 1
            else:
                np.testing.assert_allclose(np.asarray(got),
                                           np.asarray(want), **tol)
