"""Int8 KV-cache quantization (VERDICT r4 next #4).

The paged pools become QTensor pytrees (int8 rows + per-slot f32 scales,
runtime/kv_cache.py) and the attention layer quantizes at write /
dequantizes at gather (models/cache.py _kv_write/_kv_read).  Covered:
roundtrip error bounds, engine serving vs the dense-KV engine, pool
sharing (prefix cache) with quantized pages, TP-mesh consistency, and the
config wiring.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.models.cache import _kv_read, _kv_write
from kafka_tpu.models.quant import QTensor
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="kvq-test", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(5))
    return cfg, params


def make_engine(cfg, params, kv_quantize="", mesh=None,
                attention_backend="auto"):
    return InferenceEngine(
        cfg, params,
        EngineConfig(max_batch=2, page_size=8, num_pages=64,
                     max_pages_per_seq=8, prefill_buckets=(8, 16, 32),
                     kv_quantize=kv_quantize,
                     attention_backend=attention_backend),
        kv_dtype=jnp.float32, mesh=mesh,
    )


class TestPoolPrimitives:
    def test_make_quantized_pool_shapes(self):
        cfg = ModelConfig(num_layers=3, num_kv_heads=2, head_dim=16)
        k, v = make_kv_pool_arrays(cfg, num_pages=10, page_size=8,
                                   quantize="int8")
        assert isinstance(k, QTensor) and k.q.dtype == jnp.int8
        assert k.q.shape == (3, 80, 32)
        assert k.s.shape == (3, 80, 1) and k.s.dtype == jnp.float32
        with pytest.raises(ValueError):
            make_kv_pool_arrays(cfg, 10, 8, quantize="fp4")

    def test_write_read_roundtrip_bound(self):
        pool = QTensor(q=jnp.zeros((40, 128), jnp.int8),
                       s=jnp.zeros((40, 1), jnp.float32))
        rows = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 128),
                                 jnp.float32) * 5.0
        idx = jnp.array([[1, 2, 3], [10, 11, 12]])
        pool = _kv_write(pool, idx, rows)
        back = _kv_read(pool, idx, jnp.float32)
        # symmetric per-row int8: |err| <= row_max/254 + eps
        bound = np.abs(np.asarray(rows)).max(-1, keepdims=True) / 254 + 1e-5
        assert (np.abs(np.asarray(back) - np.asarray(rows)) <= bound).all()

    def test_dense_path_unchanged(self):
        pool = jnp.zeros((40, 32), jnp.float32)
        rows = jnp.ones((1, 2, 32))
        pool = _kv_write(pool, jnp.array([[4, 5]]), rows)
        assert float(pool[4].sum()) == 32.0
        assert _kv_read(pool, jnp.array([[4]]), jnp.float32).shape == (1, 1, 32)


class TestQuantizedKVServing:
    def test_greedy_match_vs_dense_kv(self, model):
        """f32 weights + int8 KV vs f32 weights + f32 KV: the KV rounding
        is the only difference; greedy streams should mostly agree (random
        weights leave near-ties, so exact match is not required)."""
        cfg, params = model
        dense = make_engine(cfg, params)
        q_eng = make_engine(cfg, params, kv_quantize="int8")
        assert q_eng.cfg.attention_backend == "xla"
        match = total = 0
        for i in range(4):
            prompt = [3 + i, 17, 92, 5, 44 + i]
            a = dense.generate(prompt, max_new_tokens=16).output_ids
            b = q_eng.generate(prompt, max_new_tokens=16).output_ids
            total += len(a)
            match += sum(1 for x, y in zip(a, b) if x == y)
        assert match / total > 0.7, f"match rate {match}/{total}"

    def test_serves_batch_with_preemption_shapes(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, kv_quantize="int8")
        for i in range(3):
            eng.submit(GenRequest(request_id=f"kq{i}",
                                  prompt_ids=[5 + i, 2, 9],
                                  max_new_tokens=8))
        done = eng.run_to_completion()
        assert len(done) == 3
        assert all(len(r.output_ids) == 8 for r in done.values())

    def test_prefix_cache_shares_quantized_pages(self, model):
        """Shared prefix pages carry their scales with them (scales are
        per-slot, slots are shared): the second request reuses the pages
        and still decodes sanely."""
        cfg, params = model
        eng = make_engine(cfg, params, kv_quantize="int8")
        p1 = [(i * 7) % 120 + 3 for i in range(20)]
        r1 = eng.generate(p1, max_new_tokens=6, prefix_key="t1")
        hits0 = eng.prefix_cache.hits
        # second turn extends the thread (the cache-hit shape): shared
        # full pages are reused with their quantized rows + scales
        p2 = p1 + r1.output_ids + [9, 4]
        r2 = eng.generate(p2, max_new_tokens=6, prefix_key="t1")
        assert eng.prefix_cache.hits > hits0
        # ground truth: same request on a fresh quantized engine, no cache
        ref = make_engine(cfg, params, kv_quantize="int8").generate(
            p2, max_new_tokens=6)
        assert r2.output_ids == ref.output_ids

    def test_forced_pallas_int8_matches_xla_int8(self, model):
        """The int8 decode kernel (paged_decode_attention_int8: int8 page
        DMAs + fused per-slot dequant) through the engine matches the XLA
        dequantizing-gather path token-for-token — both read the SAME
        quantized pool, so the kernels must agree."""
        cfg, params = model
        outs = {}
        for backend in ("xla", "pallas"):
            eng = make_engine(cfg, params, kv_quantize="int8",
                              attention_backend=backend)
            assert eng.cfg.attention_backend == backend
            outs[backend] = eng.generate(
                [3, 17, 92, 5, 44, 8, 29], max_new_tokens=12
            ).output_ids
        assert outs["pallas"] == outs["xla"]


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
class TestQuantizedKVTP:
    def test_tp_matches_single_device(self, model):
        from kafka_tpu.parallel import MeshConfig, make_mesh

        cfg, params = model
        base = make_engine(cfg, params, kv_quantize="int8")
        eng = make_engine(cfg, params, kv_quantize="int8",
                          mesh=make_mesh(MeshConfig(tp=2)))
        prompt = [5, 99, 23, 4, 17]
        want = base.generate(prompt, max_new_tokens=10).output_ids
        got = eng.generate(prompt, max_new_tokens=10).output_ids
        assert got == want

    def test_tp_pallas_int8_matches_xla(self, model):
        """The sharded int8 kernel (shard_map per-shard DMAs, scales
        replicated) through a tp mesh engine matches the xla int8 mesh
        engine token-for-token.  Child-isolated (tests/_isolation.py)."""
        from _isolation import isolated

        if not isolated(
            "tests/test_kv_quant.py::TestQuantizedKVTP::"
            "test_tp_pallas_int8_matches_xla"
        ):
            return
        from kafka_tpu.parallel import MeshConfig, make_mesh

        cfg, params = model
        outs = {}
        for backend in ("xla", "pallas"):
            eng = make_engine(cfg, params, kv_quantize="int8",
                              attention_backend=backend,
                              mesh=make_mesh(MeshConfig(tp=2)))
            outs[backend] = eng.generate(
                [5, 99, 23, 4, 17], max_new_tokens=10
            ).output_ids
        assert outs["pallas"] == outs["xla"]


class TestConfigWiring:
    def test_env(self, monkeypatch):
        from kafka_tpu.server import ServingConfig

        monkeypatch.setenv("KAFKA_TPU_KV_QUANTIZE", "int8")
        assert ServingConfig.from_env().kv_quantize == "int8"

    def test_planner_models_int8_kv(self):
        from kafka_tpu.models.config import get_config
        from kafka_tpu.runtime.planner import kv_bytes_per_token

        cfg = get_config("llama-3-8b")
        assert kv_bytes_per_token(cfg, kv_dtype="int8") * 2 == \
            kv_bytes_per_token(cfg, kv_dtype="bfloat16")
