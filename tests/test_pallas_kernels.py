"""Pallas kernel numerics: each kernel vs the XLA reference formulation.

Kernels run in interpret mode here (CPU); on TPU the same code compiles to
Mosaic. The reference is ops.attention.causal_attention driven exactly the
way the engine's decode step drives it (PagedView index plan).
"""

import functools
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.ops.attention import causal_attention
from kafka_tpu.ops.pallas import (
    paged_decode_attention,
    paged_decode_attention_latent,
    paged_decode_attention_window,
)
from kafka_tpu.ops.pallas.paged_attention import (
    RING,
    decode_step_runs,
    pages_one_run,
)
from kafka_tpu.runtime.kv_cache import SequencePages


def make_paged_case(seed, B, P, ps, Hq, Hkv, D, num_pages):
    """Random paged layout: each sequence owns a random page list."""
    rng = np.random.RandomState(seed)
    total = num_pages * ps
    k_pool = rng.randn(total, Hkv, D).astype(np.float32)
    v_pool = rng.randn(total, Hkv, D).astype(np.float32)
    q = rng.randn(B, Hq, D).astype(np.float32)
    # distinct physical pages per sequence (page 0 = trash)
    free = list(range(1, num_pages))
    rng.shuffle(free)
    table = np.zeros((B, P), np.int32)
    seq_lens = rng.randint(1, P * ps - 1, size=B).astype(np.int32)
    for b in range(B):
        need = int(np.ceil((seq_lens[b] + 1) / ps))
        for i in range(need):
            table[b, i] = free.pop()
    return q, k_pool, v_pool, table, seq_lens


def xla_reference(q, k_pool, v_pool, table, seq_lens, ps, window=None):
    """Drive causal_attention through the same index plan the engine builds.
    Rows past a lane's context are masked AND zeroed: a pool may hold NaN
    there, and XLA's 0 * NaN is NaN too."""
    B, P = table.shape
    C = P * ps
    read_idx = (table[:, :, None] * ps + np.arange(ps)[None, None, :]).reshape(B, C)
    kv_positions = np.broadcast_to(np.arange(C)[None, :], (B, C))
    kv_valid = kv_positions <= np.asarray(seq_lens)[:, None]
    written = jnp.asarray(kv_valid)[:, :, None, None]
    k_win = jnp.asarray(k_pool)[jnp.asarray(read_idx)]  # [B, C, Hkv, D]
    v_win = jnp.asarray(v_pool)[jnp.asarray(read_idx)]
    out = causal_attention(
        jnp.asarray(q)[:, None],  # [B, 1, Hq, D]
        jnp.where(written, k_win, 0).astype(k_win.dtype),
        jnp.where(written, v_win, 0).astype(v_win.dtype),
        q_positions=jnp.asarray(seq_lens)[:, None],
        kv_positions=jnp.asarray(kv_positions),
        kv_valid=jnp.asarray(kv_valid),
        window=window,
    )
    return np.asarray(out[:, 0])  # [B, Hq, D]


class TestPagedDecodeAttention:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_matches_xla_gather_path(self, seed):
        q, k_pool, v_pool, table, seq_lens, = make_paged_case(
            seed, B=4, P=6, ps=8, Hq=8, Hkv=4, D=32, num_pages=32
        )
        ref = xla_reference(q, k_pool, v_pool, table, seq_lens, ps=8)
        out = paged_decode_attention(
            jnp.asarray(q),
            jnp.asarray(k_pool).reshape(k_pool.shape[0], -1),
            jnp.asarray(v_pool).reshape(v_pool.shape[0], -1),
            jnp.asarray(table), jnp.asarray(seq_lens),
            page_size=8, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)

    def test_mqa_single_kv_head(self):
        q, k_pool, v_pool, table, seq_lens = make_paged_case(
            7, B=2, P=4, ps=8, Hq=4, Hkv=1, D=16, num_pages=16
        )
        ref = xla_reference(q, k_pool, v_pool, table, seq_lens, ps=8)
        out = paged_decode_attention(
            jnp.asarray(q),
            jnp.asarray(k_pool).reshape(k_pool.shape[0], -1),
            jnp.asarray(v_pool).reshape(v_pool.shape[0], -1),
            jnp.asarray(table), jnp.asarray(seq_lens),
            page_size=8, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)

    def test_single_token_sequence(self):
        """seq_len=0: only the freshly written slot is attended."""
        q, k_pool, v_pool, table, _ = make_paged_case(
            3, B=2, P=4, ps=8, Hq=4, Hkv=2, D=16, num_pages=16
        )
        seq_lens = np.zeros(2, np.int32)
        ref = xla_reference(q, k_pool, v_pool, table, seq_lens, ps=8)
        out = paged_decode_attention(
            jnp.asarray(q),
            jnp.asarray(k_pool).reshape(k_pool.shape[0], -1),
            jnp.asarray(v_pool).reshape(v_pool.shape[0], -1),
            jnp.asarray(table), jnp.asarray(seq_lens),
            page_size=8, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("seed", [0, 4])
    def test_int8_kernel_matches_dequantized_reference(self, seed):
        """paged_decode_attention_int8 (int8 page DMAs + fused per-slot
        dequant) == the XLA path on the explicitly dequantized window:
        score[h,j] = (qx . k_q^T)[h,j] * s_k[j] and pexp * s_v must equal
        attention over q*s exactly (up to f32 associativity)."""
        from kafka_tpu.models.quant import quantize_array
        from kafka_tpu.ops.pallas import paged_decode_attention_int8

        q, k_pool, v_pool, table, seq_lens = make_paged_case(
            seed, B=4, P=6, ps=8, Hq=8, Hkv=4, D=32, num_pages=32
        )
        HD = k_pool.shape[1] * k_pool.shape[2]
        kq = quantize_array(jnp.asarray(k_pool).reshape(-1, HD), (1,))
        vq = quantize_array(jnp.asarray(v_pool).reshape(-1, HD), (1,))
        # reference attends the DEQUANTIZED values — the kernel's fused
        # scale application must match it, not the original f32 pool
        kd = np.asarray(kq.q, np.float32).reshape(k_pool.shape) * \
            np.asarray(kq.s)[:, None]
        vd = np.asarray(vq.q, np.float32).reshape(v_pool.shape) * \
            np.asarray(vq.s)[:, None]
        ref = xla_reference(q, kd, vd, table, seq_lens, ps=8)
        out = paged_decode_attention_int8(
            jnp.asarray(q), kq.q, kq.s, vq.q, vq.s,
            jnp.asarray(table), jnp.asarray(seq_lens),
            page_size=8, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)

    def test_bf16_pools(self):
        q, k_pool, v_pool, table, seq_lens = make_paged_case(
            11, B=2, P=4, ps=8, Hq=8, Hkv=4, D=32, num_pages=16
        )
        out = paged_decode_attention(
            jnp.asarray(q, jnp.bfloat16),
            jnp.asarray(k_pool, jnp.bfloat16).reshape(k_pool.shape[0], -1),
            jnp.asarray(v_pool, jnp.bfloat16).reshape(v_pool.shape[0], -1),
            jnp.asarray(table), jnp.asarray(seq_lens),
            page_size=8, interpret=True,
        )
        ref = xla_reference(
            q.astype(np.float32), k_pool, v_pool, table, seq_lens, ps=8
        )
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), ref, atol=0.05, rtol=0.05
        )


# Yi-1.5-9B's and Mellum2's attention geometry (q_per_kv 8, D 128, page 16):
# a chunk is 8 pages = 128 keys, a softmax step 4 chunks, so the contexts
# below put the query in the first step, on a chunk edge, on a step edge,
# and several whole steps deep (the unmasked step) with a partial last one.
BF16_CTX = {
    "one_chunk": 99,             # n_valid 100: one masked step
    "chunk_boundary": 383,       # n_valid 384 = 3 chunks exactly
    "step_boundary": 511,        # n_valid 512: the last row of step 0
    "step_boundary_plus_one": 512,   # one row alone in step 1
    "whole_steps_and_partial": 1235,  # two unmasked steps + 212 rows
    "whole_steps_exactly": 1535,      # n_valid 1536 = 3 steps exactly
}
# Both sides multiply the SAME bf16 q, K and V rows with f32 accumulation, so
# the scores agree to f32 rounding.  They differ in where a probability is
# rounded to bf16 for the PV product: causal_attention rounds exp / sum, the
# kernel rounds exp (against the running max) and divides the f32 sum out at
# the end.  Each rounding is at most 2^-9 relative a term, so the two weighted
# means of V differ by under 2 x 2^-9 x max|v| (randn: ~4) = 0.016 in the
# worst case and far less over hundreds of terms of mixed sign; then each side
# rounds its result to bf16 once (2^-9 of values up to ~1).  Measured here:
# at most 2^-8, one bf16 ulp of a result near 1; the bound is two.
BF16_ATOL = 2 ** -7


def make_bf16_case(seed, lens, P=104, ps=16, Hq=16, Hkv=2, D=128):
    """A bf16 pool with NaN in every row no lane has written: pages no table
    names, and the rows of a lane's last page past its context."""
    rng = np.random.RandomState(seed)
    lens = np.asarray(lens, np.int32)
    B = len(lens)
    num_pages = B * P + 1
    k_pool = np.full((num_pages * ps, Hkv, D), np.nan, np.float32)
    v_pool = np.full((num_pages * ps, Hkv, D), np.nan, np.float32)
    free = list(range(1, num_pages))
    rng.shuffle(free)
    table = np.zeros((B, P), np.int32)
    for b, n in enumerate(lens):
        pages = [free.pop() for _ in range(-(-(int(n) + 1) // ps))]
        table[b, :len(pages)] = pages
        rows = (np.asarray(pages)[:, None] * ps + np.arange(ps)).ravel()
        rows = rows[:int(n) + 1]  # position n is this step's own write
        k_pool[rows] = rng.randn(len(rows), Hkv, D)
        v_pool[rows] = rng.randn(len(rows), Hkv, D)
    q = rng.randn(B, Hq, D).astype(np.float32)
    as_bf16 = lambda a: jnp.asarray(a, jnp.bfloat16)
    return as_bf16(q), as_bf16(k_pool), as_bf16(v_pool), table, lens


class TestPagedDecodeBf16Pools:
    """ISSUE 30: the kernel multiplies the pool's own bf16 rows (f32 scores,
    softmax state and accumulators), masks only a walk's boundary steps, and
    no row a lane has not written can reach the result."""

    @pytest.mark.parametrize("ctx", list(BF16_CTX))
    def test_global_matches_causal_attention(self, ctx):
        n = BF16_CTX[ctx]
        q, k, v, table, lens = make_bf16_case(30, [n, max(n - 37, 0)])
        out = paged_decode_attention(
            q, k.reshape(k.shape[0], -1), v.reshape(v.shape[0], -1),
            jnp.asarray(table), jnp.asarray(lens), page_size=16,
            interpret=True)
        assert out.dtype == jnp.bfloat16
        got = np.asarray(out, np.float32)
        assert np.isfinite(got).all()
        ref = xla_reference(q, k, v, table, lens, 16).astype(np.float32)
        np.testing.assert_allclose(got, ref, atol=BF16_ATOL, rtol=0)

    @pytest.mark.parametrize("ctx,window", [
        ("one_chunk", 64),                 # window inside the only step
        ("chunk_boundary", 200),           # lo 184: mid-chunk 1, step 0
        ("step_boundary_plus_one", 300),   # lo 213: two masked steps
        ("whole_steps_and_partial", 1000),  # lo 236: mid-chunk, then whole
        ("whole_steps_exactly", 1024),     # lo 512: a step edge, no mask
        ("whole_steps_and_partial", 600),  # lo 636: walk starts at chunk 4
    ])
    def test_windowed_matches_causal_attention(self, ctx, window):
        from kafka_tpu.ops.pallas import paged_decode_attention_window
        from kafka_tpu.ops.pallas.paged_attention import decode_chunk_range

        n = BF16_CTX[ctx]
        q, k, v, table, lens = make_bf16_case(31, [n, max(n - 90, 0)])
        k, v = np.asarray(k, np.float32), np.asarray(v, np.float32)
        for b, m in enumerate(lens):  # chunks below the walk hold NaN too
            first, _ = decode_chunk_range(int(m), window, 16)
            for page in table[b, :first * 8]:
                k[page * 16:(page + 1) * 16] = np.nan
                v[page * 16:(page + 1) * 16] = np.nan
        k_nan, v_nan = jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16)
        out = paged_decode_attention_window(
            q, k_nan.reshape(k.shape[0], -1), v_nan.reshape(v.shape[0], -1),
            jnp.asarray(table), jnp.asarray(lens), window=window,
            page_size=16, interpret=True)
        got = np.asarray(out, np.float32)
        assert np.isfinite(got).all()
        # the reference gathers everything the table names: give it zeros
        # where the kernel must not have looked
        ref = xla_reference(
            q, jnp.nan_to_num(k_nan), jnp.nan_to_num(v_nan), table, lens, 16,
            window=window).astype(np.float32)
        np.testing.assert_allclose(got, ref, atol=BF16_ATOL, rtol=0)

    def test_f32_pool_keeps_f32_operands(self):
        """The operand dtype follows the pool: with an f32 pool a bf16-sized
        rounding of the probabilities (2^-9) would show at 2e-5."""
        n = BF16_CTX["whole_steps_and_partial"]
        q, k, v, table, lens = make_bf16_case(32, [n])
        q, k, v = (jnp.nan_to_num(a.astype(jnp.float32)) + 0.001
                   for a in (q, k, v))
        out = paged_decode_attention(
            q, k.reshape(k.shape[0], -1), v.reshape(v.shape[0], -1),
            jnp.asarray(table), jnp.asarray(lens), page_size=16,
            interpret=True)
        ref = xla_reference(q, k, v, table, lens, 16)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


class TestEnginePallasBackend:
    def test_engine_end_to_end_pallas_interpret(self):
        """Forced-pallas engine (interpret off-TPU) matches the XLA engine
        token-for-token at f32 — covers both kernels through the real
        prefill/decode scheduler."""
        from kafka_tpu.models import ModelConfig, init_params
        from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine

        cfg = ModelConfig(name="pallas-e2e", vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=8,
                          num_kv_heads=2, head_dim=16, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(13))
        prompt = list(np.random.RandomState(2).randint(1, 128, size=21))
        outs = {}
        for backend in ("xla", "pallas"):
            eng = InferenceEngine(
                cfg, params,
                EngineConfig(max_batch=2, page_size=16, num_pages=32,
                             max_pages_per_seq=8, prefill_buckets=(16,),
                             attention_backend=backend),
                kv_dtype=jnp.float32,
            )
            outs[backend] = eng.generate(prompt, max_new_tokens=6).output_ids
        assert outs["pallas"] == outs["xla"]

    def test_padded_bucket_and_prefix_hit_suffix_match_xla(self):
        """A 256-row bucket is 8 q blocks of 32 at 32 heads.  A 70-token
        prompt fills 3 of them, a 19-token suffix after a prefix hit one:
        the flash-prefill kernel walks no KV for the rest and writes zeros
        there, which flow through the MLP into the trash page.  Forced
        pallas (interpreted) against forced xla, token for token, through
        the engine's own prefill program (`correct` cannot see it: the
        benchmark's logit check calls `forward`, and its prefill(64) has
        no padded block)."""
        from kafka_tpu.models import ModelConfig, init_params
        from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine

        cfg = ModelConfig(name="pallas-padded", vocab_size=128,
                          hidden_size=64, intermediate_size=128,
                          num_layers=2, num_heads=32, num_kv_heads=4,
                          head_dim=8, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(13))
        rng = np.random.RandomState(4)
        shared = list(rng.randint(1, 128, size=70))
        suffix = list(rng.randint(1, 128, size=19))
        outs = {}
        for backend in ("xla", "pallas"):
            eng = InferenceEngine(
                cfg, params,
                EngineConfig(max_batch=2, page_size=16, num_pages=48,
                             max_pages_per_seq=24, prefill_buckets=(256,),
                             attention_backend=backend),
                kv_dtype=jnp.float32,
            )
            first = GenRequest(request_id="A", prompt_ids=shared,
                               max_new_tokens=6, prefix_key="thread-A")
            eng.submit(first)
            eng.run_to_completion()
            second = GenRequest(request_id="B", prompt_ids=shared + suffix,
                                max_new_tokens=6, prefix_key="thread-B")
            eng.submit(second)
            eng.run_to_completion()
            assert second.cached_tokens == 64  # whole pages of the hit
            # two launches of the one bucket: 70 and 89 - 64 real rows
            assert eng.prefill_rows_dispatched == 2 * 256
            assert eng.prefill_rows_filled == 70 + 25
            outs[backend] = (first.output_ids, second.output_ids)
        assert outs["pallas"] == outs["xla"]

    @pytest.mark.skipif(len(jax.devices()) < 8,
                        reason="needs 8 virtual devices")
    @pytest.mark.parametrize("mesh_axes", [
        {"tp": 2},            # plain Megatron split (1 kv head/shard)
        {"tp": 2, "tq": 2},   # grouped GQA (q over tp*tq, kv over tp)
    ])
    def test_engine_pallas_on_mesh_matches_xla(self, mesh_axes):
        """Forced-pallas engine ON A MESH (decode kernel per-shard via
        shard_map, prefill on the XLA path) is token-exact vs the forced-
        xla mesh engine AND the single-device engine — the capability
        GSPMD alone cannot provide (it cannot partition a custom call).

        Runs in a child interpreter: shard_map-wrapped interpret-mode
        kernels destabilize the shared test process (tests/_isolation.py).
        """
        from _isolation import isolated

        pid = "mesh_axes1" if "tq" in mesh_axes else "mesh_axes0"
        if not isolated(
            "tests/test_pallas_kernels.py::TestEnginePallasBackend::"
            f"test_engine_pallas_on_mesh_matches_xla[{pid}]"
        ):
            return
        from kafka_tpu.models import ModelConfig, init_params
        from kafka_tpu.parallel import MeshConfig, make_mesh
        from kafka_tpu.runtime import EngineConfig, InferenceEngine

        cfg = ModelConfig(name="pallas-mesh", vocab_size=128,
                          hidden_size=64, intermediate_size=128,
                          num_layers=2, num_heads=8, num_kv_heads=2,
                          head_dim=16, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(13))
        prompt = list(np.random.RandomState(3).randint(1, 128, size=21))
        ecfg = dict(max_batch=2, page_size=16, num_pages=32,
                    max_pages_per_seq=8, prefill_buckets=(16,))
        want = InferenceEngine(
            cfg, params, EngineConfig(**ecfg), kv_dtype=jnp.float32
        ).generate(prompt, max_new_tokens=6).output_ids
        outs = {}
        for backend in ("xla", "pallas"):
            eng = InferenceEngine(
                cfg, params,
                EngineConfig(**ecfg, attention_backend=backend),
                kv_dtype=jnp.float32,
                mesh=make_mesh(MeshConfig(**mesh_axes)),
            )
            outs[backend] = eng.generate(prompt, max_new_tokens=6).output_ids
        assert outs["pallas"] == outs["xla"] == want

    def test_pallas_mesh_ok_gates(self):
        from kafka_tpu.ops.pallas import pallas_mesh_ok
        from kafka_tpu.parallel import MeshConfig, make_mesh

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        # plain tp over dividing kv heads: ok at any local kv count
        assert pallas_mesh_ok(make_mesh(MeshConfig(tp=2)), 8, 4)
        assert pallas_mesh_ok(make_mesh(MeshConfig(tp=2)), 8, 2)
        # grouped: exactly one kv head per shard required
        assert pallas_mesh_ok(make_mesh(MeshConfig(tp=2, tq=2)), 8, 2)
        assert not pallas_mesh_ok(make_mesh(MeshConfig(tp=2, tq=2)), 8, 4)
        # tp must divide kv heads
        assert not pallas_mesh_ok(make_mesh(MeshConfig(tp=4)), 8, 2)
        # non-tensor axes exclude the per-shard kernel
        assert not pallas_mesh_ok(make_mesh(MeshConfig(sp=2, tp=2)), 8, 2)
        assert not pallas_mesh_ok(make_mesh(MeshConfig(dp=2, tp=2)), 8, 2)

    @pytest.mark.skipif(len(jax.devices()) < 8,
                        reason="needs 8 virtual devices")
    def test_explicit_pallas_on_bad_mesh_raises(self):
        from kafka_tpu.models import ModelConfig, init_params
        from kafka_tpu.parallel import MeshConfig, make_mesh
        from kafka_tpu.runtime import EngineConfig, InferenceEngine

        cfg = ModelConfig(name="pallas-badmesh", vocab_size=128,
                          hidden_size=64, intermediate_size=128,
                          num_layers=2, num_heads=8, num_kv_heads=2,
                          head_dim=16, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(13))
        with pytest.raises(ValueError, match="pure tp"):
            InferenceEngine(
                cfg, params,
                EngineConfig(max_batch=2, page_size=16, num_pages=32,
                             max_pages_per_seq=8, prefill_buckets=(16,),
                             attention_backend="pallas"),
                kv_dtype=jnp.float32,
                mesh=make_mesh(MeshConfig(tp=4)),  # 4 !| 2 kv heads
            )

    @pytest.mark.skipif(len(jax.devices()) < 8,
                        reason="needs 8 virtual devices")
    def test_engine_pallas_mesh_fused_multistep_matches(self):
        """The serving default wraps the decode body in a fused lax.scan
        (multi_step) — the per-shard pallas kernel must compose with the
        scan on a mesh.  Token-exact vs the single-step xla mesh engine,
        and the fused dispatch must actually engage.  Child-isolated
        (tests/_isolation.py)."""
        from _isolation import isolated

        if not isolated(
            "tests/test_pallas_kernels.py::TestEnginePallasBackend::"
            "test_engine_pallas_mesh_fused_multistep_matches"
        ):
            return
        from kafka_tpu.models import ModelConfig, init_params
        from kafka_tpu.parallel import MeshConfig, make_mesh
        from kafka_tpu.runtime import (
            EngineConfig, GenRequest, InferenceEngine,
        )

        cfg = ModelConfig(name="pallas-mesh-fused", vocab_size=128,
                          hidden_size=64, intermediate_size=128,
                          num_layers=2, num_heads=8, num_kv_heads=2,
                          head_dim=16, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(13))
        ecfg = dict(max_batch=4, page_size=16, num_pages=64,
                    max_pages_per_seq=8, prefill_buckets=(16,))
        base = InferenceEngine(
            cfg, params, EngineConfig(**ecfg, multi_step=1),
            kv_dtype=jnp.float32,
        )
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(**ecfg, multi_step=4, attention_backend="pallas"),
            kv_dtype=jnp.float32,
            mesh=make_mesh(MeshConfig(tp=2, tq=2)),
        )
        fused = []
        orig = eng._dispatch_multi
        eng._dispatch_multi = lambda k: (fused.append(k), orig(k))[1]
        prompts = {"a": [3, 9, 27, 81], "b": [100] * 11,
                   "c": [7, 6, 5], "d": [1, 2]}
        for rid, p in prompts.items():
            base.submit(GenRequest(request_id=rid, prompt_ids=p,
                                   max_new_tokens=12))
            eng.submit(GenRequest(request_id=rid, prompt_ids=p,
                                  max_new_tokens=12))
        want = base.run_to_completion()
        got = eng.run_to_completion()
        assert fused and set(fused) == {4}
        for rid in prompts:
            assert got[rid].output_ids == want[rid].output_ids, rid


class TestPagedVerifyAttention:
    """K+1-query speculative-verify kernel (ISSUE 5) vs the XLA reference
    driven with per-query causal masking — interpret mode on CPU."""

    def _case(self, seed, B=3, P=6, ps=8, Hq=4, Hkv=2, D=16, num_pages=24,
              S=4):
        rng = np.random.RandomState(seed)
        total = num_pages * ps
        k_pool = rng.randn(total, Hkv * D).astype(np.float32)
        v_pool = rng.randn(total, Hkv * D).astype(np.float32)
        q = rng.randn(B, S, Hq, D).astype(np.float32)
        free = list(range(1, num_pages))
        rng.shuffle(free)
        table = np.zeros((B, P), np.int32)
        # leave room for the S fresh positions inside the table
        seq_lens = rng.randint(1, P * ps - S - 1, size=B).astype(np.int32)
        q_lens = rng.randint(1, S + 1, size=B).astype(np.int32)
        for b in range(B):
            need = int(np.ceil((seq_lens[b] + S + 1) / ps))
            for i in range(need):
                table[b, i] = free.pop()
        return q, k_pool, v_pool, table, seq_lens, q_lens

    def _xla_reference(self, q, k_pool, v_pool, table, seq_lens, q_lens, ps):
        B, S = q.shape[:2]
        P = table.shape[1]
        C = P * ps
        D = q.shape[-1]
        Hkv = k_pool.shape[1] // D
        read_idx = (
            table[:, :, None] * ps + np.arange(ps)[None, None, :]
        ).reshape(B, C)
        kv_positions = np.broadcast_to(np.arange(C)[None, :], (B, C))
        kv_valid = kv_positions <= (seq_lens + q_lens - 1)[:, None]
        k_win = jnp.asarray(k_pool)[jnp.asarray(read_idx)].reshape(
            B, C, Hkv, D)
        v_win = jnp.asarray(v_pool)[jnp.asarray(read_idx)].reshape(
            B, C, Hkv, D)
        pos = seq_lens[:, None] + np.arange(S)[None, :]
        out = causal_attention(
            jnp.asarray(q),  # [B, S, Hq, D]
            k_win, v_win,
            q_positions=jnp.asarray(pos),
            kv_positions=jnp.asarray(kv_positions),
            kv_valid=jnp.asarray(kv_valid),
        )
        return np.asarray(out)  # [B, S, Hq, D]

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_matches_xla_per_query_causal(self, seed):
        from kafka_tpu.ops.pallas import paged_verify_attention

        ps = 8
        q, k_pool, v_pool, table, seq_lens, q_lens = self._case(seed, ps=ps)
        # materialize the S fresh positions' KV like the engine does
        # (writes happen before the kernel reads)
        out = paged_verify_attention(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(table), jnp.asarray(seq_lens),
            jnp.asarray(q_lens), page_size=ps, interpret=True,
        )
        ref = self._xla_reference(q, k_pool, v_pool, table, seq_lens,
                                  q_lens, ps)
        S = q.shape[1]
        for b in range(q.shape[0]):
            # only the q_lens[b] valid query rows carry a contract
            valid = int(q_lens[b])
            np.testing.assert_allclose(
                np.asarray(out)[b, :valid], ref[b, :valid],
                rtol=2e-4, atol=2e-4,
            )

    def test_engine_end_to_end_pallas_speculative(self):
        """Forced-pallas engine WITH speculation (verify kernel in
        interpret mode) matches the XLA speculative engine and the plain
        non-speculative engine token-for-token."""
        from kafka_tpu.models import ModelConfig, init_params
        from kafka_tpu.runtime import EngineConfig, InferenceEngine

        cfg = ModelConfig(name="pallas-spec", vocab_size=128,
                          hidden_size=64, intermediate_size=128,
                          num_layers=2, num_heads=8, num_kv_heads=2,
                          head_dim=16, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(13))
        prompt = list(np.random.RandomState(5).randint(1, 128, size=15))
        outs = {}
        engines = {}
        for backend, k in (("xla", 0), ("xla", 4), ("pallas", 4)):
            eng = InferenceEngine(
                cfg, params,
                EngineConfig(max_batch=2, page_size=16, num_pages=32,
                             max_pages_per_seq=8, prefill_buckets=(16,),
                             attention_backend=backend, speculative_k=k),
                kv_dtype=jnp.float32,
            )
            outs[(backend, k)] = eng.generate(
                prompt, max_new_tokens=16).output_ids
            engines[(backend, k)] = eng
        assert outs[("xla", 4)] == outs[("xla", 0)]
        assert outs[("pallas", 4)] == outs[("xla", 0)]
        # the pallas run must have actually exercised the verify kernel
        assert engines[("pallas", 4)].metrics.speculation_verify_steps > 0


# ----------------------------------------------------------------------
# one physical layout or another: the run copy (PR 53)
# ----------------------------------------------------------------------
#
# _decode_kernel fetches a whole softmax step whose pages are one ascending
# run of physical pages as ONE copy a pool, every other step a copy a page.
# Only the route the rows take into VMEM differs, so the same logical K/V
# must give the same bits whatever the physical layout: each case lays the
# lanes' pages out as named, then the SAME pages shuffled (no run anywhere:
# the copies the kernel always made), and compares with array_equal.

RUN_PS, RUN_P = 16, 128      # 2,048-key tables: four 512-key steps of 32 pages
RUN_SP = 32
RUN_WINDOW = 1200


def _run_layouts():
    """{name: (per-lane physical page lists, seq_lens)}."""
    rng = np.random.RandomState(53)
    far = lambda n, lo: [int(p) for p in rng.permutation(  # noqa: E731
        np.arange(lo, lo + 4 * n))[:n]]
    lens = [2040, 1500, 700]
    need = [-(-(n + 1) // RUN_PS) for n in lens]
    runs = [list(range(1 + 200 * b, 1 + 200 * b + n))
            for b, n in enumerate(need)]
    out = {"all_runs": (runs, lens)}
    # two whole steps of a prefix every lane shares, the tails scattered
    prefix = list(range(1, 2 * RUN_SP + 1))
    out["run_prefix_scattered_tail"] = (
        [prefix[:n] + far(max(n - len(prefix), 0), 1000 + 600 * b)
         for b, n in enumerate(need)], lens)
    broken = [list(r) for r in runs]
    for b, r in enumerate(broken):      # one page out of line, mid-step
        if len(r) > RUN_SP + 13:
            r[RUN_SP + 13] = 900 + b
    out["run_broken_mid_step"] = (broken, lens)
    out["descending_run"] = ([r[::-1] for r in runs], lens)
    out["run_starts_mid_step"] = (
        [far(16, 1000 + 100 * b) + r[16:] for b, r in enumerate(runs)], lens)
    # windowed: page0 = (n + 1 - window) // 128 * 8 is 48, 16 (chunk- but
    # not step-aligned) and 32 (step-aligned)
    lens = [2040, 1500, RUN_WINDOW + 4 * 128 + 5]
    need = [-(-(n + 1) // RUN_PS) for n in lens]
    out["window_starts_off_step"] = (
        [list(range(1 + 200 * b, 1 + 200 * b + n))
         for b, n in enumerate(need)], lens)
    # the last step full to its last row; one row into the next step
    lens = [2 * 512 - 1, 3 * 512, 512 - 1]
    need = [-(-(n + 1) // RUN_PS) for n in lens]
    out["context_ends_on_a_step"] = (
        [list(range(1 + 200 * b, 1 + 200 * b + n))
         for b, n in enumerate(need)], lens)
    return out


RUN_LAYOUTS = _run_layouts()
RUN_FORMS = ("global", "window", "latent", "latent_window", "diff")


RUN_POOL_PAGES = 2400   # one pool shape for every case: one compile a form


def _lay_out(pages, content, place):
    """(pools, table): the lanes' page lists `pages` with page p at physical
    page place(p), holding `content[p]` (its rows in each pool); every other
    page NaN."""
    pools = [np.full((RUN_POOL_PAGES * RUN_PS, rows.shape[1]), np.nan,
                     np.float32) for rows in next(iter(content.values()))]
    table = np.zeros((len(pages), RUN_P), np.int32)
    for b, row in enumerate(pages):
        table[b, :len(row)] = [place(p) for p in row]
    assert table.max() < RUN_POOL_PAGES
    for p, both in content.items():
        for pool, rows in zip(pools, both):
            pool[place(p) * RUN_PS:(place(p) + 1) * RUN_PS] = rows
    return pools, table


def _form_call(form, q, args, window, interpret=True):
    """One of RUN_FORMS over q [B, 4, 32] (latent: [q^ | q_rope], 64 + 16)
    and args = (pool, pool, table, lens)."""
    if form.startswith("latent"):
        return paged_decode_attention_latent(
            q[..., :64], q[..., 64:], *args, scale=0.11, page_size=RUN_PS,
            interpret=interpret, window=window)
    if window:
        return paged_decode_attention_window(
            q, *args, window=window, page_size=RUN_PS, interpret=interpret)
    return paged_decode_attention(
        q, *args, page_size=RUN_PS, interpret=interpret, diff=form == "diff")


def _run_case(form, dtype, layout):
    """[(output, table)] of `form` over the layout as named and over the
    same pages spread out, and the lanes' contexts."""
    pages, lens = RUN_LAYOUTS[layout]
    rng = np.random.RandomState(len(layout))
    latent = form.startswith("latent")
    widths = (64, 32) if latent else (64, 64)   # c~ | k_r, or K | V at 2 x 32
    logical = sorted({p for row in pages for p in row})
    content = {p: [rng.randn(RUN_PS, w).astype(np.float32) for w in widths]
               for p in logical}
    # the same pages, nowhere two side by side in a lane's list
    spread = dict(zip(logical, (int(p) for p in rng.permutation(
        np.arange(1, RUN_POOL_PAGES // 2 - 1))[:len(logical)] * 2 + 1)))
    q = rng.randn(len(pages), 4, 64 + 16 if latent else 32).astype(np.float32)
    window = RUN_WINDOW if form.endswith("window") else None
    outs = []
    for place in (lambda p: p, spread.__getitem__):
        pools, table = _lay_out(pages, content, place)
        args = ([jnp.asarray(pool, dtype) for pool in pools]
                + [jnp.asarray(table), jnp.asarray(lens, jnp.int32)])
        out = _form_call(form, jnp.asarray(q, dtype), args, window)
        outs.append((np.asarray(out, np.float32), table))
    return outs, lens


@pytest.mark.parametrize("layout", sorted(RUN_LAYOUTS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("form", RUN_FORMS)
def test_one_physical_layout_or_another_gives_the_same_bits(form, dtype,
                                                            layout):
    (laid, table), (spread, spread_table) = _run_case(
        form, jnp.dtype(dtype), layout)[0]
    assert np.isfinite(laid).all() and np.isfinite(spread).all()
    assert np.array_equal(laid, spread)
    # the spread layout is the control: no step of it is a run
    pages, lens = RUN_LAYOUTS[layout]
    window = RUN_WINDOW if form.endswith("window") else None
    runs = [decode_step_runs(t[b].tolist(), n, window, RUN_PS, RUN_P)
            for t in (table, spread_table) for b, n in enumerate(lens)]
    assert sum(r for _, r in runs[len(lens):]) == 0
    if layout in ("all_runs", "window_starts_off_step"):
        assert all(w == r for w, r in runs[:len(lens)])
        assert sum(w for w, _ in runs[:len(lens)]) > 0


@pytest.mark.parametrize("window", [None, RUN_WINDOW])
@pytest.mark.parametrize("layout", sorted(RUN_LAYOUTS))
def test_run_step_count_is_the_kernels_own_test(layout, window):
    """`decode_step_runs` (plain ints: the engine's counter) against the
    walk's arithmetic redone here and `pages_one_run` traced the way the
    kernel traces it, and `SequencePages.run_steps` against both."""
    pages, lens = RUN_LAYOUTS[layout]
    cp = 8

    @jax.jit
    def traced(row, base):
        return pages_one_run(
            lambda i: row[i], base, RUN_SP,
            functools.partial(jax.lax.fori_loop, unroll=True))

    for row, n in zip(pages, lens):
        n_pages = -(-(n + 1) // RUN_PS)
        page0 = 0 if window is None else (
            max(n + 1 - window, 0) // (cp * RUN_PS) * cp)
        last = -(-(n_pages - page0) // RUN_SP) - 1
        padded = jnp.asarray(row + [0] * (RUN_P - len(row)), jnp.int32)
        want = [bool(np.all(np.diff(row[page0 + k * RUN_SP:][:RUN_SP]) == 1))
                for k in range(last)]
        assert [bool(traced(padded, page0 + k * RUN_SP))
                for k in range(last)] == want
        assert decode_step_runs(
            row, n, window, RUN_PS, RUN_P) == (last, sum(want))
        if window is None:
            seq = SequencePages("s", pages=[])
            for cut in (len(row) // 3, len(row)):   # as the list grows
                seq.pages.extend(row[len(seq.pages):cut])
                for upto in range(last + 1):
                    done = min(upto, cut // RUN_SP)
                    assert seq.run_steps(RUN_SP, upto) == sum(want[:done])


# ----------------------------------------------------------------------
# one pipeline across a call's lanes (PR 62)
# ----------------------------------------------------------------------
#
# _decode_kernel starts a lane's first RING - 1 steps while the lane before
# it attends its last ones: the ring's slots, the semaphores and the place in
# the stream carry over from one lane's program to the next.  What only that
# can get wrong is a start made for the wrong neighbour (its rows, its slot,
# its count of pages), so each case asserts the batched call equals the same
# lanes called ONE AT A TIME (a call of one lane fills and drains by itself),
# bit for bit, every lane, the discarded ones too.

PIPE_KEYS = {1: 300, 2: 700, 3: 1100, 4: 1800}   # steps of a global walk: keys
assert RING == 3   # the cases below are RING - 1 = 2 and RING + 1 = 4 steps


def _pipe_cases():
    """{name: [(keys held, live)] lane by lane}; a lane that is not live has
    the trash page in every column of its row."""
    out = {}
    for order in itertools.permutations((1, RING - 1, RING + 1)):
        out["steps_" + "_".join(map(str, order))] = [
            (PIPE_KEYS[n], True) for n in order]
    out["one_lane"] = [(PIPE_KEYS[3], True)]
    out["zero_length_lane"] = [
        (PIPE_KEYS[2], True), (0, True), (PIPE_KEYS[4], True)]
    out["trash_lane_between"] = [
        (PIPE_KEYS[4], True), (PIPE_KEYS[2], False), (PIPE_KEYS[1], True)]
    out["last_lane_longest"] = [
        (PIPE_KEYS[1], True), (PIPE_KEYS[1], True), (2040, True)]
    out["last_lane_shortest"] = [
        (2040, True), (PIPE_KEYS[3], True), (3, True)]
    return out


PIPE_CASES = _pipe_cases()


def _pipe_inputs(form, case):
    """(q, pools, table, lens, window) of a case: scattered pages but every
    other lane's, which are one run; every row of the pools finite (a lane on
    the trash page reads page 0)."""
    lanes = PIPE_CASES[case]
    rng = np.random.RandomState(62 + len(case))
    latent = form.startswith("latent")
    widths = (64, 128) if latent else (64, 64)
    pools = [rng.randn(RUN_POOL_PAGES * RUN_PS, w).astype(np.float32)
             for w in widths]
    free = [int(p) for p in rng.permutation(np.arange(700, RUN_POOL_PAGES))]
    table = np.zeros((len(lanes), RUN_P), np.int32)
    for b, (n, live) in enumerate(lanes):
        if not live:
            continue
        need = -(-(n + 1) // RUN_PS)
        table[b, :need] = (range(1 + 200 * b, 1 + 200 * b + need) if b % 2
                           else [free.pop() for _ in range(need)])
    q = rng.randn(len(lanes), 4, 64 + 16 if latent else 32).astype(np.float32)
    lens = np.asarray([n for n, _ in lanes], np.int32)
    return q, pools, table, lens, (
        RUN_WINDOW if form.endswith("window") else None)


def _pipe_call(form, q, pools, table, lens, window, interpret=True):
    args = ([jnp.asarray(pool) for pool in pools]
            + [jnp.asarray(table), jnp.asarray(lens)])
    return _form_call(form, jnp.asarray(q), args, window, interpret)


@pytest.mark.parametrize("case", sorted(PIPE_CASES))
@pytest.mark.parametrize("form", RUN_FORMS)
def test_a_call_of_many_lanes_is_its_lanes_called_one_at_a_time(form, case):
    q, pools, table, lens, window = _pipe_inputs(form, case)
    together = np.asarray(_pipe_call(form, q, pools, table, lens, window))
    assert np.isfinite(together).all()
    for b in range(len(lens)):
        alone = np.asarray(_pipe_call(
            form, q[b:b + 1], pools, table[b:b + 1], lens[b:b + 1], window))
        assert np.array_equal(together[b:b + 1], alone), (b, int(lens[b]))
    if window is None:
        # the cases are what their names say: steps of the global walk
        steps = [decode_step_runs(table[b].tolist(), int(n), None, RUN_PS,
                                  RUN_P)[0] + 1 for b, n in enumerate(lens)]
        assert steps == [n // 512 + 1 for n in lens]
        if case.startswith("steps_"):
            assert steps == [int(s) for s in case.split("_")[1:]]
    if form in ("global", "window"):
        live = [b for b, (_, on) in enumerate(PIPE_CASES[case]) if on]
        k_pool, v_pool = (pool.reshape(-1, 2, 32) for pool in pools)
        ref = xla_reference(q, k_pool, v_pool, table, lens, RUN_PS, window)
        np.testing.assert_allclose(
            together[live], np.asarray(ref)[live], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("form", RUN_FORMS)
def test_every_copy_a_lane_reads_was_waited_for(form):
    """The TPU interpreter with copies that land only when they are WAITED
    for and its race detector on: a step attended before the wait that
    stands for its copies (a neighbour's start signalled on another slot's
    semaphore, or counted other pages than the lane waits for) reads what
    the ring held before, or is reported as a race."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    case = "steps_2_1_4"   # a lane of one step hands a start on
    args = _pipe_inputs(form, case)
    want = np.asarray(_pipe_call(form, *args))
    got = np.asarray(_pipe_call(form, *args, interpret=pltpu.InterpretParams(
        dma_execution_mode="on_wait", detect_races=True)))
    assert np.array_equal(got, want)
    assert not interpret_pallas_call.races.races_found
