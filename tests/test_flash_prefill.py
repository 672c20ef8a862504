"""Flash prefill kernel numerics vs the XLA gather path (interpret mode)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.ops.attention import causal_attention
from kafka_tpu.ops.pallas import paged_prefill_attention
from kafka_tpu.ops.pallas.flash_prefill import prefill_block_chunks


def make_case(seed, S, start, chunk_len, ps, P, Hq, Hkv, D):
    """Pool holds [0, start) from earlier chunks plus this chunk's KV
    (positions start..start+chunk_len), page-ordered."""
    rng = np.random.RandomState(seed)
    num_pages = P + 4
    HD = Hkv * D
    k_pool = rng.randn(num_pages * ps, HD).astype(np.float32)
    v_pool = rng.randn(num_pages * ps, HD).astype(np.float32)
    q = rng.randn(S, Hq, D).astype(np.float32)
    page_row = np.arange(1, P + 1, dtype=np.int32)  # page 0 = trash
    return q, k_pool, v_pool, page_row


def reference(q, k_pool, v_pool, page_row, start, chunk_len, ps, Hkv, D,
              window=None):
    P = len(page_row)
    C = P * ps
    read_idx = (page_row[:, None] * ps + np.arange(ps)[None, :]).reshape(C)
    k_win = k_pool[read_idx].reshape(1, C, Hkv, D)
    v_win = v_pool[read_idx].reshape(1, C, Hkv, D)
    S = q.shape[0]
    q_pos = (start + np.arange(S))[None, :]
    kv_pos = np.arange(C)[None, :]
    kv_valid = kv_pos < (start + chunk_len)
    out = causal_attention(
        jnp.asarray(q)[None], jnp.asarray(k_win), jnp.asarray(v_win),
        q_positions=jnp.asarray(q_pos), kv_positions=jnp.asarray(kv_pos),
        kv_valid=jnp.asarray(kv_valid), window=window,
    )
    return np.asarray(out[0])


class TestFlashPrefill:
    @pytest.mark.parametrize("start,chunk_len,S", [
        (0, 16, 16),     # first chunk, full
        (0, 11, 16),     # first chunk, padded tail
        (32, 16, 16),    # later chunk with context
        (48, 5, 16),     # short final chunk
    ])
    def test_matches_reference(self, start, chunk_len, S):
        ps, P, Hq, Hkv, D = 8, 12, 8, 4, 32
        q, k_pool, v_pool, page_row = make_case(0, S, start, chunk_len, ps, P,
                                                Hq, Hkv, D)
        out = paged_prefill_attention(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(page_row), jnp.int32(start), jnp.int32(chunk_len),
            page_size=ps, q_block=8, interpret=True,
        )
        ref = reference(q, k_pool, v_pool, page_row, start, chunk_len, ps,
                        Hkv, D)
        # rows past chunk_len are garbage on both paths — compare real rows
        np.testing.assert_allclose(
            np.asarray(out)[:chunk_len], ref[:chunk_len],
            atol=2e-5, rtol=2e-5,
        )

    def test_multi_qblock_long_chunk(self):
        ps, P, Hq, Hkv, D = 8, 24, 4, 2, 16
        S, start, chunk_len = 64, 96, 64
        q, k_pool, v_pool, page_row = make_case(5, S, start, chunk_len, ps, P,
                                                Hq, Hkv, D)
        out = paged_prefill_attention(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(page_row), jnp.int32(start), jnp.int32(chunk_len),
            page_size=ps, q_block=16, interpret=True,
        )
        ref = reference(q, k_pool, v_pool, page_row, start, chunk_len, ps,
                        Hkv, D)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)

    def test_mqa(self):
        ps, P, Hq, Hkv, D = 8, 8, 4, 1, 16
        S, start, chunk_len = 16, 8, 16
        q, k_pool, v_pool, page_row = make_case(7, S, start, chunk_len, ps, P,
                                                Hq, Hkv, D)
        out = paged_prefill_attention(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(page_row), jnp.int32(start), jnp.int32(chunk_len),
            page_size=ps, q_block=8, interpret=True,
        )
        ref = reference(q, k_pool, v_pool, page_row, start, chunk_len, ps,
                        Hkv, D)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


QB = 8  # q_block of the padded-bucket cases; S = 8 blocks


class TestPaddedBlocks:
    """ISSUE 28: a q block that holds no token walks no KV and writes
    zeros; every block that holds one is the kernel it was."""

    @pytest.mark.parametrize("window", [None, 12])
    @pytest.mark.parametrize(
        "chunk_len", [1, QB - 1, QB, QB + 1, 3 * QB + 5, 8 * QB])
    def test_real_rows_match_and_padded_blocks_are_zero(self, chunk_len,
                                                        window):
        ps, Hq, Hkv, D = 4, 4, 2, 16
        S, start = 8 * QB, 40
        P = (start + S) // ps + 2
        q, k_pool, v_pool, page_row = make_case(11, S, start, chunk_len, ps,
                                                P, Hq, Hkv, D)
        # the pages past the written total hold NaN: a block that walked
        # them (or multiplied a 0 weight into them) would show it
        written = -(-(start + chunk_len) // ps)
        for pool in (k_pool, v_pool):
            pool[(1 + written) * ps:] = np.nan
        out = np.asarray(paged_prefill_attention(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(page_row), jnp.int32(start), jnp.int32(chunk_len),
            page_size=ps, pages_per_chunk=2, q_block=QB, interpret=True,
            window=window,
        ))
        assert not np.isnan(out).any()
        k_ok, v_ok = np.nan_to_num(k_pool), np.nan_to_num(v_pool)
        ref = reference(q, k_ok, v_ok, page_row, start, chunk_len, ps, Hkv, D,
                        window=window)
        np.testing.assert_allclose(out[:chunk_len], ref[:chunk_len],
                                   atol=2e-5, rtol=2e-5)
        first_padded = -(-chunk_len // QB) * QB
        assert (out[first_padded:] == 0).all()

    @pytest.mark.parametrize("window", [None, 12])
    def test_block_chunk_ranges(self, window):
        """The kernel's own block arithmetic on plain ints, against the
        parent's written out here."""
        ps, cp, start, n_blocks = 4, 2, 40, 8
        S = n_blocks * QB

        def parents(qb, chunk_len):
            kv_hi = start + min((qb + 1) * QB, chunk_len)
            n_chunks = -(-(-(-kv_hi // ps)) // cp)
            first = 0
            if window is not None:
                first = max(start + qb * QB - window + 1, 0) // (ps * cp)
            return kv_hi, first, n_chunks

        def ours(qb, chunk_len):
            return tuple(int(x) for x in prefill_block_chunks(
                qb, start, chunk_len, q_block=QB, page_size=ps,
                pages_per_chunk=cp, window=window))

        for chunk_len in (1, QB - 1, QB, QB + 1, 3 * QB + 5, S):
            last_real = (chunk_len - 1) // QB
            for qb in range(n_blocks):
                if qb <= last_real:
                    assert ours(qb, chunk_len) == parents(qb, chunk_len)
                else:  # no token: no kv position, an empty chunk range
                    kv_hi, _, n_chunks = ours(qb, chunk_len)
                    assert (kv_hi, n_chunks) == (0, 0)
        # a full chunk has no padded block
        assert all(ours(qb, S)[2] > ours(qb, S)[1] for qb in range(n_blocks))
