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


# ---------------------------------------------------------------------------
# ISSUE 44: the served geometries through the lane-group form
# ---------------------------------------------------------------------------

# (query heads, KV heads, head_dim, differential pairing, the window its
# model's sliding layers have): Yi / Mellum2, K-EXAONE, Phi-4-mini-flash,
# llama-3.2-1b (chip_smoke; no served window, so K-EXAONE's)
SERVED = {
    "32/4x128": (32, 4, 128, False, 1024),
    "64/8x128": (64, 8, 128, False, 128),
    "40/20x64-diff": (40, 20, 64, True, 512),
    "32/8x64": (32, 8, 64, False, 128),
}
S_PS, S_CP, S_QB, S_ROWS = 16, 2, 16, 48   # chunk 32 keys, three q blocks
S_CHUNK = S_PS * S_CP


def served_positions(base):
    """{case: (start, chunk_len)} around `base` (0, or the window rounded
    up to a chunk so that every row's window floor is a real bound): where
    the causal edge, the written total kv_hi and the window floor (start -
    window + 1 for the first row) fall against the 32-key chunks."""
    c = S_CHUNK
    return {
        # (a) inside a chunk: first query, kv_hi and floor all mid-chunk
        "inside": (base + 2 * c + c // 2 - 3, S_ROWS - 5),
        # (b) exactly on a chunk edge: the first query IS a chunk's first
        # key (the chunk under it is whole), ...
        "q0_first_key": (base + 3 * c, S_ROWS),
        # ... its last key (that chunk is whole to its last column), ...
        "q0_last_key": (base + 3 * c - 1, S_ROWS),
        # ... kv_hi on a chunk's end, ...
        "kv_hi_on_edge": (base + 2 * c + 7, c - 7),
        # ... the first row's floor on a chunk's first key (windowed)
        "floor_on_edge": (base + 2 * c - 1, S_ROWS - 2),
        # (c) in the q block's first chunk: the walk's first chunk holds
        # the edge, kv_hi and (windowed) the floor at once
        "first_chunk": (base + 3, c - 9),
        "from_zero": (0, 20),
        # the third q block holds no token
        "padded_block": (base + c + 5, S_QB + 3),
    }


def served_reference(q, k_pool, v_pool, page_row, start, chunk_len, hkv, d,
                     diff, window):
    """Plain attention in float64 over the sequence's rows: [S, Hq, D], or
    [S, Hq, 2 D] over both value heads of the pair when `diff`."""
    S, hq, _ = q.shape
    total = start + chunk_len
    idx = (page_row[:, None] * S_PS + np.arange(S_PS)[None, :]).reshape(-1)
    k = k_pool[idx[:total]].reshape(total, hkv, d).astype(np.float64)
    v = v_pool[idx[:total]].reshape(total, hkv, d).astype(np.float64)
    g = hq // hkv
    q_pos = start + np.arange(S)[:, None]
    kv_pos = np.arange(total)[None, :]
    mask = kv_pos <= q_pos
    if window is not None:
        mask &= kv_pos > q_pos - window
    out = np.zeros((S, hq, 2 * d if diff else d))
    for h in range(hq):
        kh = 2 * (h // (2 * g)) + h % 2 if diff else h // g
        s = q[:, h].astype(np.float64) @ k[:, kh].T * d ** -0.5
        s = np.where(mask, s, -np.inf)
        p = np.exp(s - s.max(axis=1, keepdims=True))
        p /= p.sum(axis=1, keepdims=True)
        vh = (np.concatenate([v[:, kh - kh % 2], v[:, kh - kh % 2 + 1]], 1)
              if diff else v[:, kh])
        out[:, h] = p @ vh
    return out


class TestServedGeometries:
    @pytest.mark.parametrize("case", sorted(served_positions(0)))
    @pytest.mark.parametrize("windowed", [False, True],
                             ids=["global", "windowed"])
    @pytest.mark.parametrize("geometry", sorted(SERVED))
    def test_real_rows_match_and_padded_blocks_are_zero(self, geometry,
                                                        windowed, case):
        hq, hkv, d, diff, window = SERVED[geometry]
        base = 0
        if windowed:
            base = -(-window // S_CHUNK) * S_CHUNK
        else:
            window = None
        start, chunk_len = served_positions(base)[case]
        # one table length a (geometry, window): one compile for its cases
        P = (base + 3 * S_CHUNK + S_ROWS) // S_PS + 2
        q, k_pool, v_pool, page_row = make_case(
            3, S_ROWS, start, chunk_len, S_PS, P, hq, hkv, d)
        np.random.RandomState(4).shuffle(page_row)  # scattered pages
        # pages past the written total hold NaN (see TestPaddedBlocks)
        written = -(-(start + chunk_len) // S_PS)
        for pool in (k_pool, v_pool):
            for page in page_row[written:]:
                pool[page * S_PS:(page + 1) * S_PS] = np.nan
        out = np.asarray(paged_prefill_attention(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(page_row), jnp.int32(start), jnp.int32(chunk_len),
            page_size=S_PS, pages_per_chunk=S_CP, q_block=S_QB,
            interpret=True, window=window, diff=diff))
        assert out.shape == (S_ROWS, hq, 2 * d if diff else d)
        assert not np.isnan(out).any()
        ref = served_reference(q, k_pool, v_pool, page_row, start, chunk_len,
                               hkv, d, diff, window)
        np.testing.assert_allclose(out[:chunk_len], ref[:chunk_len],
                                   atol=2e-5, rtol=2e-5)
        first_padded = -(-chunk_len // S_QB) * S_QB
        assert (out[first_padded:] == 0).all()

    @pytest.mark.parametrize("geometry", sorted(SERVED))
    def test_lane_group_is_one_tile_of_whole_kv_heads(self, geometry):
        from kafka_tpu.ops.pallas.flash_prefill import LANES, prefill_plan

        hq, hkv, d, diff, _ = SERVED[geometry]
        plan = prefill_plan(512, hq, hkv, d, 2, diff=diff)
        assert plan["group_lanes"] == LANES
        assert plan["kv_heads_per_group"] == LANES // d
        assert plan["lane_groups"] * plan["kv_heads_per_group"] == hkv


class TestBf16Pool:
    """The served pools are bf16, and `correct` cannot see the prefill
    program (PERF.md section 7), so this holds the kernel's ROUNDING: q's
    bf16 values reach the MXU as the caller gave them and `scale` multiplies
    the f32 scores, as before PR 44.  One KV chunk holds every key, so the
    online softmax is one step and a reference can round where the kernel
    does: the probabilities to bf16 before V, the result to bf16 once."""

    @pytest.mark.parametrize("windowed", [False, True],
                             ids=["global", "windowed"])
    @pytest.mark.parametrize("geometry", ["32/4x128", "64/8x128"])
    def test_scale_is_on_the_f32_scores(self, geometry, windowed):
        hq, hkv, d, diff, _ = SERVED[geometry]  # 128 ** -0.5: no bf16 value
        window = 40 if windowed else None
        rows, start, P = 32, 60, 8              # 92 keys in one 128-key chunk
        bf16 = lambda a: np.asarray(  # noqa: E731
            jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))
        q, k_pool, v_pool, page_row = (
            a if a.dtype == np.int32 else bf16(a) for a in make_case(
                7, rows, start, rows, S_PS, P, hq, hkv, d))
        out = np.asarray(paged_prefill_attention(
            jnp.asarray(q, jnp.bfloat16), jnp.asarray(k_pool, jnp.bfloat16),
            jnp.asarray(v_pool, jnp.bfloat16), jnp.asarray(page_row),
            jnp.int32(start), jnp.int32(rows), page_size=S_PS,
            pages_per_chunk=P, q_block=S_QB, interpret=True, window=window,
        ).astype(jnp.float32))

        total = start + rows
        idx = (page_row[:, None] * S_PS + np.arange(S_PS)[None, :]).reshape(-1)
        k = k_pool[idx[:total]].reshape(total, hkv, d).astype(np.float64)
        v = v_pool[idx[:total]].reshape(total, hkv, d).astype(np.float64)
        q_pos = start + np.arange(rows)[:, None]
        kv_pos = np.arange(total)[None, :]
        mask = kv_pos <= q_pos
        if window is not None:
            mask &= kv_pos > q_pos - window
        ref = np.zeros((rows, hq, d), np.float32)
        for h in range(hq):
            kh = h // (hq // hkv)
            s = (q[:, h].astype(np.float64) @ k[:, kh].T).astype(np.float32)
            s = np.where(mask, s * np.float32(d ** -0.5), np.float32(-1e30))
            p = np.where(mask, np.exp(s - s.max(axis=1, keepdims=True)), 0)
            acc = (bf16(p).astype(np.float64) @ v[:, kh]).astype(np.float32)
            ref[:, h] = acc / p.sum(axis=1, keepdims=True, dtype=np.float32)
        ref = bf16(ref)
        # within a bf16 step at the outputs' size, and all but the ties equal
        # (0.9996 or more here; with scale folded into q before its cast to
        # bf16, half of the outputs move by a step: 0.49-0.51 equal)
        assert np.abs(out - ref).max() <= 2.0 ** -7
        assert (out == ref).mean() > 0.995
