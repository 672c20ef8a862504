"""benchmarks/reference.py against kafka_tpu.models.forward at tiny dense and
tiny MoE sizes on the CPU in float32, so that the on-chip logit check rests
on a tested reference; and the paged prefill + decode path of
benchmarks/paged_step.py against the same reference."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paged_step  # noqa: E402
import reference  # noqa: E402
from kafka_tpu.models import ModelConfig, forward, init_params  # noqa: E402


def tiny(moe: bool, tied: bool = False) -> ModelConfig:
    return ModelConfig(
        name="t", vocab_size=300, hidden_size=64, intermediate_size=96,
        num_layers=3, num_heads=8, num_kv_heads=2, head_dim=8,
        rope_theta=1e4, rms_norm_eps=1e-5, tie_word_embeddings=tied,
        dtype="float32", num_experts=4 if moe else 0, num_experts_per_tok=2)


@pytest.mark.parametrize("moe,tied", [(False, False), (False, True),
                                      (True, False)])
def test_reference_matches_program_forward(moe, tied):
    cfg = tiny(moe, tied)
    params = init_params(cfg, jax.random.PRNGKey(1))
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, size=24)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, jnp.asarray(ids)[None],
                            jnp.arange(24)[None])
    ref = reference.reference_logits(params, reference.hyper(cfg), ids,
                                     list(range(24)))
    np.testing.assert_allclose(np.asarray(logits[0]), ref["logits"],
                               rtol=2e-4, atol=2e-4)
    assert ref["router_gap"].shape == (24,)
    assert np.isinf(ref["router_gap"]).all() != moe


@pytest.mark.parametrize("moe", [False, True])
def test_paged_prefill_and_decode_match_reference(moe):
    cfg = tiny(moe)
    params = init_params(cfg, jax.random.PRNGKey(2))
    ids = np.random.RandomState(3).randint(0, cfg.vocab_size, size=20)
    served = paged_step.served_logits(params, cfg, ids, 16, page_size=16,
                                      pages_per_seq=4)
    ref = reference.reference_logits(params, reference.hyper(cfg), ids,
                                     list(range(15, 20)))
    res = reference.compare_logits(served, ref["logits"], ref["router_gap"],
                                   tol=1e-4)
    assert res["ok"], res


def test_compare_fails_on_a_dropped_term_and_on_coarse_weights():
    cfg = tiny(False)
    params = init_params(cfg, jax.random.PRNGKey(4))
    ids = np.random.RandomState(5).randint(0, cfg.vocab_size, size=12)
    pos = list(range(8, 12))
    hp = reference.hyper(cfg)
    ref = reference.reference_logits(params, hp, ids, pos)
    gap = ref["router_gap"]
    assert reference.compare_logits(ref["logits"], ref["logits"], gap)["ok"]
    # a dropped term: (almost) no rotary positions
    broken = reference.reference_logits(params, dict(hp, rope_theta=1e30),
                                        ids, pos)
    assert not reference.compare_logits(broken["logits"], ref["logits"],
                                        gap)["ok"]
    # weights rounded to 5 bits of mantissa (coarser than int8 per channel)
    coarse = jax.tree_util.tree_map(
        lambda w: (jnp.round(w * 16) / 16).astype(w.dtype)
        if w.ndim > 1 else w, params)
    c = reference.reference_logits(coarse, hp, ids, pos)
    assert not reference.compare_logits(c["logits"], ref["logits"], gap)["ok"]
