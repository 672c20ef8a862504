"""Memory-fit planner (runtime/planner.py): the feasibility artifact for
BASELINE topologies this single-chip environment cannot execute.

Ground truths pinned here were OBSERVED on real hardware in round 4:
llama-3-8b bf16 does not fit one v5e chip (the server OOMed; COVERAGE.md),
llama-3-8b int8 does (served at ~540 tok/s).  The unreachable-topology
numbers (v5e-8, v5p-64) are pure arithmetic over the same placement rules
parallel/sharding.py applies, so the planner's credibility rests on the
observed cases matching.
"""

import glob
import math
import os

import jax
import pytest

from kafka_tpu.models import init_params, quantize_params
from kafka_tpu.models.config import config_from_hf_json, get_config
from kafka_tpu.models.quant import param_bytes
from kafka_tpu.runtime.planner import (
    GiB,
    HBM_BYTES,
    kv_bytes_per_token,
    plan_memory,
    plan_for_serving,
    weight_bytes_per_device,
)
from kafka_tpu.server.config import ServingConfig


class TestWeightArithmetic:
    def test_8b_bf16_weights_match_param_count(self):
        # 8.03B params * 2 bytes, +- 1% (norms/rounding)
        cfg = get_config("llama-3-8b")
        wb = weight_bytes_per_device(cfg)
        assert math.isclose(wb, 8.03e9 * 2, rel_tol=0.01)

    def test_int8_halves_weight_bytes(self):
        cfg = get_config("llama-3-8b")
        bf16 = weight_bytes_per_device(cfg)
        int8 = weight_bytes_per_device(cfg, quantize="int8")
        assert 0.50 < int8 / bf16 < 0.53  # 1B/param + f32 scales

    def test_tp_shards_everything_but_embed(self):
        cfg = get_config("llama-3-8b")
        full = weight_bytes_per_device(cfg)
        tp8 = weight_bytes_per_device(cfg, tp=8)
        embed = cfg.vocab_size * cfg.hidden_size * 2  # replicated
        # sharded part must divide by ~8
        assert math.isclose(tp8 - embed, (full - embed) / 8, rel_tol=0.01)

    def test_grouped_kv_shard_when_tp_exceeds_kv_heads(self):
        # 70B: 8 kv heads, degree 16 -> grouped layout (tp=8 x tq=2):
        # per-chip KV is 1/8 of the pool, NOT a full copy
        # (parallel/mesh.py factor_tp_for_kv)
        cfg = get_config("llama-3-70b")
        full = kv_bytes_per_token(cfg, tp=1)
        assert kv_bytes_per_token(cfg, tp=16) == full // 8
        assert kv_bytes_per_token(cfg, tp=8) == full // 8
        # a degree sharing no factor with Hkv degrades to full replication
        assert kv_bytes_per_token(cfg, tp=3) == full

    def test_moe_experts_shard_over_ep_and_tp(self):
        cfg = get_config("mixtral-8x7b")
        full = weight_bytes_per_device(cfg)
        ep8 = weight_bytes_per_device(cfg, ep=8)
        # experts are ~96% of Mixtral's params; ep8 keeps 1/8 of them
        assert ep8 < 0.2 * full
        assert weight_bytes_per_device(cfg, ep=8, tp=4) < ep8


class TestObservedGroundTruths:
    """Cases executed on the real chip in round 4 — the planner must agree."""

    def test_8b_bf16_does_not_fit_one_v5e(self):
        plan = plan_memory(
            get_config("llama-3-8b"), num_pages=512, page_size=16,
            max_pages_per_seq=128, max_batch=8,
        )
        assert not plan.fits
        assert plan.weight_bytes > 14 * GiB  # weights alone ~15 GiB

    def test_8b_int8_fits_one_v5e(self):
        plan = plan_memory(
            get_config("llama-3-8b"), num_pages=512, page_size=16,
            max_pages_per_seq=128, max_batch=8, quantize="int8",
        )
        assert plan.fits
        assert plan.headroom_bytes > 4 * GiB

    def test_1b_bf16_fits_with_room(self):
        plan = plan_memory(
            get_config("llama-3.2-1b"), num_pages=2048, page_size=16,
            max_pages_per_seq=512, max_batch=8,
        )
        assert plan.fits and plan.headroom_bytes > 8 * GiB


class TestBaselineTopologies:
    """BASELINE configs 3 and 5: the feasibility numbers for topologies
    this environment cannot reach (VERDICT r4 weak #6)."""

    def test_config3_8b_tp8_v5e8_holds_256_threads_at_2k(self):
        # 256 concurrent threads, 2048-token windows, 8B bf16 over tp=8
        plan = plan_memory(
            get_config("llama-3-8b"), tp=8, num_pages=256 * 128 + 1,
            page_size=16, max_pages_per_seq=128, max_batch=64,
            prefill_bucket=2048,
        )
        assert plan.fits
        assert plan.max_concurrent_windows >= 256

    def test_config5_70b_tp16_sp4_v5p64_fits(self):
        scfg = ServingConfig.profile_32k()
        plan = plan_for_serving(scfg, chip="v5p")
        assert plan.fits
        # degree 16 over 8 kv heads -> grouped layout (tp=8 x tq=2): the
        # pool shards 8-ways, each head on 2 chips — partially replicated
        assert plan.kv_replicated
        assert "tp=8 x tq=2" in plan.notes
        # grouped sharding holds 61 concurrent full 32k windows in leftover
        # HBM (the fully-replicated fallback held 7)
        assert plan.max_concurrent_windows >= 61
        # per-device weights ~10.2 GiB: 140 GB of bf16 across tp=16 with
        # replicated embed; kv projections now 8-way sharded
        assert 9 * GiB < plan.weight_bytes < 12 * GiB

    def test_ulysses_config_charges_full_replication(self):
        """cp_strategy='ulysses' keeps the plain tensor axis (the engine
        rejects tq>1 with the all_to_all head scatter), so the plan must
        charge FULL kv replication — not the grouped layout the server
        would build for ring CP.  Plan and placement resolve through the
        same resolve_tensor_axes call (parallel/mesh.py)."""
        import dataclasses

        ring = ServingConfig.profile_32k()
        uly = dataclasses.replace(ring, cp_strategy="ulysses")
        ring_plan = plan_for_serving(ring, chip="v5p")
        uly_plan = plan_for_serving(uly, chip="v5p")
        # 70B, 8 kv heads, degree 16: grouped shards kv 8-ways
        assert uly_plan.kv_bytes_per_token == 8 * ring_plan.kv_bytes_per_token
        assert "plain tensor axis" in uly_plan.notes

    def test_config5_would_not_fit_on_v5e(self):
        scfg = ServingConfig.profile_32k()
        assert not plan_for_serving(scfg, chip="v5e").fits

    def test_int8_kv_doubles_32k_capacity(self):
        cfg = get_config("llama-3-70b")
        kw = dict(tp=16, sp=4, num_pages=8193, page_size=16,
                  max_pages_per_seq=2048, max_batch=4, prefill_bucket=4096,
                  chip="v5p")
        bf16 = plan_memory(cfg, **kw)
        int8 = plan_memory(cfg, kv_dtype="int8", **kw)
        assert int8.max_concurrent_windows >= 2 * bf16.max_concurrent_windows


class TestServingIntegration:
    def test_plan_for_serving_default_config(self):
        plan = plan_for_serving(ServingConfig())
        assert plan.fits
        assert plan.model == "llama-3.2-1b"

    def test_health_reports_plan(self):
        # summary() is JSON-serializable (health endpoint payload)
        import json

        s = plan_for_serving(ServingConfig()).summary()
        json.dumps(s)
        assert {"fits", "weight_gib", "max_concurrent_windows"} <= set(s)


class TestMachineReadableFactorization:
    """MemoryPlan.kv_shard/tq: the grouped tp×tq layout as fields, not
    free-text notes (ADVICE r5).  Invariant: tp == kv_shard * tq."""

    def test_grouped_layout_fields(self):
        scfg = ServingConfig.profile_32k()  # degree 16 over 8 kv heads
        plan = plan_for_serving(scfg, chip="v5p")
        assert plan.kv_shard == 8 and plan.tq == 2
        assert plan.mesh["tp"] * 1 == plan.kv_shard * plan.tq * 1
        assert plan.summary()["kv_shard"] == 8
        assert plan.summary()["tq"] == 2

    def test_full_replication_reports_tq_equal_tp(self):
        # a degree sharing no factor with Hkv: kv fully replicated, so
        # tq must equal the whole degree (tp = kv_shard * tq holds)
        plan = plan_memory(
            get_config("llama-3-70b"), tp=3, num_pages=64, page_size=16,
            max_pages_per_seq=16, max_batch=4,
        )
        assert plan.kv_shard == 1 and plan.tq == 3

    def test_unsharded_plan_is_identity(self):
        plan = plan_memory(
            get_config("llama-3.2-1b"), num_pages=64, page_size=16,
            max_pages_per_seq=16, max_batch=4,
        )
        assert plan.kv_shard == 1 and plan.tq == 1


# ----------------------------------------------------------------------
# the planner asks the tree (ISSUE 58): `weight_bytes_per_device` is
# `param_bytes` of the abstract tree `init_params` builds, no formula of its
# own.  The numbers below were taken AT THE PARENT (f31dccb, whose leaf-by-leaf
# formulas they are), so a change of a tree shows here as a changed plan.
# ----------------------------------------------------------------------

CONFIG_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "configs")
PARENT_WEIGHT_BYTES = {
    "dots3-note-prev": 10_022_188_544,
    "falcon-h1-34b": 6_690_159_232,
    "granite-4.0-h-small": 9_514_430_464,
    "k-exaone-236b-a23b": 8_935_605_760,
    "kanana-2-30b-a3b": 7_579_169_280,
    "lfm2-8b-a1b": 9_334_155_520,
    "mellum2-12b-a2.5b": 7_589_933_568,
    "mixtral-8x7b": 6_329_376_768,
    # (PR 60: 7 layers hold experts, stacked per kind; no parent to compare)
    "nemotron-3-nano-30b-a3b": 10_565_072_896,
    # (PR 66: 12 linear + 4 full layers, dense, the whole vocabulary; no
    # parent to compare)
    "olmo-hybrid-7b": 8_201_579_328,
    "phi-4-mini-flash-reasoning": 7_707_253_760,
    "solar-open2-250b": 7_797_691_392,
    "xing4.0-29b-a4b": 11_332_171_968,
    "yi-1.5-9b": 7_969_513_472,
    "yi-1.5-9b-dp4": 7_969_513_472,
}
# (preset, mesh) -> the parent's sharded arithmetic, which dividing each
# leaf by the axes parallel/sharding.param_specs names reproduces
PARENT_SHARDED_BYTES = {
    ("llama-3-8b", (("tp", 8),)): 2_927_370_240,
    ("llama-3-8b", (("tp", 4), ("pp", 2))): 3_058_442_240,
    ("llama-3-70b", (("tp", 16),)): 10_959_470_592,
    ("llama-3-70b", (("tp", 8), ("pp", 4))): 6_642_876_416,
    ("llama-3-70b", (("tp", 8), ("kv_shard", 1))): 21_828_222_976,
    ("llama-3.2-1b", (("tp", 2), ("pp", 2))): 1_011_945_472,
    ("mixtral-8x7b", (("ep", 8),)): 14_485_561_344,
    ("mixtral-8x7b", (("ep", 8), ("tp", 4))): 3_819_970_560,
    ("mixtral-8x7b", (("ep", 3), ("tp", 2))): 46_835_179_520,
}


@pytest.mark.parametrize("name", sorted(PARENT_WEIGHT_BYTES))
def test_a_configurations_weights_are_what_the_parent_planned(name):
    cfg = config_from_hf_json(os.path.join(CONFIG_DIR, name + ".json"))
    assert weight_bytes_per_device(cfg) == PARENT_WEIGHT_BYTES[name]


def test_every_configuration_file_has_its_weights_pinned():
    files = {os.path.basename(p)[:-len(".json")]
             for p in glob.glob(os.path.join(CONFIG_DIR, "*.json"))}
    assert files == set(PARENT_WEIGHT_BYTES)


@pytest.mark.parametrize("preset, mesh", sorted(PARENT_SHARDED_BYTES))
def test_a_mesh_divides_each_leaf_by_its_specs_axes(preset, mesh):
    assert weight_bytes_per_device(
        get_config(preset), **dict(mesh)) == PARENT_SHARDED_BYTES[preset, mesh]


@pytest.mark.parametrize("preset", ["tiny-gqa", "tiny-moe"])
def test_an_int8_plan_counts_the_quantized_tree(preset):
    """Every scale `quantize_params` really makes (the parent's formula
    missed some: 512 B on each of these two)."""
    cfg = get_config(preset)
    held = param_bytes(quantize_params(
        init_params(cfg, jax.random.PRNGKey(0)), cfg))
    assert weight_bytes_per_device(cfg, quantize="int8") == held
    assert weight_bytes_per_device(cfg) == param_bytes(
        init_params(cfg, jax.random.PRNGKey(0)))
