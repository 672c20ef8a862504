"""The Mellum2 configuration's own files (PR 27): `references/mellum2.py`
against `kafka_tpu.models.forward` at a tiny patterned size in float32 (the
pattern, the sliding mask, both ropes, the routed block), what it reports
about router ties, the POWER of the check (its `variants` must move the
logits), the three readers the cell adds on synthetic input, the byte count
of a windowed decode call, and the CPU rehearsal of the tiny twin under
`benchmarks/tests/mellum2/`.  (`test_check_resolution.py` scans every file
under `references/` for imports of the program, this one included.)"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import named  # noqa: E402
import paged_step  # noqa: E402
import reference  # noqa: E402
import window_roofline  # noqa: E402
from kafka_tpu.models import forward, init_params  # noqa: E402
from kafka_tpu.models.config import config_from_hf_json  # noqa: E402

TWIN = os.path.join(HERE, "mellum2")
mellum2 = named.load((BENCH,), "references", "mellum2")


@pytest.fixture(scope="module")
def tiny():
    cfg = config_from_hf_json(
        os.path.join(TWIN, "configs", "tiny-mellum2.json"))
    return cfg, init_params(cfg, jax.random.PRNGKey(1))


IDS = np.random.RandomState(0).randint(0, 512, size=72)  # 4.5 windows of 16


def test_reference_matches_program_forward(tiny):
    cfg, params = tiny
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, jnp.asarray(IDS)[None],
                            jnp.arange(len(IDS))[None])
    ref = mellum2.reference_logits(params, mellum2.hyper(cfg), IDS,
                                   list(range(len(IDS))))
    np.testing.assert_allclose(np.asarray(logits[0]), ref["logits"],
                               rtol=2e-4, atol=2e-4)
    # the reported gap is the raw one moved so that compare_logits' fixed
    # 0.05 lands on this reference's own margin
    raw = ref["raw_router_gap"]
    assert raw.shape == (len(IDS),) and (raw >= 0).all()
    np.testing.assert_allclose(
        ref["router_gap"],
        raw * reference.ROUTER_TIE_MARGIN / mellum2.ROUTER_FLIP_MARGIN,
        rtol=1e-6)
    assert mellum2.COMPARE_SKIPS_UNDER == reference.ROUTER_TIE_MARGIN


def test_paged_prefill_and_decode_match_reference_past_the_window(tiny):
    cfg, params = tiny
    n_prefill = 64  # four windows: every compared position is past it
    served = paged_step.served_logits(params, cfg, IDS, n_prefill,
                                      page_size=16, pages_per_seq=5)
    pos = list(range(n_prefill - 1, len(IDS)))
    ref = mellum2.reference_logits(params, mellum2.hyper(cfg), IDS, pos)
    res = reference.compare_logits(served, ref["logits"], ref["router_gap"],
                                   tol=1e-4)
    assert res["ok"] and res["compared"] >= reference.MIN_COMPARED, res


def rel_rms(a, b):
    return float(np.sqrt(np.mean((a - b) ** 2)) / np.sqrt(np.mean(b ** 2)))


def test_the_check_has_power_past_the_window(tiny):
    """Were the program to ignore the window, or to rotate the global layers
    with the default table, the logits at positions past the window move by
    far more than the tolerance; inside the first window the all-global
    variant IS the model."""
    cfg, params = tiny
    hp = mellum2.hyper(cfg)
    pos = list(range(60, 72))
    ref = mellum2.reference_logits(params, hp, IDS, pos)["logits"]
    for name, variant in mellum2.variants(hp).items():
        got = mellum2.reference_logits(params, variant, IDS, pos)["logits"]
        worst = min(rel_rms(g, r) for g, r in zip(got, ref))
        assert worst > 2 * mellum2.TOLERANCE["value"], (name, worst)
    early = list(range(0, 16))  # positions 0..15 see at most 16 keys
    a = mellum2.reference_logits(params, hp, IDS[:16], early)["logits"]
    b = mellum2.reference_logits(
        params, mellum2.variants(hp)["all_global"], IDS[:16], early)["logits"]
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_hyper_refuses_a_model_that_is_not_mellum_shaped(tiny):
    cfg, _ = tiny
    with pytest.raises(ValueError):
        mellum2.hyper(cfg.replace(num_experts=0))
    with pytest.raises(ValueError):
        mellum2.hyper(cfg.replace(rope_by_kind=()))


def test_yarn_table_of_the_published_parameters():
    inv, att = mellum2.rope_table(
        {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
         "original_max_position": 8192, "beta_fast": 32, "beta_slow": 1,
         "attention_factor": None}, 128)
    assert att == pytest.approx(1.2772588722239782, rel=1e-12)
    base = 1.0 / 500000.0 ** (np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(inv[:8], base[:8])          # extrapolated
    np.testing.assert_allclose(inv[-8:], base[-8:] / 16)   # interpolated
    assert (np.diff(inv) < 0).all()


# --------------------------------------------------------------------------
# the byte count and the three readers
# --------------------------------------------------------------------------

def test_windowed_decode_bytes_do_not_grow_with_the_context():
    shape = dict(num_heads=32, num_kv_heads=4, head_dim=128, page_size=16)
    f1, b1 = window_roofline.windowed_decode([1024], 1024, **shape)
    f8, b8 = window_roofline.windowed_decode([8300], 1024, **shape)
    assert (f1, b1) == (f8, b8)
    # 1024 keys x (K + V) x 4 x 128 x 2 B, plus q in and out
    assert b8 == 2 * 1024 * 512 * 2 + 2 * 32 * 128 * 2
    assert f8 == 4.0 * 1024 * 32 * 128
    # inside the first window it is the global call's need, in whole chunks
    _, small = window_roofline.windowed_decode([99], 1024, **shape)
    assert small == 2 * 128 * 512 * 2 + 2 * 32 * 128 * 2
    assert window_roofline.windowed_decode([0, 0], 0, **shape) == (0.0, 0.0)


def reader(name):
    return named.load((BENCH,), "layer_metrics", name).read


def cell(sliding_window=1024):
    config = {"num_attention_heads": 32, "num_key_value_heads": 4,
              "head_dim": 128, "hidden_size": 2304, "num_hidden_layers": 8,
              "sliding_window": sliding_window,
              "serving": {"page_size": 16}, "scopes": ["attn_window"]}
    return types.SimpleNamespace(config=config, name="synthetic")


def test_window_attn_roofline_on_a_synthetic_capture():
    lanes, calls = 16.0, 600
    _, nbytes = window_roofline.windowed_decode(
        [8300], 1024, 32, 4, 128, 16)
    least = nbytes * calls * lanes / 819e9
    decode = {"steps": 100, "batch_occupancy": lanes}
    ctx = {
        "cell": cell(), "info": {"kind": "TPU v5 lite"},
        "trace": {"op_self_s": {
            "paged_decode_attention_window.1 bf16[16,32,512]": 2 * least,
            "paged_decode_attention.1 bf16[16,32,512]": 123.0},
            "op_count": {"paged_decode_attention_window": calls,
                         "paged_decode_attention": 200}},
        "after": {"decode": decode},
        "before": {"decode": {"steps": 0, "batch_occupancy": 0.0}},
        "log": [{"error": None, "in_window": True, "done": True,
                 "usage": {"prompt_tokens": 8100, "completion_tokens": 400}}],
    }
    assert reader("window_attn_roofline")(ctx) == pytest.approx(50.0)
    # the parent's capture holds no such kernel; a config without a window
    ctx["trace"]["op_self_s"].pop(
        "paged_decode_attention_window.1 bf16[16,32,512]")
    assert reader("window_attn_roofline")(ctx) is None
    assert reader("window_attn_roofline")(
        dict(ctx, cell=cell(None), trace=None)) is None


def test_dev_window_attn_share_reads_the_scope_or_nothing():
    acc = {"scoped": True, "unnamed_programs": [], "busy_s": 4.0,
           "by_component": {"attn_window": 0.5, "attn_core": 1.0,
                            "moe_experts": 2.5}}
    read = reader("dev_window_attn_share")
    assert read({"scope_account": acc}) == pytest.approx(12.5)
    acc["by_component"].pop("attn_window")  # the parent names no such scope
    assert read({"scope_account": acc}) is None
    assert read({"scope_account": None}) is None


def test_kv_window_dead_share_reads_the_counter_or_nothing():
    read = reader("kv_window_dead_share")
    assert read({"after": {"engine": {"kv_window_dead_share": 0.65}}}) \
        == pytest.approx(65.0)
    assert read({"after": {"replicas": [
        {"engine": {"kv_window_dead_share": 0.2}},
        {"engine": {"kv_window_dead_share": 0.4}}]}}) == pytest.approx(40.0)
    assert read({"after": {"engine": {"pages_total": 9}}}) is None  # parent


# --------------------------------------------------------------------------
# the tiny twin, end to end
# --------------------------------------------------------------------------

def test_the_twin_lists_what_the_real_cell_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TWIN, "BENCHMARK.json")) as f:
        twin = json.load(f)
    cell_name = "mellum2-12b-a2.5b.chat-decode"
    want = {m["name"] for m in real["per_layer"]
            if "workloads" not in m or cell_name in m["workloads"]}
    assert {m["name"] for m in twin["per_layer"]} == want
    assert {"window_attn_roofline", "dev_window_attn_share",
            "kv_window_dead_share"} <= want
    with open(os.path.join(BENCH, "configs", "mellum2-12b-a2.5b.json")) as f:
        config = json.load(f)
    assert config["scopes"] == ["attn_window"]
    assert config["check"]["n_prefill"] >= 1536 > config["sliding_window"]


def test_rehearsal_of_the_tiny_twin():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", "tiny-mellum2.chat-decode", "--seed", "3000000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"kv_window_dead_share", "prefix_hit_share",
            "decode_batch_occupancy"} <= set(line["metrics"])
    # device metrics never come from a CPU run
    assert not {"window_attn_roofline", "dev_window_attn_share",
                "decode_step_dev_ms"} & set(line["metrics"])
    check = json.loads(next(
        ln for ln in lines if ln.startswith("run.py: logit check ")
    )[len("run.py: logit check "):])
    assert check["ok"] and check["reference"] == "references/mellum2"
    assert check["compared"] >= 3
