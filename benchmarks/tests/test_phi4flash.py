"""The Phi-4-mini-flash-reasoning configuration's own files (PR 38):
`references/phi4flash.py` against `kafka_tpu.models.forward` at the tiny
twin's size in float32 (the layout, Mamba, differential attention, the gated
memory units and the cross layers), the paged path through pages AND state
slots with the configuration's driver, the POWER of the check (its `variants`
must move the logits), the scan's byte count, the five readers the cell adds
on synthetic input, and the CPU rehearsal of the tiny twin under
`benchmarks/tests/phi4flash/`.  (`test_check_resolution.py` scans every file
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

import kernel_calls  # noqa: E402
import named  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import ssm_roofline  # noqa: E402
import window_roofline  # noqa: E402
from kafka_tpu.models import forward, init_params  # noqa: E402
from kafka_tpu.models.config import config_from_hf_json  # noqa: E402

TWIN = os.path.join(HERE, "phi4flash")
CELL = "phi-4-mini-flash-reasoning.reason-decode"
phi = named.load((BENCH,), "references", "phi4flash")
driver = named.load((BENCH,), "drivers", "phi4flash_pool")
IDS = np.random.RandomState(0).randint(0, 512, size=123)  # ~8 windows of 16


@pytest.fixture(scope="module")
def tiny():
    cfg = config_from_hf_json(
        os.path.join(TWIN, "configs", "tiny-phi4flash.json"))
    return cfg, init_params(cfg, jax.random.PRNGKey(1))


def rel_rms(a, b):
    return float(np.sqrt(np.mean((a - b) ** 2)) / np.sqrt(np.mean(b ** 2)))


def test_reference_matches_program_forward(tiny):
    cfg, params = tiny
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, t: forward(
            p, cfg, t, jnp.arange(t.shape[1])[None]))(
                params, jnp.asarray(IDS)[None])
    ref = phi.reference_logits(params, phi.hyper(cfg), IDS,
                               list(range(len(IDS))))
    np.testing.assert_allclose(np.asarray(logits[0]), ref["logits"],
                               rtol=2e-4, atol=2e-4)
    assert np.isinf(ref["router_gap"]).all()  # no router: all compared


def test_pages_and_state_match_reference_past_the_window(tiny):
    cfg, params = tiny
    n_prefill = 112  # launches of 64 and 48 rows, both padded
    with jax.default_matmul_precision("highest"):
        served = driver.served_logits(params, cfg, IDS, n_prefill,
                                      page_size=16, pages_per_seq=8)
    pos = list(range(n_prefill - 1, len(IDS)))
    ref = phi.reference_logits(params, phi.hyper(cfg), IDS, pos)
    res = reference.compare_logits(served, ref["logits"], ref["router_gap"],
                                   tol=1e-4)
    assert res["ok"] and res["compared"] == len(pos), res


def test_the_check_sees_the_precision_the_state_is_carried_in(
        tiny, monkeypatch, capfd):
    """The logits cannot show a bfloat16 state at the published widths, so
    the driver reads the slot: float32 leaves whose h needs float32."""
    from kafka_tpu.runtime import kv_cache

    cfg, params = tiny
    driver.served_logits(params, cfg, IDS, 112, page_size=16, pages_per_seq=8)
    said = capfd.readouterr().err
    assert float(said.split("state_f32_share ")[1].split()[0]) > 0.99
    _, pool = kv_cache.make_kv_pool_arrays(cfg, 3, 16, state_slots=3)
    h = jax.random.normal(jax.random.PRNGKey(1), pool["ssm"].shape)
    assert driver.state_f32_share(dict(pool, ssm=h)) > 0.99
    assert driver.state_f32_share(pool) == 0.0  # nothing written: all zero
    rounded = h.astype(jnp.bfloat16)
    assert driver.state_f32_share(
        dict(pool, ssm=rounded.astype(jnp.float32))) == 0.0
    assert driver.state_f32_share(dict(pool, ssm=rounded)) == 0.0
    # were the program to round the state on its way into the slot, the
    # check fails by name (another pool size: a trace of its own)
    from kafka_tpu.models import hybrid

    real = hybrid._write_state
    monkeypatch.setattr(
        hybrid, "_write_state",
        lambda leaf, layer, plan, new, old: real(
            leaf, layer, plan, jax.lax.reduce_precision(new, 8, 7), old))
    with pytest.raises(driver.StatePrecisionError):
        driver.served_logits(params, cfg, IDS, 112, page_size=16,
                             pages_per_seq=9)


def test_the_check_has_power(tiny):
    """Were the program to drop D x, skip the sub-layer norm or zero the
    state where two prefill launches meet, the logits at the compared
    positions move by more than the tolerance; a state rounded to bfloat16
    moves them too, by less (tests/test_hybrid_model.py holds that one)."""
    cfg, params = tiny
    hp = phi.hyper(cfg)
    pos = list(range(111, 123))
    ref = phi.reference_logits(params, hp, IDS, pos)["logits"]
    moved = {}
    for name, variant in phi.variants(hp).items():
        if "zero_state_at" in variant:
            variant = dict(variant, zero_state_at=112 - phi.TAIL)
        got = phi.reference_logits(params, variant, IDS, pos)["logits"]
        moved[name] = max(rel_rms(g, r) for g, r in zip(got, ref))
    assert set(moved) == {"bf16_state", "drop_dx", "no_subln",
                          "zero_state_at_boundary"}
    for name in ("drop_dx", "no_subln", "zero_state_at_boundary"):
        assert moved[name] > 2 * phi.TOLERANCE["value"], (name, moved)
    assert moved["bf16_state"] > 1e-4


def test_hyper_refuses_a_model_that_is_not_phi4flash_shaped(tiny):
    cfg, _ = tiny
    with pytest.raises(ValueError):
        phi.hyper(types.SimpleNamespace(
            layer_types=("full_attention",), tie_word_embeddings=True))
    with pytest.raises(ValueError):
        phi.hyper(types.SimpleNamespace(
            layer_types=cfg.layer_types, tie_word_embeddings=False))


# --------------------------------------------------------------------------
# the byte count and the readers
# --------------------------------------------------------------------------

def test_scan_bytes_and_exponentials():
    exps, nbytes = ssm_roofline.scan_call(1, 2048, 5120, 16)
    assert exps == 2048 * 16 * 5120
    # x, dt, y: 3 x 2048 x 5120 f32; B, C; the state in and out; A and D
    assert nbytes == 4 * (3 * 2048 * 5120 + 2 * 2048 * 16 + 2 * 16 * 5120
                          + 16 * 5120 + 5120)
    # the state does not move with the rows: twice the rows, not twice it
    _, twice = ssm_roofline.scan_call(1, 4096, 5120, 16)
    assert twice - nbytes == 4 * 2048 * (3 * 5120 + 2 * 16)


def reader(name):
    return named.load((BENCH,), "layer_metrics", name)


def test_ssm_scan_roofline_reads_the_calls_shapes():
    mod = reader("ssm_scan_roofline")
    text = ("%selective_scan.3 = (f32[1,2048,5120]{2,1,0}, f32[1,16,5120]"
            "{2,1,0}) custom-call(f32[1,2048,5120]{2,1,0} %x, "
            "f32[1,2048,5120]{2,1,0} %dt, f32[16,5120]{1,0} %a, "
            "f32[1,2048,16,128]{3,2,1,0} %b), "
            "custom_call_target=\"tpu_custom_call\"")
    assert mod.call_shape(text) == (1, 2048, 5120, 16)
    assert mod.call_shape("%selective_scan.3 = custom-call(f32[8] %x)") is None
    _, nbytes = ssm_roofline.scan_call(1, 2048, 5120, 16)
    least = nbytes / 819e9
    other = "%paged_decode_attention.1 = bf16[32,40,1280]{2,1,0} custom-call()"
    ctx = {"kernel_events": [(text, 4 * least)] * 9 + [(other, 1.0)],
           "info": {"kind": "TPU v5 lite"}}
    assert mod.read(ctx) == pytest.approx(25.0)
    # the parent's capture holds no such call
    assert mod.read({"kernel_events": [(other, 1.0)]}) is None
    assert mod.read({"trace": None, "cell": types.SimpleNamespace(
        name="none")}) is None


def synthetic_ctx(**kw):
    """A window of 29 busy lanes at a mean context of 8,500 keys."""
    config = {"num_attention_heads": 40, "num_key_value_heads": 20,
              "hidden_size": 2560, "num_hidden_layers": 32,
              "sliding_window": 512,
              "serving": {"page_size": 16, "max_batch": 32}}
    ctx = {"cell": types.SimpleNamespace(config=config, name="synthetic"),
           "info": {"kind": "TPU v5 lite"},
           "after": {"decode": {"steps": 100, "batch_occupancy": 29.0}},
           "before": {"decode": {"steps": 0, "batch_occupancy": 0.0}},
           "log": [{"error": None, "in_window": True, "done": True,
                    "usage": {"prompt_tokens": 8100,
                              "completion_tokens": 800}}]}
    return dict(ctx, **kw)


def test_cross_attn_roofline_counts_the_decode_programs_calls():
    def text(i, lanes):
        return (f"%paged_decode_attention.{i} = bf16[{lanes},40,1280]"
                "{2,1,0} custom-call(s32[32,1024]{1,0} %pt, "
                "bf16[32,40,1280]{2,1,0} %q), "
                "custom_call_target=\"tpu_custom_call\"")
    _, nbytes = roofline.paged_decode([8500], 40, 20, 64, 16)
    least = nbytes * 29.0 / 819e9  # one call at decode's occupancy
    # eight calls a decode pass; a prefill launch's calls, 1 and 4 lanes
    # wide, are neither counted nor timed
    calls = ([(text(1, 32), 2 * least)] * 8
             + [(text(2, 1), 50 * least), (text(3, 4), 50 * least)])
    read = reader("cross_attn_roofline").read
    window = ("%paged_decode_attention_window.7 = bf16[32,40,1280]{2,1,0} "
              "custom-call()", 9.0)  # another kernel's call
    ctx = synthetic_ctx(kernel_events=calls + [window])
    assert read(ctx) == pytest.approx(50.0)
    assert kernel_calls.result_lanes("%fusion.1 = custom-call()") is None
    # the parent's capture holds no such call; a run without a capture
    assert read(synthetic_ctx(kernel_events=[window])) is None
    assert read(synthetic_ctx(trace=None)) is None


def test_diff_window_attn_roofline_on_a_synthetic_capture():
    calls = 800
    _, nbytes = window_roofline.windowed_decode([8500], 512, 40, 20, 64, 16)
    least = nbytes * calls * 29.0 / 819e9
    trace = {"op_self_s": {"paged_decode_attention_window": 4 * least,
                           "paged_decode_attention": 123.0},
             "op_count": {"paged_decode_attention_window": calls,
                          "paged_decode_attention": 900}}
    read = reader("diff_window_attn_roofline").read
    assert read(synthetic_ctx(trace=trace)) == pytest.approx(25.0)
    trace["op_self_s"].pop("paged_decode_attention_window")
    assert read(synthetic_ctx(trace=trace)) is None
    assert read(synthetic_ctx(trace=None)) is None


def test_scope_share_readers_read_their_scopes_or_nothing():
    acc = {"scoped": True, "unnamed_programs": [], "busy_s": 8.0,
           "by_component": {"ssm_proj": 1.0, "ssm_conv": 0.2, "ssm_scan": 0.4,
                            "attn_cross": 2.0, "attn_window": 0.4,
                            "mlp": 4.0}}
    assert reader("dev_ssm_share").read({"scope_account": acc}) \
        == pytest.approx(20.0)
    assert reader("dev_cross_attn_share").read({"scope_account": acc}) \
        == pytest.approx(25.0)
    assert reader("dev_diff_window_attn_share").read(
        {"scope_account": acc}) == pytest.approx(5.0)
    bare = {"scoped": True, "unnamed_programs": [], "busy_s": 8.0,
            "by_component": {"mlp": 8.0}}  # the parent names no such scope
    for name in ("dev_ssm_share", "dev_cross_attn_share",
                 "dev_diff_window_attn_share"):
        assert reader(name).read({"scope_account": bare}) is None
        assert reader(name).read({"scope_account": None}) is None


def test_state_readers_read_the_section_or_nothing():
    before = {"state": {"state_tokens_matched": 1000,
                        "state_tokens_skipped": 400}}
    after = {"state": {"state_tokens_matched": 9000,
                       "state_tokens_skipped": 8000, "state_slots_total": 129,
                       "state_slots_live": 43}}
    ctx = {"before": before, "after": after}
    assert reader("state_restore_share").read(ctx) == pytest.approx(95.0)
    assert reader("state_slots_used_share").read(ctx) \
        == pytest.approx(100 * 43 / 129)
    parent = {"before": {"engine": {}}, "after": {"engine": {}}}
    assert reader("state_restore_share").read(parent) is None
    assert reader("state_slots_used_share").read(parent) is None


# --------------------------------------------------------------------------
# the tiny twin, end to end
# --------------------------------------------------------------------------

def test_the_twin_lists_what_the_real_cell_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TWIN, "BENCHMARK.json")) as f:
        twin = json.load(f)
    want = {m["name"] for m in real["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    assert {m["name"] for m in twin["per_layer"]} == want
    new = {"dev_ssm_share", "dev_cross_attn_share", "ssm_scan_roofline",
           "state_restore_share", "state_slots_used_share",
           "cross_attn_roofline", "diff_window_attn_roofline",
           "dev_diff_window_attn_share"}
    assert new <= want
    assert all(m["workloads"] == [CELL] for m in real["per_layer"]
               if m["name"] in new)
    with open(os.path.join(
            BENCH, "configs", "phi-4-mini-flash-reasoning.json")) as f:
        config = json.load(f)
    assert config["reduced"] == {} and config["num_hidden_layers"] == 32
    assert set(config["scopes"]) == {
        "attn_window", "ssm_proj", "ssm_conv", "ssm_scan", "gmu",
        "attn_cross", "attn_diff"}
    check = config["check"]
    assert check["n_prefill"] >= 1536 > config["sliding_window"]
    assert check["n_decode"] >= 47 and check["pages_per_seq"] >= 100
    assert (check["reference"], check["driver"]) == (
        "phi4flash", "phi4flash_pool")
    cell = next(w for w in real["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "reason-decode")
    assert len(real["configs"]) == 6 and len(real["workloads"]) == 6
    assert all(w["chips"] == 1 for w in real["workloads"])


def test_rehearsal_of_the_tiny_twin():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", "tiny-phi4flash.reason-decode", "--seed", "3000000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"state_restore_share", "state_slots_used_share",
            "prefix_hit_share", "decode_batch_occupancy"} \
        <= set(line["metrics"])
    assert line["metrics"]["state_restore_share"]["value"] >= 95.0
    # device metrics never come from a CPU run
    assert not {"ssm_scan_roofline", "dev_ssm_share", "dev_cross_attn_share",
                "cross_attn_roofline", "diff_window_attn_roofline",
                "dev_diff_window_attn_share", "decode_step_dev_ms"} \
        & set(line["metrics"])
    check = json.loads(next(
        ln for ln in lines if ln.startswith("run.py: logit check ")
    )[len("run.py: logit check "):])
    assert check["ok"] and check["reference"] == "references/phi4flash"
    assert check["driver"] == "drivers/phi4flash_pool"
    assert check["compared"] == 12
