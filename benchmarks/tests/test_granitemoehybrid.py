"""The granite-4.0-h-small configuration's own files (PR 63): the readers the
cell adds on synthetic input (six wrap an accepted metric's reader through
`named.load`; `moe_pairs_per_expert` is new and reads the program's new
counters or nothing), `ssd_roofline.py`'s two counts at 128 heads of 64 x 128
in ONE group, the reference against `kafka_tpu.models.forward` at the tiny
size, what the tiny twin lists against the real cell, and the CPU rehearsal of
the twin under `benchmarks/tests/granitemoehybrid/`.  (`test_check_resolution.py`
scans every file under `references/` for imports of the program; the paged
path through pages and state slots with the configuration's driver, the
kernels at the published geometry and the reference's `variants` are held in
`tests/test_granite_moe_hybrid.py`, tier-1.)"""

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

import named  # noqa: E402

TWIN = os.path.join(HERE, "granitemoehybrid")
CELL = "granite-4.0-h-small.chat-decode"
NEW = ["dev_ssd_g1_share", "ssd_g1_step_roofline", "ssd_g1_chunk_roofline",
       "gqa4_attn_roofline", "ep2_top10_experts_read_share",
       "ssd_g1_state_restore_share", "moe_pairs_per_expert"]
granite = named.load((BENCH,), "references", "granitemoehybrid")
driver = named.load((BENCH,), "drivers", "granitemoehybrid_pool")


def reader(name):
    return named.load((BENCH,), "layer_metrics", name)


def test_the_scope_and_counter_readers_read_their_source_or_nothing():
    acc = {"scoped": True, "unnamed_programs": [], "busy_s": 8.0,
           "by_component": {"ssd_proj": 0.6, "ssd_conv": 0.2, "ssd_gate": 0.2,
                            "ssd_scan": 1.0, "moe_experts": 3.0}}
    assert reader("dev_ssd_g1_share").read({"scope_account": acc}) \
        == pytest.approx(25.0)
    bare = dict(acc, by_component={"mlp": 8.0})  # the parent: no such scope
    assert reader("dev_ssd_g1_share").read({"scope_account": bare}) is None
    engine = {"moe_experts_read": 100, "moe_experts_held": 200,
              "moe_picks_held": 50, "moe_picks_routed": 90}
    after = {"moe_experts_read": 100 + 909, "moe_experts_held": 200 + 1000,
             "moe_picks_held": 50 + 2182, "moe_picks_routed": 90 + 4400}
    ctx = {"before": {"engine": engine, "state": {
               "state_tokens_matched": 10, "state_tokens_skipped": 10}},
           "after": {"engine": after, "state": {
               "state_tokens_matched": 10 + 7424 * 5,
               "state_tokens_skipped": 10 + 7424 * 5}}}
    assert reader("ep2_top10_experts_read_share").read(ctx) \
        == pytest.approx(90.9)
    assert reader("moe_pairs_per_expert").read(ctx) \
        == pytest.approx(2182 / 909)
    assert reader("ssd_g1_state_restore_share").read(ctx) \
        == pytest.approx(100.0)
    parent = {"before": {"engine": {}}, "after": {"engine": {}}}
    for name in ("ep2_top10_experts_read_share", "moe_pairs_per_expert",
                 "ssd_g1_state_restore_share"):
        assert reader(name).read(parent) is None


def test_the_kernel_readers_count_from_each_calls_own_shapes():
    import kernel_calls
    import ssd_roofline

    heads, P, G, N, lanes = 128, 64, 1, 128, 16
    step = ("%ssd_step.7 = (f32[16,1,8192]{2,1,0}, "
            "f32[9,65,8192,128]{3,2,1,0}) custom-call(s32[1]{0} %l, "
            "s32[16]{0} %s, f32[16,1,8192]{2,1,0} %x, f32[16,1,128]{2,1,0} "
            "%b, f32[16,1,128]{2,1,0} %c, f32[16,2,1,64]{3,2,1,0} %g, "
            "f32[9,65,8192,128]{3,2,1,0} %leaf)")
    assert ssd_roofline._sizes(kernel_calls.shapes(step, "operands")) == (
        lanes, 1, heads, P, G, N)
    nbytes = 4 * lanes * (2 * heads * P * N + 2 * heads * P + 2 * G * N
                          + heads)
    least = nbytes / 819e9
    ctx = {"cell": types.SimpleNamespace(name="synthetic"),
           "info": {"kind": "TPU v5 lite"}, "trace": {},
           "kernel_events": [(step, 2 * least), (step, 2 * least)]}
    assert reader("ssd_g1_step_roofline").read(ctx) == pytest.approx(50.0)
    assert reader("ssd_g1_chunk_roofline").read(ctx) is None  # no such call
    chunk = ("%ssd_chunk.2 = (f32[1,512,8192]{2,1,0}, "
             "f32[9,65,8192,128]{3,2,1,0}) custom-call(s32[1]{0} %l, "
             "s32[1]{0} %a, s32[1]{0} %b, s32[1]{0} %c, s32[1]{0} %f, "
             "f32[1,512,8192]{2,1,0} %x, f32[1,512,128]{2,1,0} %bm, "
             "f32[1,512,128]{2,1,0} %cm, f32[1,2,512,64]{3,2,1,0} %g, "
             "f32[9,65,8192,128]{3,2,1,0} %leaf)")
    flops, moved = ssd_roofline.chunk_call(
        kernel_calls.shapes(chunk, "operands"))
    assert moved == 4 * (512 * (2 * heads * P + 2 * G * N + heads)
                         + 3 * heads * P * N)
    # C B^T ONCE a group (the kernel takes it once a grid step, twice here)
    assert flops == 4 * 2 * (G * 128 * 128 * N + heads * (
        128 * 128 * P + 2 * 128 * P * N))
    least = max(moved / 819e9, flops / 197e12)
    ctx["kernel_events"] = [(chunk, 4 * least)]
    assert reader("ssd_g1_chunk_roofline").read(ctx) == pytest.approx(25.0)
    assert reader("ssd_g1_step_roofline").read(ctx) is None
    ctx["kernel_events"] = None  # no capture
    assert reader("ssd_g1_chunk_roofline").read(ctx) is None


def test_the_reference_holds_forward_at_the_tiny_size():
    import jax
    import jax.numpy as jnp

    from kafka_tpu.models import forward, init_params
    from kafka_tpu.models.config import config_from_hf_json

    cfg = config_from_hf_json(os.path.join(
        TWIN, "configs", "tiny-granitemoehybrid.json"))
    assert cfg.layer_types == ("mamba2", "full_attention", "mamba2", "mamba2")
    assert cfg.mixer_then_ffn and cfg.num_router_experts == 16
    params = init_params(cfg, jax.random.PRNGKey(1))
    ids = np.random.RandomState(2).randint(0, cfg.vocab_size, 48)
    want = granite.reference_logits(params, granite.hyper(cfg), ids,
                                    list(range(40, 48)))
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, jnp.asarray(ids)[None],
                         jnp.arange(48)[None])
    err = np.sqrt(np.mean((np.asarray(got[0, 40:]) - want["logits"]) ** 2,
                          -1) / np.mean(want["logits"] ** 2, -1))
    assert err.max() < 1e-4


def test_the_twin_lists_what_the_real_cell_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TWIN, "BENCHMARK.json")) as f:
        twin = json.load(f)
    want = {m["name"] for m in real["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    assert {m["name"] for m in twin["per_layer"]} == want
    assert set(NEW) <= want
    listed = [m for m in real["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in listed] == NEW
    assert all(m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
               for m in listed)
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-small.json")) as f:
        config = json.load(f)
    assert list(config["reduced"]) == ["num_hidden_layers",
                                       "num_local_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_local_experts"],
            config["vocab_size"]) == (10, 36, 50176)
    assert config["serving"]["max_batch"] == 16
    assert config["serving"]["num_pages"] == 8192
    assert config["serving"]["prefill_buckets"] == [128, 256, 512]
    assert config["expect"]["attention_backend"] == "pallas"
    check = config["check"]
    assert (check["reference"], check["driver"]) == (
        "granitemoehybrid", "granitemoehybrid_pool")
    assert (check["n_prefill"], check["n_decode"]) == (1536, 47)
    assert granite.RUN_IN == driver.RUN_IN
    assert (check["n_prefill"] - granite.RUN_IN) \
        % config["serving"]["page_size"] == 0
    cell = next(w for w in real["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "chat-decode")
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        assert json.load(f)["params"] == {}


def test_rehearsal_of_the_tiny_twin():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", "tiny-granitemoehybrid.chat-decode", "--seed",
         "3000000019", "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"ep2_top10_experts_read_share", "ssd_g1_state_restore_share",
            "moe_pairs_per_expert", "prefix_hit_share",
            "decode_batch_occupancy"} <= set(line["metrics"])
    assert 0 < line["metrics"]["ep2_top10_experts_read_share"]["value"] <= 100
    assert line["metrics"]["moe_pairs_per_expert"]["value"] > 0
    # device metrics never come from a CPU run
    assert not {"dev_ssd_g1_share", "ssd_g1_step_roofline",
                "ssd_g1_chunk_roofline", "gqa4_attn_roofline",
                "decode_step_dev_ms"} & set(line["metrics"])
    check = json.loads(next(
        ln for ln in lines if ln.startswith("run.py: logit check ")
    )[len("run.py: logit check "):])
    assert check["ok"]
    assert check["reference"] == "references/granitemoehybrid"
    assert check["driver"] == "drivers/granitemoehybrid_pool"
