"""The Nemotron-3-Nano-30B-A3B configuration's own files (PR 60): the readers
the cell adds on synthetic input (each reads its source or nothing: all five
wrap an accepted metric's reader through `named.load`), `ssd_roofline.py`'s
two counts at heads of 64 x 128, the reference against
`kafka_tpu.models.forward` at the tiny size, what the tiny twin lists against
the real cell, and the CPU rehearsal of the twin under
`benchmarks/tests/nemotronh/`.  (`test_check_resolution.py` scans every file
under `references/` for imports of the program; the paged path through pages
and state slots with the configuration's driver, the kernels at the tile and
the reference's `variants` are held in `tests/test_nemotron_h.py`, tier-1.)"""

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

TWIN = os.path.join(HERE, "nemotronh")
CELL = "nemotron-3-nano-30b-a3b.chat-decode"
NEW = {"dev_lone_ssd_share", "ssd64_step_roofline", "ssd64_chunk_roofline",
       "gqa16_attn_roofline", "ep2_experts_read_share"}
nemotron = named.load((BENCH,), "references", "nemotronh")
driver = named.load((BENCH,), "drivers", "nemotronh_pool")


def reader(name):
    return named.load((BENCH,), "layer_metrics", name)


def test_the_scope_and_counter_readers_read_their_source_or_nothing():
    acc = {"scoped": True, "unnamed_programs": [], "busy_s": 8.0,
           "by_component": {"ssd_proj": 0.6, "ssd_conv": 0.2, "ssd_gate": 0.2,
                            "ssd_scan": 1.0, "moe_experts": 3.0}}
    assert reader("dev_lone_ssd_share").read({"scope_account": acc}) \
        == pytest.approx(25.0)
    bare = dict(acc, by_component={"mlp": 8.0})  # the parent: no such scope
    assert reader("dev_lone_ssd_share").read({"scope_account": bare}) is None
    assert reader("dev_lone_ssd_share").read({"scope_account": None}) is None
    ctx = {"before": {"engine": {"moe_experts_read": 100,
                                 "moe_experts_held": 200}},
           "after": {"engine": {"moe_experts_read": 100 + 785,
                                "moe_experts_held": 200 + 1000}}}
    assert reader("ep2_experts_read_share").read(ctx) == pytest.approx(78.5)
    parent = {"before": {"engine": {}}, "after": {"engine": {}}}
    assert reader("ep2_experts_read_share").read(parent) is None


def test_the_kernel_readers_count_from_each_calls_own_shapes():
    import kernel_calls
    import ssd_roofline

    heads, P, G, N, lanes = 64, 64, 8, 128, 32
    step = ("%ssd_step.7 = (f32[32,1,4096]{2,1,0}, "
            "f32[7,129,4096,128]{3,2,1,0}) custom-call(s32[1]{0} %l, "
            "s32[32]{0} %s, f32[32,1,4096]{2,1,0} %x, f32[32,1,1024]{2,1,0} "
            "%b, f32[32,1,1024]{2,1,0} %c, f32[32,8,1,8]{3,2,1,0} %g, "
            "f32[7,129,4096,128]{3,2,1,0} %leaf)")
    nbytes = 4 * lanes * (2 * heads * P * N + 2 * heads * P + 2 * G * N
                          + heads)
    least = nbytes / 819e9
    ctx = {"cell": types.SimpleNamespace(name="synthetic"),
           "info": {"kind": "TPU v5 lite"}, "trace": {},
           "kernel_events": [(step, 2 * least), (step, 2 * least)]}
    assert reader("ssd64_step_roofline").read(ctx) == pytest.approx(50.0)
    assert reader("ssd64_chunk_roofline").read(ctx) is None  # no such call
    chunk = ("%ssd_chunk.2 = (f32[1,512,4096]{2,1,0}, "
             "f32[7,129,4096,128]{3,2,1,0}) custom-call(s32[1]{0} %l, "
             "s32[1]{0} %a, s32[1]{0} %b, s32[1]{0} %c, s32[1]{0} %f, "
             "f32[1,512,4096]{2,1,0} %x, f32[1,512,1024]{2,1,0} %bm, "
             "f32[1,512,1024]{2,1,0} %cm, f32[1,8,512,8]{3,2,1,0} %g, "
             "f32[7,129,4096,128]{3,2,1,0} %leaf)")
    flops, moved = ssd_roofline.chunk_call(
        kernel_calls.shapes(chunk, "operands"))
    assert moved == 4 * (512 * (2 * heads * P + 2 * G * N + heads)
                         + 3 * heads * P * N)
    assert flops == 4 * 2 * (G * 128 * 128 * N + heads * (
        128 * 128 * P + 2 * 128 * P * N))
    least = max(moved / 819e9, flops / 197e12)
    ctx["kernel_events"] = [(chunk, 4 * least)]
    assert reader("ssd64_chunk_roofline").read(ctx) == pytest.approx(25.0)
    assert reader("ssd64_step_roofline").read(ctx) is None
    ctx["kernel_events"] = None  # no capture
    assert reader("ssd64_chunk_roofline").read(ctx) is None


def test_the_reference_holds_forward_at_the_tiny_size():
    import jax
    import jax.numpy as jnp

    from kafka_tpu.models import forward, init_params
    from kafka_tpu.models.config import config_from_hf_json

    cfg = config_from_hf_json(os.path.join(
        TWIN, "configs", "tiny-nemotronh.json"))
    assert cfg.pattern[1] == tuple(
        {"M": "mamba2", "E": "moe", "*": "full_attention"}[c]
        for c in "MEM*EME")
    params = init_params(cfg, jax.random.PRNGKey(1))
    ids = np.random.RandomState(2).randint(0, cfg.vocab_size, 48)
    want = nemotron.reference_logits(params, nemotron.hyper(cfg), ids,
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
    assert NEW <= want
    # each new entry lists the new cell alone
    assert all(m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
               for m in real["per_layer"] if m["name"] in NEW)
    assert [m["name"] for m in real["per_layer"][-5:]] == [
        "dev_lone_ssd_share", "ssd64_step_roofline", "ssd64_chunk_roofline",
        "gqa16_attn_roofline", "ep2_experts_read_share"]
    with open(os.path.join(BENCH, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    assert list(config["reduced"]) == ["num_hidden_layers",
                                       "n_routed_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (16, 64, 65536)
    assert config["serving"]["max_batch"] == 32
    assert config["serving"]["num_pages"] == 8192
    assert config["serving"]["prefill_buckets"] == [128, 256, 512]
    assert config["expect"]["attention_backend"] == "pallas"
    check = config["check"]
    assert (check["reference"], check["driver"]) == (
        "nemotronh", "nemotronh_pool")
    assert (check["n_prefill"], check["n_decode"]) == (1536, 47)
    assert nemotron.RUN_IN == driver.RUN_IN
    assert (check["n_prefill"] - nemotron.RUN_IN) \
        % config["serving"]["page_size"] == 0
    cell = next(w for w in real["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "chat-decode")
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        params = json.load(f)["params"]
    assert params == {"clients": 32, "stagger_s": 0.45}


def test_rehearsal_of_the_tiny_twin():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", "tiny-nemotronh.chat-decode", "--seed", "3000000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"ep2_experts_read_share", "prefix_hit_share",
            "decode_batch_occupancy"} <= set(line["metrics"])
    assert 0 < line["metrics"]["ep2_experts_read_share"]["value"] <= 100.0
    # device metrics never come from a CPU run
    assert not {"dev_lone_ssd_share", "ssd64_step_roofline",
                "ssd64_chunk_roofline", "gqa16_attn_roofline",
                "decode_step_dev_ms"} & set(line["metrics"])
    check = json.loads(next(
        ln for ln in lines if ln.startswith("run.py: logit check ")
    )[len("run.py: logit check "):])
    assert check["ok"] and check["reference"] == "references/nemotronh"
    assert check["driver"] == "drivers/nemotronh_pool"
