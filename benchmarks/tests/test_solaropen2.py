"""The Solar-Open2-250B configuration's own files (PR 50): the readers the
cell adds on synthetic input (each reads its source or nothing), the two
counts of `delta_roofline.py`, what the tiny twin lists against the real
cell, and the CPU rehearsal of the twin under `benchmarks/tests/solar_open2/`.
(`test_check_resolution.py` scans every file under `references/` for imports
of the program; the reference against `kafka_tpu.models.forward`, the paged
path through pages and state slots with the configuration's driver, the
driver's reading of the slot and the reference's `variants` are held in
`tests/test_solar_open2.py`, tier-1.)"""

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import named  # noqa: E402

TWIN = os.path.join(HERE, "solar_open2")
CELL = "solar-open2-250b.chat-decode"
NEW = {"dev_kda_share", "delta_step_roofline", "delta_chunk_roofline",
       "delta_state_restore_share", "gated_gqa_attn_roofline",
       "ep16_experts_read_share"}
solar = named.load((BENCH,), "references", "solaropen2")
driver = named.load((BENCH,), "drivers", "solaropen2_pool")


def reader(name):
    return named.load((BENCH,), "layer_metrics", name)


def test_the_scope_and_counter_readers_read_their_source_or_nothing():
    acc = {"scoped": True, "unnamed_programs": [], "busy_s": 8.0,
           "by_component": {"kda_proj": 1.0, "kda_conv": 0.2, "kda_gate": 0.2,
                            "kda_delta": 1.0, "moe_experts": 3.0}}
    assert reader("dev_kda_share").read({"scope_account": acc}) \
        == pytest.approx(30.0)
    bare = dict(acc, by_component={"mlp": 8.0})  # the parent: no such scope
    assert reader("dev_kda_share").read({"scope_account": bare}) is None
    assert reader("dev_kda_share").read({"scope_account": None}) is None
    ctx = {"before": {"state": {"state_tokens_matched": 1000,
                                "state_tokens_skipped": 400},
                      "engine": {"moe_experts_read": 100,
                                 "moe_experts_held": 200}},
           "after": {"state": {"state_tokens_matched": 9000,
                               "state_tokens_skipped": 8000},
                     "engine": {"moe_experts_read": 650,
                                "moe_experts_held": 1200}}}
    assert reader("delta_state_restore_share").read(ctx) \
        == pytest.approx(95.0)
    assert reader("ep16_experts_read_share").read(ctx) == pytest.approx(55.0)
    parent = {"before": {"engine": {}}, "after": {"engine": {}}}
    assert reader("delta_state_restore_share").read(parent) is None
    assert reader("ep16_experts_read_share").read(parent) is None


def test_the_kernel_readers_count_from_each_calls_own_shapes():
    import delta_roofline

    heads, d, lanes = 64, 128, 32
    step = ("%gated_delta_step.7 = (f32[32,1,8192]{2,1,0}, "
            "f32[6,129,8192,128]{3,2,1,0}) custom-call(s32[1]{0} %l, "
            "s32[32]{0} %s, f32[32,1,8192]{2,1,0} %q, f32[32,1,8192]{2,1,0} "
            "%k, f32[32,1,8192]{2,1,0} %kb, f32[32,1,8192]{2,1,0} %vb, "
            "f32[32,1,8192]{2,1,0} %g, f32[6,129,8192,128]{3,2,1,0} %leaf)")
    nbytes = 4 * lanes * (2 * heads * d * d + 6 * heads * d)
    least = nbytes / 819e9
    ctx = {"cell": types.SimpleNamespace(name="synthetic"),
           "info": {"kind": "TPU v5 lite"}, "trace": {},
           "kernel_events": [(step, 2 * least), (step, 2 * least)]}
    assert reader("delta_step_roofline").read(ctx) == pytest.approx(50.0)
    assert reader("delta_chunk_roofline").read(ctx) is None  # no such call
    chunk = ("%gated_delta_chunk.2 = (f32[1,512,8192]{2,1,0}, "
             "f32[6,129,8192,128]{3,2,1,0}) custom-call(s32[1]{0} %l, "
             "s32[1]{0} %a, s32[1]{0} %b, s32[1]{0} %c, s32[1]{0} %f, "
             + ", ".join(f"f32[1,512,8192]{{2,1,0}} %r{i}" for i in range(5))
             + ", f32[6,129,8192,128]{3,2,1,0} %leaf)")
    import kernel_calls

    flops, moved = delta_roofline.chunk_call(
        kernel_calls.shapes(chunk, "operands"))
    assert moved == 4 * (512 * 6 * heads * d + 3 * heads * d * d)
    assert flops == heads * 8 * 2 * (4 * 64 * 64 * d + 3 * 64 * d * d)
    least = max(moved / 819e9, flops / 197e12)
    ctx["kernel_events"] = [(chunk, 4 * least)]
    assert reader("delta_chunk_roofline").read(ctx) == pytest.approx(25.0)
    assert reader("delta_step_roofline").read(ctx) is None
    ctx["kernel_events"] = None  # no capture
    assert reader("delta_chunk_roofline").read(ctx) is None


def test_the_twin_lists_what_the_real_cell_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TWIN, "BENCHMARK.json")) as f:
        twin = json.load(f)
    want = {m["name"] for m in real["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    assert {m["name"] for m in twin["per_layer"]} == want
    assert NEW <= want
    assert all(m["workloads"] == [CELL] for m in real["per_layer"]
               if m["name"] in NEW)
    with open(os.path.join(BENCH, "configs", "solar-open2-250b.json")) as f:
        config = json.load(f)
    assert list(config["reduced"]) == ["num_hidden_layers",
                                       "n_routed_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (8, 20, 24576)
    assert config["serving"]["max_batch"] == 32
    assert config["expect"]["attention_backend"] == "pallas"
    check = config["check"]
    assert (check["reference"], check["driver"]) == (
        "solaropen2", "solaropen2_pool")
    assert (check["n_prefill"], check["n_decode"]) == (1536, 47)
    assert solar.RUN_IN == driver.RUN_IN
    assert (check["n_prefill"] - solar.RUN_IN) \
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
         "--workload", "tiny-solaropen2.chat-decode", "--seed", "3000000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"delta_state_restore_share", "ep16_experts_read_share",
            "prefix_hit_share", "decode_batch_occupancy"} <= set(
                line["metrics"])
    assert line["metrics"]["delta_state_restore_share"]["value"] >= 95.0
    # device metrics never come from a CPU run
    assert not {"dev_kda_share", "delta_step_roofline",
                "decode_step_dev_ms"} & set(line["metrics"])
    check = json.loads(next(
        ln for ln in lines if ln.startswith("run.py: logit check ")
    )[len("run.py: logit check "):])
    assert check["ok"] and check["reference"] == "references/solaropen2"
    assert check["driver"] == "drivers/solaropen2_pool"
