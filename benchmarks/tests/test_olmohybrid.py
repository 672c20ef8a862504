"""The Olmo-Hybrid-7B configuration's own files (PR 66): the readers the cell
adds on synthetic input (each reads its source or nothing), the two counts of
`gdn_roofline.py` through their readers, what the tiny twin lists against the
real cell, and the CPU rehearsal of the twin under
`benchmarks/tests/olmohybrid/`.  (`test_check_resolution.py` scans every file
under `references/` for imports of the program; the reference against
`kafka_tpu.models.forward`, the paged path through pages and state slots
with the configuration's driver, the driver's reading of the slot and the
reference's `variants` are held in `tests/test_olmo_hybrid.py`, tier-1.)"""

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

TWIN = os.path.join(HERE, "olmohybrid")
CELL = "olmo-hybrid-7b.chat-decode"
NEW = {"dev_gdn_share", "dev_gdn_conv_share", "gdn_step_roofline",
       "gdn_chunk_roofline", "mha_attn_roofline", "gdn_state_restore_share",
       "state_kernel_launch_share"}
olmo = named.load((BENCH,), "references", "olmohybrid")
driver = named.load((BENCH,), "drivers", "olmohybrid_pool")


def reader(name):
    return named.load((BENCH,), "layer_metrics", name)


def test_the_scope_and_counter_readers_read_their_source_or_nothing():
    acc = {"scoped": True, "unnamed_programs": [], "busy_s": 8.0,
           "by_component": {"kda_proj": 1.0, "kda_conv": 0.4, "kda_gate": 0.2,
                            "kda_delta": 0.8, "mlp": 3.0}}
    assert reader("dev_gdn_share").read({"scope_account": acc}) \
        == pytest.approx(30.0)
    assert reader("dev_gdn_conv_share").read({"scope_account": acc}) \
        == pytest.approx(5.0)
    bare = dict(acc, by_component={"mlp": 8.0})  # the parent: no such scope
    for name in ("dev_gdn_share", "dev_gdn_conv_share"):
        assert reader(name).read({"scope_account": bare}) is None
        assert reader(name).read({"scope_account": None}) is None
    launches = {"state_launches_recurrence_kernel": 100,
                "state_launches_recurrence_xla": 0,
                "state_launches_tail_kernel": 0,
                "state_launches_tail_xla": 100}
    ctx = {"before": {"state": {"state_tokens_matched": 1000,
                                "state_tokens_skipped": 400},
                      "engine": launches},
           "after": {"state": {"state_tokens_matched": 9000,
                               "state_tokens_skipped": 8000},
                     "engine": {k: 13 * v for k, v in launches.items()}}}
    assert reader("gdn_state_restore_share").read(ctx) == pytest.approx(95.0)
    assert reader("state_kernel_launch_share").read(ctx) \
        == pytest.approx(50.0)
    # the parent exports no such counter; a window without a state launch
    parent = {"before": {"engine": {}}, "after": {"engine": {}}}
    assert reader("gdn_state_restore_share").read(parent) is None
    assert reader("state_kernel_launch_share").read(parent) is None
    still = {"before": ctx["before"], "after": ctx["before"]}
    assert reader("state_kernel_launch_share").read(still) is None


def test_the_kernel_readers_count_from_each_calls_own_shapes():
    import gdn_roofline
    import kernel_calls

    heads, dk, dv, lanes = 30, 96, 192, 16
    leaf = "f32[12,65,96,5760]{3,2,1,0}"
    step = (f"%gdn_step.7 = (f32[16,1,5760]{{2,1,0}}, {leaf}) custom-call("
            "s32[1]{0} %l, s32[16]{0} %s, f32[16,1,3840]{2,1,0} %q, "
            "f32[16,1,3840]{2,1,0} %k, f32[16,1,5760]{2,1,0} %vb, "
            "f32[16,1,5760]{2,1,0} %a, f32[16,1,5760]{2,1,0} %b, "
            f"{leaf} %leaf)")
    nbytes = 4 * lanes * (2 * heads * dk * dv + heads * (2 * dk + 2 * dv + 2))
    least = nbytes / 819e9
    ctx = {"cell": types.SimpleNamespace(name="synthetic"),
           "info": {"kind": "TPU v5 lite"}, "trace": {},
           "kernel_events": [(step, 2 * least), (step, 2 * least)]}
    assert reader("gdn_step_roofline").read(ctx) == pytest.approx(50.0)
    assert reader("gdn_chunk_roofline").read(ctx) is None  # no such call
    chunk = (f"%gdn_chunk.2 = (f32[1,512,5760]{{2,1,0}}, {leaf}) "
             "custom-call(s32[1]{0} %l, s32[1]{0} %a, s32[1]{0} %b, "
             "s32[1]{0} %c, s32[1]{0} %f, f32[1,512,3840]{2,1,0} %q, "
             "f32[1,512,3840]{2,1,0} %k, f32[1,512,5760]{2,1,0} %vb, "
             "f32[1,512,30]{2,1,0} %g, f32[1,8,30,64]{3,2,1,0} %gt, "
             f"f32[1,512,30]{{2,1,0}} %beta, {leaf} %leaf)")
    flops, moved = gdn_roofline.chunk_call(
        kernel_calls.shapes(chunk, "operands"))
    assert moved == 4 * (512 * heads * (2 * dk + 2 * dv + 2)
                         + 3 * heads * dk * dv)
    assert flops == heads * 8 * 2 * (2 * 64 * 64 * dk + 3 * 64 * dk * dv
                                     + 2 * 64 * 64 * dv)
    least = max(moved / 819e9, flops / 197e12)
    ctx["kernel_events"] = [(chunk, 4 * least)]
    assert reader("gdn_chunk_roofline").read(ctx) == pytest.approx(25.0)
    assert reader("gdn_step_roofline").read(ctx) is None
    ctx["kernel_events"] = None  # no capture
    assert reader("gdn_chunk_roofline").read(ctx) is None


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
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    assert list(config["reduced"]) == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 16
    assert config["serving"]["max_batch"] == 16
    assert config["expect"]["attention_backend"] == "pallas"
    check = config["check"]
    assert (check["reference"], check["driver"]) == (
        "olmohybrid", "olmohybrid_pool")
    assert (check["n_prefill"], check["n_decode"]) == (1536, 47)
    assert olmo.LAST == driver.LAST
    assert (check["n_prefill"] - olmo.LAST) \
        % config["serving"]["page_size"] == 0
    cell = next(w for w in real["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "chat-decode")
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        assert json.load(f)["params"] == {}


def test_rehearsal_of_the_tiny_twin():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", "tiny-olmohybrid.chat-decode", "--seed", "3000000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"gdn_state_restore_share", "state_kernel_launch_share",
            "prefix_hit_share", "decode_batch_occupancy"} <= set(
                line["metrics"])
    assert line["metrics"]["gdn_state_restore_share"]["value"] >= 95.0
    # (the twin serves on the XLA backend: no launch ran a kernel)
    assert line["metrics"]["state_kernel_launch_share"]["value"] == 0.0
    # device metrics never come from a CPU run
    assert not {"dev_gdn_share", "dev_gdn_conv_share", "gdn_step_roofline",
                "mha_attn_roofline", "decode_step_dev_ms"} & set(
                    line["metrics"])
    check = json.loads(next(
        ln for ln in lines if ln.startswith("run.py: logit check ")
    )[len("run.py: logit check "):])
    assert check["ok"] and check["reference"] == "references/olmohybrid"
    assert check["driver"] == "drivers/olmohybrid_pool"
