"""The K-EXAONE configuration's own files (PR 43): `references/kexaone.py`
imports nothing of the program and matches `kafka_tpu.models.forward` at the
tiny twin's size through the paged pool; the seven readers the cell adds, on
synthetic input (each returns None where the program has nothing to read, as
the parent has not); the model's flop count of a prefill chunk; what the
configuration's file must say; and the CPU rehearsal of the tiny twin under
`benchmarks/tests/kexaone/`.  (`tests/test_exaone_moe.py`, in the tier-1 run,
holds the program's side against the same reference.)"""

import ast
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

import named  # noqa: E402
import paged_step  # noqa: E402
import prefill_flops  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import window_roofline  # noqa: E402
from kafka_tpu.models import init_params  # noqa: E402
from kafka_tpu.models.config import config_from_hf_json  # noqa: E402

TWIN = os.path.join(HERE, "kexaone")
CELL = "k-exaone-236b-a23b.chat-decode"
NEW = ["wide_gqa_attn_roofline", "narrow_window_attn_roofline",
       "wide_gqa_prefill_mfu", "dev_narrow_window_attn_share",
       "dev_shared_expert_share", "dev_qk_norm_share",
       "kv_narrow_window_dead_share"]
kexaone = named.load((BENCH,), "references", "kexaone")


def reader(name):
    return named.load((BENCH,), "layer_metrics", name).read


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "kexaone.py")) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module or "" for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert names and not [n for n in names if n.split(".")[0] == "kafka_tpu"]
    assert {n.split(".")[0] for n in names} <= {
        "__future__", "functools", "typing", "jax", "numpy"}


def test_paged_prefill_and_decode_match_the_reference_at_the_twins_size():
    cfg = config_from_hf_json(
        os.path.join(TWIN, "configs", "tiny-kexaone.json"))
    assert cfg.first_k_dense == 1 and cfg.kind_of(0) == "sliding_attention"
    assert (cfg.num_experts, cfg.num_experts_routed, cfg.expert_offset) == (
        4, 16, 4)
    params = init_params(cfg, jax.random.PRNGKey(1))
    ids = np.random.RandomState(0).randint(0, 512, size=76)
    n_prefill = 64  # four windows of 16: every compared position is past it
    served = paged_step.served_logits(params, cfg, ids, n_prefill,
                                      page_size=16, pages_per_seq=5)
    pos = list(range(n_prefill - 1, len(ids)))
    ref = kexaone.reference_logits(params, kexaone.hyper(cfg), ids, pos)
    res = reference.compare_logits(served, ref["logits"], ref["router_gap"],
                                   tol=1e-4)
    assert res["ok"] and res["compared"] >= reference.MIN_COMPARED, res
    assert kexaone.COMPARE_SKIPS_UNDER == reference.ROUTER_TIE_MARGIN


def test_tolerance_and_margin_are_set_from_chip_readings():
    assert 0.0 < kexaone.TOLERANCE["value"] < 0.2
    assert "chip run" in kexaone.TOLERANCE["why"]
    assert 0.0 < kexaone.ROUTER_FLIP_MARGIN < 0.05


# --------------------------------------------------------------------------
# the flop count and the readers
# --------------------------------------------------------------------------

def test_attended_pairs_under_both_masks():
    def brute(s, c, w=None):
        return sum(min(c + i + 1, w or c + i + 1) for i in range(s))

    for s, c, w in [(5, 0, None), (5, 3, None), (5, 0, 4), (10, 2, 4),
                    (3, 100, 128), (200, 0, 128), (200, 100, 128),
                    (1, 126, 128), (5, 127, 128), (512, 28400, 128)]:
        assert prefill_flops.attended_pairs(s, c, w) == brute(s, c, w)
    assert prefill_flops.attended_pairs(0, 9, 4) == 0.0
    # the causal count is roofline.flash_prefill's
    assert prefill_flops.gqa_prefill(512, 28400, 64, 128) == \
        roofline.flash_prefill(512, 28400, 64, 8, 128)[0]
    # a window far below the context: 128 keys a row whatever the start
    assert prefill_flops.gqa_prefill(512, 28400, 64, 128, 128) == \
        4.0 * 512 * 128 * 64 * 128


def cell(layers=6):
    kinds = (["sliding_attention"] * 3 + ["full_attention"]) * 12
    config = {"num_attention_heads": 64, "num_key_value_heads": 8,
              "head_dim": 128, "hidden_size": 6144,
              "num_hidden_layers": layers, "layer_types": kinds,
              "sliding_window": 128,
              "serving": {"page_size": 16, "max_batch": 32},
              "scopes": ["attn_window", "moe_shared", "qk_norm"]}
    return types.SimpleNamespace(config=config, name="synthetic")


LOG = [{"error": None, "in_window": True, "done": True, "t_first": 12.0,
        "cached_tokens": 28192,
        "usage": {"prompt_tokens": 28192 + 640, "completion_tokens": 400,
                  "prompt_tokens_details": {"cached_tokens": 28192}}}]


def test_narrow_window_attn_roofline_on_a_synthetic_capture():
    lanes, calls = 30.0, 500
    _, nbytes = window_roofline.windowed_decode(
        [29032], 128, 64, 8, 128, 16)
    assert nbytes == 2 * 128 * 1024 * 2 + 2 * 64 * 128 * 2
    least = nbytes * calls * lanes / 819e9
    ctx = {
        "cell": cell(), "info": {"kind": "TPU v5 lite"},
        "trace": {"op_self_s": {
            "paged_decode_attention_window.1 bf16[32,64,1024]": 4 * least,
            "paged_decode_attention.1 bf16[32,64,1024]": 9.0},
            "op_count": {"paged_decode_attention_window": calls,
                         "paged_decode_attention": 100}},
        "after": {"decode": {"steps": 100, "batch_occupancy": lanes}},
        "before": {"decode": {"steps": 0, "batch_occupancy": 0.0}},
        "log": LOG,
    }
    assert reader("narrow_window_attn_roofline")(ctx) == pytest.approx(25.0)
    ctx["trace"]["op_self_s"].pop(
        "paged_decode_attention_window.1 bf16[32,64,1024]")
    assert reader("narrow_window_attn_roofline")(ctx) is None  # the parent


def test_wide_gqa_attn_roofline_counts_the_decode_programs_calls_only():
    lanes = 30.0
    _, nbytes = roofline.paged_decode([29032], 64, 8, 128, 16)
    least = nbytes * lanes / 819e9
    wide = ("%paged_decode_attention.3 = bf16[32,64,1024]{2,1,0} "
            "custom-call(bf16[32,64,1024]{2,1,0} %q, bf16[131072,1024] %k)")
    narrow = wide.replace("bf16[32,64,1024]", "bf16[2,64,1024]")
    windowed = wide.replace("paged_decode_attention.3",
                            "paged_decode_attention_window.4")
    ctx = {
        "cell": cell(), "info": {"kind": "TPU v5 lite"}, "trace": {},
        "kernel_events": [(wide, 2 * least), (wide, 2 * least),
                          (narrow, 5.0), (windowed, 7.0)],
        "after": {"decode": {"steps": 100, "batch_occupancy": lanes}},
        "before": {"decode": {"steps": 0, "batch_occupancy": 0.0}},
        "log": LOG,
    }
    assert reader("wide_gqa_attn_roofline")(ctx) == pytest.approx(50.0)
    ctx["kernel_events"] = [(windowed, 7.0)]
    assert reader("wide_gqa_attn_roofline")(ctx) is None
    ctx["kernel_events"] = None  # no capture
    assert reader("wide_gqa_attn_roofline")(ctx) is None


def test_wide_gqa_prefill_mfu_is_the_models_flops_over_the_kernels_time():
    read = reader("wide_gqa_prefill_mfu")
    need = (prefill_flops.gqa_prefill(640, 28192, 64, 128)
            + 5 * prefill_flops.gqa_prefill(640, 28192, 64, 128, 128))
    seconds = 20 * need / 197e12
    ctx = {
        "cell": cell(), "info": {"kind": "TPU v5 lite"},
        "trace": {"window_s": 5.0, "op_self_s": {
            "paged_prefill_attention.2 bf16[8192,1024]": seconds}},
        "profile": {"flight_window": {"t_start": 100.0, "t_end": 108.8}},
        "t_open": 0.0, "wall_open": 90.0, "log": LOG,
    }
    assert read(ctx) == pytest.approx(5.0)
    assert read(dict(ctx, log=[dict(LOG[0], t_first=2.0)])) is None
    # the reply came after the window closed: the capture is where run.py
    # posts it, a third into the window (t_first 12.0 lies in 10.5 .. 15.5)
    late = dict(ctx, profile=None, t_open=0.0, t_close=30.0)
    assert read(late) == pytest.approx(5.0)
    assert read(dict(late, t_close=60.0)) is None
    ctx["trace"]["op_self_s"] = {"fusion.1": 1.0}  # the xla backend, the parent
    assert read(ctx) is None
    assert read(dict(ctx, trace=None)) is None


@pytest.mark.parametrize("name, scope", [
    ("dev_narrow_window_attn_share", "attn_window"),
    ("dev_shared_expert_share", "moe_shared"),
    ("dev_qk_norm_share", "qk_norm"),
])
def test_a_scope_share_reads_its_scope_or_nothing(name, scope):
    acc = {"scoped": True, "unnamed_programs": [], "busy_s": 4.0,
           "by_component": {scope: 0.5, "attn_core": 1.0, "moe_experts": 2.5}}
    read = reader(name)
    assert read({"scope_account": acc}) == pytest.approx(12.5)
    acc["by_component"].pop(scope)  # the parent names no such scope
    assert read({"scope_account": acc}) is None
    assert read({"scope_account": None}) is None


def test_kv_narrow_window_dead_share_reads_the_counter_or_nothing():
    read = reader("kv_narrow_window_dead_share")
    assert read({"after": {"engine": {"kv_window_dead_share": 0.83}}}) \
        == pytest.approx(83.0)
    assert read({"after": {"engine": {"pages_total": 9}}}) is None


# --------------------------------------------------------------------------
# the files
# --------------------------------------------------------------------------

def test_the_configuration_says_what_the_issue_asks():
    with open(os.path.join(BENCH, "configs", "k-exaone-236b-a23b.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "configs", "dots3-note-prev.json")) as f:
        dots3 = json.load(f)
    for key, want in (("hidden_size", 6144), ("num_attention_heads", 64),
                      ("num_key_value_heads", 8), ("head_dim", 128),
                      ("intermediate_size", 18432),
                      ("moe_intermediate_size", 2048),
                      ("num_experts_per_tok", 8), ("sliding_window", 128),
                      ("num_experts_published", 128)):
        assert config[key] == want, key
    assert config["rope_parameters"]["rope_theta"] == 1000000
    assert sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert {"residual_form", "qk_norm", "full_layers_do_not_rotate",
            "selection_bias", "mtp_module"} <= set(config["assumed"])
    assert "8 chips" in config["deployment"]
    assert config["expect"] == {"attention_backend": "pallas"}
    assert config["scopes"] == ["attn_window", "moe_shared", "qk_norm"]
    check, serving = config["check"], config["serving"]
    assert check["reference"] == "kexaone" and "driver" not in check
    assert check["n_prefill"] >= 1536 > config["sliding_window"]
    assert check["pages_per_seq"] >= 99
    assert serving["attention_backend"] == "pallas" and serving["ignore_eos"]
    assert (serving["max_batch"], serving["num_pages"],
            serving["max_pages_per_seq"]) == (32, 8192, 2048)
    assert serving["prefill_buckets"] == [64, 256, 512]
    assert serving["system_prompt"] == dots3["serving"]["system_prompt"]


def test_the_cell_and_its_twin_list_the_seven_new_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TWIN, "BENCHMARK.json")) as f:
        twin = json.load(f)
    entry = next(w for w in real["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "k-exaone-236b-a23b", "chat-decode", 1)
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        assert json.load(f)["params"] == {"clients": 32, "stagger_s": 0.45}
    own = [m for m in real["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in own] == NEW
    assert all(m["unit"] == "%" for m in own)
    assert [m["moves"] for m in own] == ["tpot_p50_ms"] * 6 + ["out_tok_s"]
    want = {m["name"] for m in real["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    assert {m["name"] for m in twin["per_layer"]} == want
    for name in NEW:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name


def test_rehearsal_of_the_tiny_twin():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", "tiny-kexaone.chat-decode", "--seed", "3000000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=600, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"kv_narrow_window_dead_share", "prefix_hit_share",
            "decode_batch_occupancy"} <= set(line["metrics"])
    # device metrics never come from a CPU run
    assert not (set(NEW) - {"kv_narrow_window_dead_share"}) & set(
        line["metrics"])
    check = json.loads(next(
        ln for ln in lines if ln.startswith("run.py: logit check ")
    )[len("run.py: logit check "):])
    assert check["ok"] and check["reference"] == "references/kexaone"
    assert check["driver"] == "paged_step" and check["compared"] >= 3
