"""The dots3-note-prev configuration's own files (PR 33): `references/dots3.py`
against `kafka_tpu.models.forward` at a tiny size in float32 (the key
selection with index_topk 8, the window of 5, the two geometries, the gate,
the rescale, 4 of 16 experts held), what it reports about router ties, the
POWER of the check (each of its `variants` must move the logits), the
two-row cache driver against the reference, the flop and byte counts of the
two new decode reads, the five readers the cell adds on synthetic input, and
the CPU rehearsal of the tiny twin under `benchmarks/tests/dots3/`."""

import json
import os
import re
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
import sparse_roofline  # noqa: E402
from kafka_tpu.models import forward, init_params  # noqa: E402
from kafka_tpu.models.config import config_from_hf_json  # noqa: E402

TWIN = os.path.join(HERE, "dots3")
CELL = "dots3-note-prev.chat-decode"
dots3 = named.load((BENCH,), "references", "dots3")
dots3_pool = named.load((BENCH,), "drivers", "dots3_pool")


@pytest.fixture(scope="module")
def tiny():
    cfg = config_from_hf_json(os.path.join(TWIN, "configs", "tiny-dots3.json"))
    return cfg, init_params(cfg, jax.random.PRNGKey(1))


IDS = np.random.RandomState(0).randint(0, 512, size=72)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "dots3.py")) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+kafka_tpu", src, re.M)
    assert not re.search(r"^\s*(from|import)\s+(reference|paged_step)\b", src,
                         re.M)


def test_reference_matches_program_forward(tiny):
    cfg, params = tiny
    assert cfg.index_topk == 8 and cfg.sliding_window == 5
    assert (cfg.num_experts, cfg.num_router_experts, cfg.expert_offset) == (
        4, 16, 4)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, jnp.asarray(IDS)[None],
                            jnp.arange(len(IDS))[None])
    ref = dots3.reference_logits(params, dots3.hyper(cfg), IDS,
                                 list(range(len(IDS))))
    np.testing.assert_allclose(np.asarray(logits[0]), ref["logits"],
                               rtol=2e-4, atol=2e-4)
    raw = ref["raw_router_gap"]
    assert raw.shape == (len(IDS),) and (raw >= 0).all()
    # no position is skipped (references/dots3.py, ROUTER TIES)
    assert np.isinf(ref["router_gap"]).all()


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_two_row_pool_driver_matches_the_reference(tiny, backend, monkeypatch):
    """Prefill in chunks (16 here, 512 served), then decode, through the
    pools the engine would allocate: the check's served side."""
    cfg, params = tiny
    monkeypatch.setattr(dots3_pool, "CHUNK", 16)
    n_prefill = 40
    served = dots3_pool.served_logits(
        params, cfg.replace(attention_backend=backend), IDS, n_prefill,
        page_size=16, pages_per_seq=5)
    pos = list(range(n_prefill - 1, len(IDS)))
    ref = dots3.reference_logits(params, dots3.hyper(cfg), IDS, pos)
    res = reference.compare_logits(served, ref["logits"], ref["router_gap"],
                                   tol=1e-4)
    assert res["ok"] and res["compared"] >= reference.MIN_COMPARED, res


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
@pytest.mark.parametrize("delta", [-1, 1])
def test_the_driver_holds_the_windows_edge(tiny, monkeypatch, capsys, program,
                                           delta):
    """A served program whose window is one key shorter or longer, in decode
    or in prefill, agrees with the reference to any bfloat16 tolerance at the
    published widths; the driver's poisoned rows tell it exactly."""
    cfg, params = tiny
    monkeypatch.setattr(dots3_pool, "CHUNK", 16)
    dots3_pool.served_logits(params, cfg, IDS, 40, page_size=16,
                             pages_per_seq=5)
    said = capsys.readouterr().out
    assert "dots3_pool: window edge" in said
    edge = json.loads(said.split("window edge", 1)[1])
    assert edge["window"] == 5 and edge["query"] == len(IDS) - 1
    for path in ("decode", "prefill"):
        assert edge[path]["outside"] == 0.0
        assert edge[path]["oldest_inside"] > dots3_pool.MOVED

    right = getattr(paged_step, program)

    def off_by_one(params, cfg, *args, **kwargs):
        return right(params, cfg.replace(
            sliding_window=cfg.sliding_window + delta), *args, **kwargs)

    monkeypatch.setattr(paged_step, program, off_by_one)
    with pytest.raises(AssertionError, match="window edge: query 71"):
        dots3_pool.served_logits(params, cfg, IDS, 40, page_size=16,
                                 pages_per_seq=5)


def rel_rms(a, b):
    return float(np.sqrt(np.mean((a - b) ** 2)) / np.sqrt(np.mean(b ** 2)))


def test_every_variant_moves_the_logits(tiny):
    """Were the program to make one of these mistakes, the logits move: at
    the tiny size (8 of up to 72 keys chosen, a window of 5) by more than
    any tolerance at the worst compared position."""
    cfg, params = tiny
    hp = dots3.hyper(cfg)
    pos = list(range(48, 72))
    ref = dots3.reference_logits(params, hp, IDS, pos)["logits"]
    names = set()
    for name, variant in dots3.variants(hp).items():
        got = dots3.reference_logits(params, variant, IDS, pos)["logits"]
        worst = max(rel_rms(g, r) for g, r in zip(got, ref))
        assert worst > 0.05, (name, worst)
        names.add(name)
    assert names == {
        "attend_all_no_selection", "index_scores_without_relu",
        "index_scores_without_head_weights", "index_scores_bf16",
        "window_512", "window_514", "no_gate", "no_rescale",
        "sliding_sizes_on_full_layers", "absent_experts_renormalised_away"}


def test_hyper_refuses_a_model_that_is_not_dots3_shaped(tiny):
    cfg, _ = tiny
    with pytest.raises(ValueError):
        dots3.hyper(cfg.replace(index_topk=0))
    with pytest.raises(ValueError):
        dots3.hyper(cfg.replace(tie_word_embeddings=True))


# --------------------------------------------------------------------------
# the flop and byte counts, and the five readers
# --------------------------------------------------------------------------

def test_chosen_rows_decode_counts_the_kept_rows_only():
    flops, nbytes = sparse_roofline.chosen_rows_decode(
        [29300], 2048, 128, 512, 64, 640)
    assert flops == 2.0 * 2048 * 128 * (576 + 512)
    assert nbytes == 2048 * 640 * 2 + 128 * (576 + 512) * 2
    # below index_topk every key is kept
    short, _ = sparse_roofline.chosen_rows_decode(
        [300], 2048, 128, 512, 64, 640)
    assert short == 2.0 * 300 * 128 * (576 + 512)
    assert sparse_roofline.chosen_rows_decode(
        [0, -2], 2048, 128, 512, 64, 640) == (0.0, 0.0)


def test_latent_window_decode_counts_the_window_in_whole_chunks():
    flops, nbytes = sparse_roofline.latent_window_decode(
        [29300], 513, 64, 1024, 64, 1152, 16)
    assert flops == 2.0 * 513 * 64 * (1088 + 1024)
    assert nbytes == 640 * 1152 * 2 + 64 * (1088 + 1024) * 2  # 5 chunks of 128
    short, _ = sparse_roofline.latent_window_decode(
        [99], 513, 64, 1024, 64, 1152, 16)
    assert short == 2.0 * 100 * 64 * (1088 + 1024)


def reader(name):
    return named.load((BENCH,), "layer_metrics", name).read


def cell(**changes):
    with open(os.path.join(BENCH, "configs", "dots3-note-prev.json")) as f:
        config = dict(json.load(f), **changes)
    return types.SimpleNamespace(config=config, name="synthetic")


LOG = [{"error": None, "in_window": True, "done": True,
        "usage": {"prompt_tokens": 29100, "completion_tokens": 400}}]
KERNEL = "paged_decode_attention_latent_window"


def test_latent_window_attn_roofline_on_a_synthetic_capture():
    lanes, calls = 32.0, 600
    flops, nbytes = sparse_roofline.latent_window_decode(
        [29300], 513, 64, 1024, 64, 1152, 16)
    least = max(nbytes / 819e9, flops / 197e12) * calls * lanes
    ctx = {
        "cell": cell(), "info": {"kind": "TPU v5 lite"}, "log": LOG,
        "trace": {"op_self_s": {KERNEL + ".1 bf16[32,64,1024]": 4 * least},
                  "op_count": {KERNEL: calls}},
        "after": {"decode": {"steps": 100, "batch_occupancy": lanes}},
        "before": {"decode": {"steps": 0, "batch_occupancy": 0.0}},
    }
    read = reader("latent_window_attn_roofline")
    assert read(ctx) == pytest.approx(25.0)
    assert read(dict(ctx, trace=None)) is None
    assert read(dict(ctx, cell=cell(swa_kv_lora_rank=None))) is None
    ctx["trace"]["op_self_s"].clear()  # the parent runs no such kernel
    assert read(ctx) is None


def test_sparse_attn_roofline_on_a_synthetic_capture():
    lanes, passes = 32.0, 200
    flops, nbytes = sparse_roofline.chosen_rows_decode(
        [29300], 2048, 128, 512, 64, 640)
    # three full layers a pass, three kernel calls (sliding layers) a pass
    least = max(nbytes / 819e9, flops / 197e12) * passes * 3 * lanes
    acc = {"scoped": True, "unnamed_programs": [], "busy_s": 50.0,
           "by_component": {"attn_select": 1.0, "attn_core": 9.0},
           "table": {
               "jit_fn_multi_decode_16": {"attn_select": 3 * least,
                                          "attn_core": 5 * least,
                                          "moe_experts": 7.0},
               "jit_body_decode": {"attn_core": 2 * least},
               "jit_fn_prefill_512": {"attn_core": 1e3}}}
    ctx = {
        "cell": cell(), "info": {"kind": "TPU v5 lite"}, "log": LOG,
        "scope_account": acc,
        "trace": {"op_self_s": {KERNEL: 1.0},
                  "op_count": {KERNEL: 3 * passes}},
        "after": {"decode": {"steps": 100, "batch_occupancy": lanes}},
        "before": {"decode": {"steps": 0, "batch_occupancy": 0.0}},
    }
    read = reader("sparse_attn_roofline")
    assert read(ctx) == pytest.approx(10.0)
    acc["by_component"].pop("attn_select")  # the parent names no such scope
    assert read(ctx) is None
    assert read(dict(ctx, scope_account=None)) is None
    assert read(dict(ctx, trace=None, scope_account=None)) is None


@pytest.mark.parametrize("name, scope", [
    ("dev_index_share", "attn_index"), ("dev_select_share", "attn_select")])
def test_scope_share_readers_read_the_scope_or_nothing(name, scope):
    acc = {"scoped": True, "unnamed_programs": [], "busy_s": 4.0,
           "by_component": {scope: 0.5, "attn_core": 1.0, "moe_experts": 2.5}}
    read = reader(name)
    assert read({"scope_account": acc}) == pytest.approx(12.5)
    acc["by_component"].pop(scope)
    assert read({"scope_account": acc}) is None
    assert read({"scope_account": None}) is None


def test_index_keep_share_reads_the_counters_or_nothing():
    read = reader("index_keep_share")
    snap = lambda s, k: {"engine": {"index_keys_scored": s,
                                    "index_keys_kept": k}}
    assert read({"before": snap(1000, 500), "after": snap(30300, 2548)}) \
        == pytest.approx(100.0 * 2048 / 29300)
    assert read({"before": {"replicas": [snap(0, 0), snap(0, 0)]},
                 "after": {"replicas": [snap(100, 10), snap(300, 30)]}}) \
        == pytest.approx(10.0)
    assert read({"before": snap(5, 5), "after": snap(5, 5)}) is None
    # the parent exports no such counters
    assert read({"before": {"engine": {"pages_total": 9}},
                 "after": {"engine": {"pages_total": 9}}}) is None


# --------------------------------------------------------------------------
# the tiny twin, end to end
# --------------------------------------------------------------------------

NEW = {"dev_index_share", "dev_select_share", "index_keep_share",
       "sparse_attn_roofline", "latent_window_attn_roofline"}


def test_the_twin_lists_what_the_real_cell_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TWIN, "BENCHMARK.json")) as f:
        twin = json.load(f)
    want = {m["name"] for m in real["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    assert {m["name"] for m in twin["per_layer"]} == want
    assert NEW <= want and len(want) == 17 + len(NEW)
    # the new metrics list the new cell alone; no other metric's list has it
    for m in real["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
        else:
            assert CELL not in m.get("workloads", [])
    assert [c["name"] for c in real["configs"]][-1] == "dots3-note-prev"
    assert len(real["configs"]) == len(real["workloads"]) == 5
    assert all(w["chips"] == 1 for w in real["workloads"])
    config = cell().config
    assert config["scopes"] == ["attn_window", "attn_latent_proj",
                                "moe_shared", "attn_index", "attn_select",
                                "attn_gate"]
    assert config["check"] == {"reference": "dots3", "driver": "dots3_pool",
                               "n_prefill": 3072, "n_decode": 47,
                               "pages_per_seq": 200}
    assert 24000 <= len(config["serving"]["system_prompt"].encode()) <= 26000
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        assert json.load(f)["params"] == {"clients": 32, "stagger_s": 0.45}


def test_the_file_keeps_the_catalog_rows_numbers():
    """Every number of the catalog entry's `config` under the same key, but
    the three keys `reduced` names."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "dots3-note-prev")
    config = cell().config
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}


def test_rehearsal_of_the_tiny_twin():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", "tiny-dots3.chat-decode", "--seed", "3000000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=600, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"index_keep_share", "prefix_hit_share",
            "decode_batch_occupancy"} <= set(line["metrics"])
    # contexts of a few hundred byte-tokens, 8 kept of each
    assert 0 < line["metrics"]["index_keep_share"]["value"] < 10
    # device metrics never come from a CPU run
    assert not {"sparse_attn_roofline", "latent_window_attn_roofline",
                "dev_index_share", "dev_select_share", "decode_step_dev_ms"} \
        & set(line["metrics"])
    check = json.loads(next(
        ln for ln in lines if ln.startswith("run.py: logit check ")
    )[len("run.py: logit check "):])
    assert check["ok"] and check["reference"] == "references/dots3"
    assert check["driver"] == "drivers/dots3_pool"
    assert check["compared"] >= 3
    # replies end at the length the seed drew (`serving.ignore_eos`), and a
    # stop token, which renders as nothing, is rare enough at 1,024 ids
    checks = json.loads(next(
        ln for ln in lines if ln.startswith("run.py: checks ")
    )[len("run.py: checks "):])
    assert checks["lengths_from_seed"] and checks["one_char_per_token"]
