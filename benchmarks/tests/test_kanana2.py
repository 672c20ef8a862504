"""The Kanana-2 configuration's own files (PR 31): `references/kanana2.py`
against `kafka_tpu.models.forward` at a tiny latent size in float32 (latent
attention with de-interleaved rotary pairs, the dense layer, sigmoid routing
with a selection bias, the shared branch), what it reports about router ties,
the POWER of the check (each of its `variants` must move the logits), the
latent-pool cache driver against the reference, the flop and byte count of a
latent decode call, the four readers the cell adds on synthetic input, and
the CPU rehearsal of the tiny twin under `benchmarks/tests/kanana2/`."""

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

import mla_roofline  # noqa: E402
import named  # noqa: E402
import reference  # noqa: E402
from kafka_tpu.models import forward, init_params  # noqa: E402
from kafka_tpu.models.config import config_from_hf_json  # noqa: E402

TWIN = os.path.join(HERE, "kanana2")
CELL = "kanana-2-30b-a3b.chat-decode"
kanana2 = named.load((BENCH,), "references", "kanana2")
latent_pool = named.load((BENCH,), "drivers", "latent_pool")


@pytest.fixture(scope="module")
def tiny():
    cfg = config_from_hf_json(
        os.path.join(TWIN, "configs", "tiny-kanana2.json"))
    return cfg, init_params(cfg, jax.random.PRNGKey(1))


IDS = np.random.RandomState(0).randint(0, 512, size=72)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "kanana2.py")) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+kafka_tpu", src, re.M)
    assert not re.search(r"^\s*(from|import)\s+(reference|paged_step)\b", src,
                         re.M)


def test_reference_matches_program_forward(tiny):
    cfg, params = tiny
    assert float(jnp.abs(params["layers"]["router_bias"]).min()) > 0
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, jnp.asarray(IDS)[None],
                            jnp.arange(len(IDS))[None])
    ref = kanana2.reference_logits(params, kanana2.hyper(cfg), IDS,
                                   list(range(len(IDS))))
    np.testing.assert_allclose(np.asarray(logits[0]), ref["logits"],
                               rtol=2e-4, atol=2e-4)
    raw = ref["raw_router_gap"]
    assert raw.shape == (len(IDS),) and (raw >= 0).all()
    np.testing.assert_allclose(
        ref["router_gap"],
        raw * reference.ROUTER_TIE_MARGIN / kanana2.ROUTER_FLIP_MARGIN,
        rtol=1e-6)
    assert kanana2.COMPARE_SKIPS_UNDER == reference.ROUTER_TIE_MARGIN


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_latent_pool_driver_matches_the_reference(tiny, backend):
    """Prefill, then decode through the pool the engine would allocate
    (absorbed form; Pallas interpreted): the check's served side."""
    cfg, params = tiny
    n_prefill = 48
    served = latent_pool.served_logits(
        params, cfg.replace(attention_backend=backend), IDS, n_prefill,
        page_size=16, pages_per_seq=5)
    pos = list(range(n_prefill - 1, len(IDS)))
    ref = kanana2.reference_logits(params, kanana2.hyper(cfg), IDS, pos)
    res = reference.compare_logits(served, ref["logits"], ref["router_gap"],
                                   tol=1e-4)
    assert res["ok"] and res["compared"] >= reference.MIN_COMPARED, res


def rel_rms(a, b):
    return float(np.sqrt(np.mean((a - b) ** 2)) / np.sqrt(np.mean(b ** 2)))


def test_every_variant_moves_the_logits(tiny):
    """Were the program to make one of these mistakes, the logits at every
    compared position move by more than twice the tolerance."""
    cfg, params = tiny
    hp = kanana2.hyper(cfg)
    pos = list(range(48, 72))
    ref = kanana2.reference_logits(params, hp, IDS, pos)["logits"]
    names = set()
    for name, variant in kanana2.variants(hp).items():
        got = kanana2.reference_logits(params, variant, IDS, pos)["logits"]
        worst = min(rel_rms(g, r) for g, r in zip(got, ref))
        assert worst > 2 * kanana2.TOLERANCE["value"], (name, worst)
        names.add(name)
    assert names == {
        "bias_ignored_in_choice", "weights_not_renormalised", "scale_one",
        "no_shared_expert", "rope_not_deinterleaved", "latent_not_normed",
        "softmax_routing"}


def test_hyper_refuses_a_model_that_is_not_kanana_shaped(tiny):
    cfg, _ = tiny
    with pytest.raises(ValueError):
        kanana2.hyper(cfg.replace(kv_lora_rank=0, first_k_dense=0,
                                  shared_intermediate_size=0))
    with pytest.raises(ValueError):
        kanana2.hyper(cfg.replace(tie_word_embeddings=True))


# --------------------------------------------------------------------------
# the flop and byte count, and the four readers
# --------------------------------------------------------------------------

def test_latent_decode_counts_one_row_a_key_and_no_value_read():
    flops, nbytes = mla_roofline.latent_decode(
        [8300], 32, 512, 64, 640, 16)
    assert flops == 2.0 * 8300 * 32 * (576 + 512)
    rows = 519 * 16  # whole pages
    assert nbytes == rows * 640 * 2 + 32 * (576 + 512) * 2
    # a 576-lane row (two tokens packed, or no padding) reads a tenth less
    _, packed = mla_roofline.latent_decode([8300], 32, 512, 64, 576, 16)
    assert packed == rows * 576 * 2 + 32 * (576 + 512) * 2
    assert mla_roofline.latent_decode([0, -3], 32, 512, 64, 640, 16) \
        == (0.0, 0.0)


def reader(name):
    return named.load((BENCH,), "layer_metrics", name).read


def cell(**changes):
    with open(os.path.join(BENCH, "configs", "kanana-2-30b-a3b.json")) as f:
        config = dict(json.load(f), **changes)
    return types.SimpleNamespace(config=config, name="synthetic")


def test_mla_attn_roofline_on_a_synthetic_capture():
    lanes, calls = 32.0, 600
    flops, nbytes = mla_roofline.latent_decode([8300], 32, 512, 64, 640, 16)
    # bandwidth-bound at the v5e's peaks: 0.41 ms of bytes, 0.09 ms of flops
    assert nbytes / 819e9 > flops / 197e12
    least = nbytes * calls * lanes / 819e9
    ctx = {
        "cell": cell(), "info": {"kind": "TPU v5 lite"},
        "trace": {"op_self_s": {
            "paged_decode_attention_latent.1 bf16[32,32,512]": 2 * least,
            "paged_decode_attention.1 bf16[16,32,512]": 123.0},
            "op_count": {"paged_decode_attention_latent": calls,
                         "paged_decode_attention": 200}},
        "after": {"decode": {"steps": 100, "batch_occupancy": lanes},
                  "engine": {"kv_bytes_per_token": 7680}},
        "before": {"decode": {"steps": 0, "batch_occupancy": 0.0}},
        "log": [{"error": None, "in_window": True, "done": True,
                 "usage": {"prompt_tokens": 8100, "completion_tokens": 400}}],
    }
    read = reader("mla_attn_roofline")
    assert read(ctx) == pytest.approx(50.0)
    # the parent's capture holds no such kernel and exports no such gauge
    gone = dict(ctx, after={"decode": ctx["after"]["decode"],
                            "engine": {"pages_total": 9}})
    assert read(gone) is None
    ctx["trace"]["op_self_s"].pop(
        "paged_decode_attention_latent.1 bf16[32,32,512]")
    assert read(ctx) is None
    assert read(dict(ctx, trace=None)) is None
    assert read(dict(ctx, cell=cell(kv_lora_rank=None))) is None


@pytest.mark.parametrize("name, scope", [
    ("dev_latent_proj_share", "attn_latent_proj"),
    ("dev_moe_shared_share", "moe_shared")])
def test_scope_share_readers_read_the_scope_or_nothing(name, scope):
    acc = {"scoped": True, "unnamed_programs": [], "busy_s": 4.0,
           "by_component": {scope: 0.5, "attn_core": 1.0, "moe_experts": 2.5}}
    read = reader(name)
    assert read({"scope_account": acc}) == pytest.approx(12.5)
    acc["by_component"].pop(scope)  # the parent names no such scope
    assert read({"scope_account": acc}) is None
    assert read({"scope_account": None}) is None


def test_kv_bytes_per_token_reads_the_gauge_or_nothing():
    read = reader("kv_bytes_per_token")
    assert read({"after": {"engine": {"kv_bytes_per_token": 7680}}}) == 7680.0
    assert read({"after": {"replicas": [
        {"engine": {"kv_bytes_per_token": 7680}},
        {"engine": {"kv_bytes_per_token": 7680}}]}}) == 7680.0
    assert read({"after": {"engine": {"pages_total": 9}}}) is None  # parent
    assert read({"after": {}}) is None


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
    new = {"mla_attn_roofline", "dev_latent_proj_share",
           "dev_moe_shared_share", "kv_bytes_per_token"}
    assert new <= want and len(want) == 17 + len(new)
    # the new metrics list the new cell alone; no other metric's list has it
    for m in real["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
        else:
            assert CELL not in m.get("workloads", [])
    config = cell().config
    assert config["scopes"] == ["attn_latent_proj", "moe_shared"]
    assert config["check"]["driver"] == "latent_pool"
    assert config["check"]["n_prefill"] + config["check"]["n_decode"] > 3 * 512
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        assert json.load(f)["params"] == {"clients": 32, "stagger_s": 0.45}


def test_rehearsal_of_the_tiny_twin():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", "tiny-kanana2.chat-decode", "--seed", "3000000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"kv_bytes_per_token", "prefix_hit_share",
            "decode_batch_occupancy"} <= set(line["metrics"])
    # 3 layers x (32 latent + 128 padded rotary lanes) x float32
    assert line["metrics"]["kv_bytes_per_token"]["value"] == 3 * 160 * 4
    # device metrics never come from a CPU run
    assert not {"mla_attn_roofline", "dev_latent_proj_share",
                "dev_moe_shared_share", "decode_step_dev_ms"} \
        & set(line["metrics"])
    check = json.loads(next(
        ln for ln in lines if ln.startswith("run.py: logit check ")
    )[len("run.py: logit check "):])
    assert check["ok"] and check["reference"] == "references/kanana2"
    assert check["driver"] == "drivers/latent_pool"
    assert check["compared"] >= 3
