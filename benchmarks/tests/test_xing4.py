"""The Xing4.0-29B-A4B configuration's own files (PR 56): the reference
against `kafka_tpu.models.forward` at the tiny twin's size, the readers the
cell adds on synthetic input (each reads its source or nothing), the count of
`hc_roofline.py`, what the tiny twin lists against the real cell, and the CPU
rehearsal of the twin under `benchmarks/tests/xing4/`.
(`test_check_resolution.py` scans every file under `references/` for imports
of the program; the paged path through the latent pool on both backends, the
reference's `variants`, the engine and the loader are held in
`tests/test_xing4.py`, tier-1.)"""

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

TWIN = os.path.join(HERE, "xing4")
CELL = "xing4.0-29b-a4b.chat-decode"
NEW = {"dev_hc_share", "hc_stream_roofline", "yarn_mla_attn_roofline"}
xing4 = named.load((BENCH,), "references", "xing4")


def reader(name):
    return named.load((BENCH,), "layer_metrics", name)


def test_the_reference_is_forward_at_the_twins_size():
    import jax
    import jax.numpy as jnp

    from kafka_tpu.models import forward, init_params
    from kafka_tpu.models.config import config_from_hf_json

    cfg = config_from_hf_json(
        os.path.join(TWIN, "configs", "tiny-xing4.json")).replace(
            dtype="float32")
    assert (cfg.hc_mult, cfg.first_k_dense, cfg.num_layers) == (4, 2, 6)
    params = init_params(cfg, jax.random.PRNGKey(0))
    ids = np.random.RandomState(0).randint(0, 512, size=72)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, jnp.asarray(ids)[None],
                            jnp.arange(72)[None])
    pos = list(range(36, 72))  # past the twin's original context of 32
    got = xing4.reference_logits(params, xing4.hyper(cfg), ids, pos)
    a, b = np.asarray(logits[0], np.float64)[pos], got["logits"]
    err = np.sqrt(np.mean((a - b) ** 2, -1)) / np.sqrt(np.mean(b ** 2, -1))
    assert err.max() < 1e-4
    # the scale and the frequencies are the program's own, to the digit
    hp = xing4.hyper(cfg)
    assert xing4.softmax_scale(hp) == pytest.approx(
        cfg.latent_softmax_scale())
    from kafka_tpu.ops.rope import kind_frequencies
    inv, factor = kind_frequencies(cfg, "full_attention")
    np.testing.assert_allclose(xing4.yarn_inv_freq(hp), np.asarray(inv),
                               rtol=1e-6)
    assert factor == 1.0


def _acc(by_component, busy=10.0):
    return {"scoped": True, "unnamed_programs": [], "busy_s": busy,
            "by_component": by_component}


def test_the_scope_readers_read_their_source_or_nothing():
    acc = _acc({"hc_map": 0.3, "hc_mix": 0.2, "mlp": 6.0, "attn_core": 3.5})
    assert reader("dev_hc_share").read({"scope_account": acc}) \
        == pytest.approx(5.0)
    bare = _acc({"mlp": 10.0})  # the parent: no such scope
    assert reader("dev_hc_share").read({"scope_account": bare}) is None
    assert reader("dev_hc_share").read({"scope_account": None}) is None


def test_hc_stream_roofline_counts_rows_from_the_captures_own_launches():
    import hc_roofline

    n, c, layers, lanes = 4, 3584, 8, 32
    config = {"hc_mult": n, "hidden_size": c, "num_hidden_layers": layers,
              "serving": {"max_batch": lanes}}
    modules = {
        "jit_fn_multi_decode_16(11)": {"count": 3, "loops": 48,
                                       "total_s": 1.0, "kernels": {}},
        "jit_body_decode(12)": {"count": 5, "loops": 5, "total_s": 0.1,
                                "kernels": {}},
        "jit_fn_bprefill_512x2(13)": {"count": 2, "loops": 2, "total_s": 0.2,
                                      "kernels": {}},
        "jit_fn_prefill_256(14)": {"count": 1, "loops": 1, "total_s": 0.1,
                                   "kernels": {}},
        "jit_fn_state_copy(15)": {"count": 9, "loops": 0, "total_s": 0.0,
                                  "kernels": {}},
    }
    sites = 2 * layers
    nbytes = sites * (53 * hc_roofline.site(lanes, n, c)[1]
                      + 2 * hc_roofline.site(1024, n, c)[1]
                      + hc_roofline.site(256, n, c)[1])
    least = nbytes / 819e9
    ctx = {"cell": types.SimpleNamespace(name="synthetic", config=config),
           "info": {"kind": "TPU v5 lite"},
           "trace": {"modules": modules},
           "scope_account": _acc({"hc_map": 3 * least, "hc_mix": least,
                                  "mlp": 1.0}, busy=1.0 + 4 * least)}
    assert reader("hc_stream_roofline").read(ctx) == pytest.approx(25.0)
    # whatever implements a site moves the same bytes: one fused scope
    # instead of two, the same seconds, the same share
    fused = dict(ctx, scope_account=_acc({"hc_map": 4 * least, "mlp": 1.0},
                                         busy=1.0 + 4 * least))
    assert reader("hc_stream_roofline").read(fused) == pytest.approx(25.0)
    for parent in (dict(ctx, scope_account=_acc({"mlp": 1.0}, busy=1.0)),
                   dict(ctx, scope_account=None), dict(ctx, trace=None),
                   dict(ctx, cell=types.SimpleNamespace(
                       name="x", config=dict(config, hc_mult=1)))):
        assert reader("hc_stream_roofline").read(parent) is None
    # the count itself: (2n + 2) C values a row, Phi + the norm's weight once
    flops, moved = hc_roofline.site(lanes, n, c)
    assert moved == 2 * (lanes * 10 * c + n * c * 25)
    assert flops == lanes * (2 * n * c * 24 + 2 * n * c * 7)


def test_yarn_mla_attn_roofline_is_mla_attn_rooflines_reader():
    assert reader("yarn_mla_attn_roofline").read.__code__.co_filename \
        == reader("mla_attn_roofline").read.__code__.co_filename


def test_the_twin_lists_what_the_real_cell_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TWIN, "BENCHMARK.json")) as f:
        twin = json.load(f)
    want = {m["name"] for m in real["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    assert {m["name"] for m in twin["per_layer"]} == want
    assert NEW <= want
    assert all(m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
               for m in real["per_layer"] if m["name"] in NEW)
    with open(os.path.join(BENCH, "configs", "xing4.0-29b-a4b.json")) as f:
        config = json.load(f)
    assert list(config["reduced"]) == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8
    assert config["serving"]["max_batch"] == 32
    assert config["serving"]["prefill_buckets"] == [64, 256, 512]
    assert config["expect"]["attention_backend"] == "pallas"
    check = config["check"]
    assert (check["reference"], check["driver"]) == ("xing4", "xing4_pool")
    assert (check["n_prefill"], check["n_decode"]) == (4608, 47)
    assert check["n_prefill"] > config["rope_scaling"][
        "original_max_position_embeddings"]
    cell = next(w for w in real["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "chat-decode")
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        params = json.load(f)["params"]
    assert params == {"clients": 32, "stagger_s": 0.45}


def test_rehearsal_of_the_tiny_twin():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", "tiny-xing4.chat-decode", "--seed", "3000000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"prefix_hit_share", "decode_batch_occupancy"} <= set(
        line["metrics"])
    # device metrics never come from a CPU run
    assert not (NEW | {"decode_step_dev_ms"}) & set(line["metrics"])
    check = json.loads(next(
        ln for ln in lines if ln.startswith("run.py: logit check ")
    )[len("run.py: logit check "):])
    assert check["ok"] and check["reference"] == "references/xing4"
    assert check["driver"] == "drivers/xing4_pool"
