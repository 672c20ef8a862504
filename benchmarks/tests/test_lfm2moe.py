"""The LFM2-8B-A1B configuration's own files (PR 47): `references/lfm2moe.py`
against `kafka_tpu.models.forward` at the tiny twin's size in float32, the
paged path through pages AND state slots with the configuration's driver,
what the driver reads off the slots (the check fails by name where a tail is
rounded or never written), the two readers the cell adds on synthetic input,
and the CPU rehearsal of the tiny twin under `benchmarks/tests/lfm2moe/`.
(`test_check_resolution.py` scans every file under `references/` for imports
of the program, this one included; the reference's `variants` are held in
`tests/test_lfm2_moe.py`, tier-1.)"""

import json
import os
import subprocess
import sys

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
import reference  # noqa: E402
from kafka_tpu.models import forward, init_params  # noqa: E402
from kafka_tpu.models.config import config_from_hf_json  # noqa: E402

TWIN = os.path.join(HERE, "lfm2moe")
CELL = "lfm2-8b-a1b.chat-decode"
lfm = named.load((BENCH,), "references", "lfm2moe")
driver = named.load((BENCH,), "drivers", "lfm2_pool")
IDS = np.random.RandomState(0).randint(0, 512, size=123)


@pytest.fixture(scope="module")
def tiny():
    cfg = config_from_hf_json(
        os.path.join(TWIN, "configs", "tiny-lfm2moe.json"))
    return cfg, init_params(cfg, jax.random.PRNGKey(1))


def test_reference_matches_program_forward(tiny):
    cfg, params = tiny
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, t: forward(
            p, cfg, t, jnp.arange(t.shape[1])[None]))(
                params, jnp.asarray(IDS)[None])
    ref = lfm.reference_logits(params, lfm.hyper(cfg), IDS,
                               list(range(len(IDS))))
    np.testing.assert_allclose(np.asarray(logits[0]), ref["logits"],
                               rtol=2e-4, atol=2e-4)
    assert np.isfinite(ref["raw_router_gap"]).all()


def test_pages_and_state_match_reference(tiny):
    cfg, params = tiny
    n_prefill = 97  # launches of 96 rows and of one, both padded
    with jax.default_matmul_precision("highest"):
        served = driver.served_logits(params, cfg, IDS, n_prefill,
                                      page_size=16, pages_per_seq=8)
    pos = list(range(n_prefill - 1, len(IDS)))
    ref = lfm.reference_logits(params, lfm.hyper(cfg), IDS, pos)
    res = reference.compare_logits(served, ref["logits"],
                                   np.full(len(pos), np.inf), tol=1e-4)
    assert res["ok"] and res["compared"] == len(pos), res


def test_the_check_reads_the_tail_off_its_slot(tiny, monkeypatch, capfd):
    """The logits cannot show a tail rounded to bfloat16 at the published
    widths, so the driver reads the slots: float32, every conv layer's tail
    written, most values past what bfloat16 holds."""
    from kafka_tpu.models import llama
    from kafka_tpu.runtime import kv_cache

    cfg, params = tiny
    driver.served_logits(params, cfg, IDS, 97, page_size=16, pages_per_seq=8)
    said = capfd.readouterr().err
    assert float(said.split("'tail_f32_share': ")[1].split("}")[0]) > 0.99
    _, pool = kv_cache.make_kv_pool_arrays(cfg, 3, 16, state_slots=3)
    assert set(pool) == {"v", "conv"}
    empty = driver.tail_report(pool)
    assert empty["lane_layers_written"] == 0 and empty["tail_f32_share"] == 0
    full = jax.random.normal(jax.random.PRNGKey(1), pool["conv"].shape)
    assert driver.tail_report(dict(pool, conv=full))["tail_f32_share"] > 0.99
    rounded = full.astype(jnp.bfloat16).astype(jnp.float32)
    assert driver.tail_report(dict(pool, conv=rounded))["tail_f32_share"] == 0
    # were the program to round the tail on its way into the slot, the check
    # fails by name (another pool size: a trace of its own)
    real = llama._write_state
    monkeypatch.setattr(
        llama, "_write_state",
        lambda leaf, layer, plan, new, old: real(
            leaf, layer, plan, jax.lax.reduce_precision(new, 8, 7), old))
    with pytest.raises(driver.ConvTailError):
        driver.served_logits(params, cfg, IDS, 97, page_size=16,
                             pages_per_seq=9)


def test_check_seeds_rounds_every_matrix_of_the_tree(tiny):
    """`check_seeds.int8_tree`: every stacked matrix (mixers, lead, experts,
    routers, the embedding) on at most 255 levels an output channel, the
    taps, the norms and the selection bias as they were."""
    import check_seeds

    cfg, params = tiny
    kept = jax.tree.map(np.asarray, params)
    rounded = check_seeds.int8_tree(jax.tree.map(jnp.array, params))
    conv, attn = rounded["attn"]["conv"], rounded["attn"]["full_attention"]
    for same, was in ((conv["conv_w"], kept["attn"]["conv"]["conv_w"]),
                      (rounded["layers"]["router_bias"],
                       kept["layers"]["router_bias"]),
                      (attn["ln_q"], kept["attn"]["full_attention"]["ln_q"])):
        np.testing.assert_array_equal(np.asarray(same), was)
    for leaf, was, axis in (
            (conv["w_in"], kept["attn"]["conv"]["w_in"], 1),
            (attn["wq"], kept["attn"]["full_attention"]["wq"], 1),
            (rounded["layers"]["wd"], kept["layers"]["wd"], 2),
            (rounded["dense_layers"]["wg"], kept["dense_layers"]["wg"], 1),
            (rounded["embed"], kept["embed"], 1)):
        got = np.asarray(leaf, np.float32)
        assert not np.array_equal(got, np.asarray(was, np.float32))
        column = np.moveaxis(got, axis, 0).reshape(got.shape[axis], -1)[:, 0]
        assert len(np.unique(column)) <= 255


def reader(name):
    return named.load((BENCH,), "layer_metrics", name)


def test_the_two_readers_read_their_source_or_nothing():
    acc = {"scoped": True, "unnamed_programs": [], "busy_s": 8.0,
           "by_component": {"conv_proj": 0.3, "conv_mix": 0.1,
                            "moe_experts": 5.0}}
    assert reader("dev_conv_share").read({"scope_account": acc}) \
        == pytest.approx(5.0)
    bare = {"scoped": True, "unnamed_programs": [], "busy_s": 8.0,
            "by_component": {"mlp": 8.0}}  # the parent names no such scope
    assert reader("dev_conv_share").read({"scope_account": bare}) is None
    assert reader("dev_conv_share").read({"scope_account": None}) is None
    ctx = {"before": {"state": {"state_tokens_matched": 1000,
                                "state_tokens_skipped": 400}},
           "after": {"state": {"state_tokens_matched": 9000,
                               "state_tokens_skipped": 8000}}}
    assert reader("conv_state_restore_share").read(ctx) == pytest.approx(95.0)
    parent = {"before": {"engine": {}}, "after": {"engine": {}}}
    assert reader("conv_state_restore_share").read(parent) is None


def test_the_twins_of_the_attention_readers_read_this_geometry():
    """`gqa64_attn_roofline`: the decode programs' calls only (as wide as
    `max_batch`), bytes at 32 / 8 x 64; `dev_gqa64_qk_norm_share`: the
    `qk_norm` scope.  Without the kernel or the scope: nothing."""
    import types

    import roofline

    config = {"num_attention_heads": 32, "num_key_value_heads": 8,
              "hidden_size": 2048, "num_hidden_layers": 14,
              "serving": {"page_size": 16, "max_batch": 32},
              "scopes": ["conv_proj", "conv_mix", "qk_norm"]}
    lanes = 30.0
    log = [{"error": None, "in_window": True, "done": True, "t_first": 1.0,
            "usage": {"prompt_tokens": 8000, "completion_tokens": 400}}]
    _, nbytes = roofline.paged_decode([8200], 32, 8, 64, 16)
    least = nbytes * lanes / 819e9
    wide = ("%paged_decode_attention.3 = bf16[32,32,512]{2,1,0} "
            "custom-call(bf16[32,32,512]{2,1,0} %q, bf16[131072,512] %k)")
    ctx = {
        "cell": types.SimpleNamespace(config=config, name="synthetic"),
        "info": {"kind": "TPU v5 lite"}, "trace": {},
        "kernel_events": [(wide, 2 * least), (wide, 2 * least),
                          (wide.replace("bf16[32,32,512]", "bf16[2,32,512]"),
                           5.0)],
        "after": {"decode": {"steps": 100, "batch_occupancy": lanes}},
        "before": {"decode": {"steps": 0, "batch_occupancy": 0.0}},
        "log": log,
    }
    assert reader("gqa64_attn_roofline").read(ctx) == pytest.approx(50.0)
    ctx["kernel_events"] = None  # no capture
    assert reader("gqa64_attn_roofline").read(ctx) is None
    acc = {"scoped": True, "unnamed_programs": [], "busy_s": 8.0,
           "by_component": {"qk_norm": 0.2, "moe_experts": 5.0}}
    assert reader("dev_gqa64_qk_norm_share").read({"scope_account": acc}) \
        == pytest.approx(2.5)
    bare = dict(acc, by_component={"mlp": 8.0})
    assert reader("dev_gqa64_qk_norm_share").read(
        {"scope_account": bare}) is None


def test_the_twin_lists_what_the_real_cell_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TWIN, "BENCHMARK.json")) as f:
        twin = json.load(f)
    want = {m["name"] for m in real["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    assert {m["name"] for m in twin["per_layer"]} == want
    new = {"dev_conv_share", "conv_state_restore_share",
           "gqa64_attn_roofline", "dev_gqa64_qk_norm_share"}
    assert new <= want
    assert all(m["workloads"] == [CELL] for m in real["per_layer"]
               if m["name"] in new)
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")) as f:
        config = json.load(f)
    assert list(config["reduced"]) == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 14
    assert set(config["scopes"]) == {"conv_proj", "conv_mix", "qk_norm"}
    assert config["serving"]["max_batch"] == 32
    check = config["check"]
    assert (check["reference"], check["driver"]) == ("lfm2moe", "lfm2_pool")
    assert check["n_prefill"] == lfm.N_PREFILL and check["n_decode"] >= 47
    assert lfm.TAIL == driver.TAIL
    cell = next(w for w in real["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "chat-decode")


def test_rehearsal_of_the_tiny_twin():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", "tiny-lfm2moe.chat-decode", "--seed", "3000000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"conv_state_restore_share", "prefix_hit_share",
            "decode_batch_occupancy"} <= set(line["metrics"])
    assert line["metrics"]["conv_state_restore_share"]["value"] >= 95.0
    # device metrics never come from a CPU run
    assert not {"dev_conv_share", "decode_step_dev_ms"} & set(line["metrics"])
    check = json.loads(next(
        ln for ln in lines if ln.startswith("run.py: logit check ")
    )[len("run.py: logit check "):])
    assert check["ok"] and check["reference"] == "references/lfm2moe"
    assert check["driver"] == "drivers/lfm2_pool"
