"""K-EXAONE's block on the served path (`model_type` "exaone_moe"): grouped-
query attention with QK-norm whose full-attention layers do not rotate, a
128-key-style sliding window far below a prefill bucket and a kernel chunk, a
dense lead that is a SLIDING layer of a pattern the routed layers meet offset,
and a held share of sigmoid-routed experts beside a shared one, on the GQA
tree.  Everything numeric is held to `benchmarks/references/kexaone.py`, the
plain float32 reference that imports nothing of the program."""

import json
import os
import sys
from functools import partial

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, BENCH)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import named  # noqa: E402
import paged_step  # noqa: E402
from kafka_tpu.models import forward, init_params  # noqa: E402
from kafka_tpu.models.config import (  # noqa: E402
    GLOBAL,
    WINDOWED,
    ModelConfig,
    UnsupportedConfigError,
    config_from_hf_json,
)
from kafka_tpu.models.ffn import _moe_block  # noqa: E402
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine  # noqa: E402
from kafka_tpu.runtime.engine import (  # noqa: E402
    RoutedTreeUnsupported,
    WindowedAttentionUnsupported,
)
from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays  # noqa: E402
from kafka_tpu.runtime.planner import (  # noqa: E402
    kv_pool_bytes_per_device,
    weight_bytes_per_device,
)

kexaone = named.load((BENCH,), "references", "kexaone")
PUBLISHED = os.path.join(BENCH, "configs", "k-exaone-236b-a23b.json")

WINDOW = 24
PATTERN = ((WINDOWED,) * 3 + (GLOBAL,)) * 3
BASE = dict(
    name="tiny-kexaone", vocab_size=128, hidden_size=32, intermediate_size=16,
    num_layers=10, num_heads=4, num_kv_heads=2, head_dim=64,
    tie_word_embeddings=False, dtype="float32", rope_theta=1e6,
    num_experts=4, num_experts_per_tok=3, num_experts_routed=16,
    expert_offset=4, moe_scoring="sigmoid", routed_scaling_factor=2.5,
    first_k_dense=1, dense_intermediate_size=48, shared_intermediate_size=16,
    layer_types=PATTERN[:10], sliding_window=WINDOW, qk_norm=True,
    unrotated_kinds=(GLOBAL,), nextn_predict_layers=1)


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(**BASE)
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


IDS = np.random.RandomState(0).randint(0, 128, size=206)


def rel_rms(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return (np.sqrt(np.mean((a - b) ** 2, axis=-1))
            / np.sqrt(np.mean(b ** 2, axis=-1)))


def program_logits(cfg, params, ids):
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, jnp.asarray(ids)[None],
                            jnp.arange(len(ids))[None])
    return np.asarray(logits[0])


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def test_forward_matches_the_reference(model):
    cfg, params = model
    ids = IDS[:60]  # two and a half windows
    ref = kexaone.reference_logits(params, kexaone.hyper(cfg), ids,
                                   list(range(len(ids))))
    np.testing.assert_allclose(program_logits(cfg, params, ids),
                               ref["logits"], rtol=2e-4, atol=2e-4)
    raw = ref["raw_router_gap"]
    assert raw.shape == (len(ids),) and (raw >= 0).all()
    np.testing.assert_allclose(
        ref["router_gap"],
        raw * kexaone.COMPARE_SKIPS_UNDER / kexaone.ROUTER_FLIP_MARGIN,
        rtol=1e-6)


def test_the_tree_is_the_lead_and_routed_one_on_gqa_leaves(model):
    cfg, params = model
    dense, routed = params["dense_layers"], params["layers"]
    assert set(dense) == {"ln_attn", "ln_mlp", "ln_q", "ln_k", "wq", "wk",
                          "wv", "wo", "wg", "wu", "wd"}
    assert dense["wg"].shape == (1, 32, 48) and dense["ln_q"].shape == (1, 64)
    assert routed["wq"].shape == (9, 32, 4, 64)
    assert routed["wk"].shape == routed["wv"].shape == (9, 32, 2, 64)
    # the router and its bias keep the published width, the experts are held
    assert routed["router"].shape == (9, 32, 16)
    assert routed["router_bias"].shape == (9, 16)
    assert routed["wg"].shape == (9, 4, 32, 16)
    assert routed["ws_d"].shape == (9, 16, 32)
    # off-identity, so that a program that skips one fails the check
    assert float(jnp.std(routed["ln_q"])) > 0.1
    assert float(jnp.std(routed["router_bias"])) > 0.05


def test_the_lead_is_a_sliding_layer_and_the_period_starts_offset(model):
    cfg, params = model
    assert cfg.kind_of(0) == WINDOWED
    # routed layers 1..9 are L L G | L L L G | L L: one lone layer, then
    # whole periods of (L G L L) - not the published period's own phase
    assert cfg.pattern == (1, (WINDOWED, GLOBAL, WINDOWED, WINDOWED))
    full = cfg.replace(num_layers=48, layer_types=PATTERN * 4)
    assert full.pattern == (3, (WINDOWED,) * 3 + (GLOBAL,))
    # one scan over the two whole periods, the lead and the lone layer ahead
    jaxpr = jax.make_jaxpr(
        lambda p, i, q: forward(p, cfg, i, q)[0]
    )(params, jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None])
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 2
    # a program that began the period anew after the lead (L | L L L G ..)
    # is another model: the reference told so moves the logits
    hp = kexaone.hyper(cfg)
    ids = IDS[:60]
    pos = list(range(40, 60))
    want = kexaone.reference_logits(params, hp, ids, pos)["logits"]
    anew = dict(hp, layer_types=[WINDOWED] + list(PATTERN[:9]))
    got = kexaone.reference_logits(params, anew, ids, pos)["logits"]
    assert rel_rms(got, want).min() > 0.05
    # and so is one whose lead layer is a full-attention layer
    lead_full = dict(hp, layer_types=[GLOBAL] + hp["layer_types"][1:])
    got = kexaone.reference_logits(params, lead_full, ids, pos)["logits"]
    assert rel_rms(got, want).min() > 0.05


@pytest.mark.parametrize("variant", sorted(kexaone.variants({})))
def test_each_mechanism_matters(model, variant):
    """Zeroing QK-norm, rotating the full layers, dropping the window (or
    moving it by a key), the selection bias, the scale, the shared expert or
    the held share's rule breaks the match with the program; and rounding
    the accumulator to bfloat16 costs far more than float32 noise."""
    cfg, params = model
    ids = IDS[:72]
    pos = list(range(48, 72))  # past two windows
    served = program_logits(cfg, params, ids)[pos]
    hp = kexaone.hyper(cfg)
    assert rel_rms(served, kexaone.reference_logits(
        params, hp, ids, pos)["logits"]).max() < 1e-4
    got = kexaone.reference_logits(params, kexaone.variants(hp)[variant],
                                   ids, pos)["logits"]
    floor = 1e-3 if variant == "bf16_accumulate" else 0.02
    assert np.median(rel_rms(got, served)) > floor, variant


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_paged_prefill_then_decode_past_the_window(model, backend):
    """Through the paged pool as the engine drives it: a prefill chunk of
    192 rows (the window is 24: an eighth of the chunk, under half a kernel
    chunk of 8 pages of 8), then 14 decode steps at contexts 192-205, where
    a sliding layer's first chunk is chunk 2, not chunk 0.  On `pallas` the
    flash-prefill and both decode kernels run interpreted."""
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    n_prefill = 192
    served = paged_step.served_logits(params, cfg, IDS, n_prefill,
                                      page_size=8, pages_per_seq=27)
    pos = list(range(n_prefill - 1, len(IDS)))
    ref = kexaone.reference_logits(params, kexaone.hyper(cfg), IDS, pos)
    assert served.shape == ref["logits"].shape == (15, cfg.vocab_size)
    # float32 everywhere: no position's routing is near enough a tie to flip
    assert rel_rms(served, ref["logits"]).max() < 1e-4


def test_engine_is_token_exact_with_chunks_wider_than_the_window(model):
    cfg, params = model
    eng = InferenceEngine(
        cfg, params, EngineConfig(
            max_batch=4, page_size=8, num_pages=96, max_pages_per_seq=16,
            prefill_buckets=(8, 32, 64)), kv_dtype=jnp.float32)
    prompts = {"a": [int(t) for t in IDS[:70]], "b": [int(t) for t in IDS[70:75]],
               "c": [int(t) for t in IDS[80:113]]}
    for rid, p in prompts.items():
        eng.submit(GenRequest(request_id=rid, prompt_ids=p,
                              max_new_tokens=12))
    done = eng.run_to_completion()
    hp = kexaone.hyper(cfg)
    for rid, p in prompts.items():
        out = done[rid].output_ids
        ids = p + out
        ref = kexaone.reference_logits(
            params, hp, ids, list(range(len(p) - 1, len(ids) - 1)))
        assert out == [int(t) for t in np.argmax(ref["logits"], -1)], rid
    snap = eng.metrics.snapshot(eng)["engine"]
    assert snap["kv_bytes_per_token"] == 10 * 2 * 128 * 4
    assert eng.device_info["sliding_window"] == WINDOW


def test_kv_window_dead_share_counts_the_sliding_layers_rows(model):
    cfg, params = model
    eng = InferenceEngine(
        cfg, params, EngineConfig(
            max_batch=2, page_size=8, num_pages=64, max_pages_per_seq=16,
            prefill_buckets=(8, 32, 64)), kv_dtype=jnp.float32)
    eng.submit(GenRequest(request_id="a", prompt_ids=[int(t) for t in IDS[:90]],
                          max_new_tokens=20))
    for _ in range(6):
        eng.step()
    live = [s for s in eng.slots if s is not None]
    n = live[0].seq.length
    sliding = cfg.layers_of(WINDOWED)
    assert sliding == 8
    want = sliding * (n - WINDOW + 1) / (cfg.num_layers * n)
    assert eng.kv_window_dead_share() == pytest.approx(want)
    eng.run_to_completion()


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def test_the_eight_held_shares_add_up_to_the_uncut_layer():
    """One routed FFN of 16 experts, cut into eight shares of two: the
    shares' routed parts and the shared expert counted ONCE are the uncut
    layer, in the program (`_moe_block`) and in the reference (`_moe`)."""
    E, held, h, f = 16, 2, 32, 16
    whole = ModelConfig(**dict(BASE, num_experts=E, num_experts_routed=0,
                               expert_offset=0))
    lp = jax.tree.map(lambda a: a[2],
                      init_params(whole, jax.random.PRNGKey(5))["layers"])
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, h), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, _ = _moe_block(x, lp, whole)
        shared = uncut - _moe_block(
            x, lp, whole.replace(shared_intermediate_size=0))[0]
        routed = jnp.zeros_like(uncut)
        hp = dict(kexaone.hyper(whole.replace(
            num_experts_routed=E, num_experts=held)))
        ref_routed = np.zeros((18, h), np.float32)
        for i in range(E // held):
            share = whole.replace(num_experts=held, num_experts_routed=E,
                                  expert_offset=i * held)
            part = dict(lp, **{k: lp[k][i * held:(i + 1) * held]
                               for k in ("wg", "wu", "wd")})
            routed = routed + (_moe_block(x, part, share)[0] - shared)
            out, _ = kexaone._moe(x.reshape(18, h), part, dict(
                hp, expert_offset=i * held, skip_shared=True))
            ref_routed += np.asarray(out)
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(uncut),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        ref_routed, np.asarray(uncut - shared).reshape(18, h),
        rtol=1e-5, atol=1e-5)
    # a share is a real part: no one share is the layer
    assert float(jnp.abs(shared).mean()) > 1e-3
    assert float(jnp.abs(uncut - shared).mean()) > 1e-3


# ---------------------------------------------------------------------------
# the memory plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_memory_plan_bytes_are_the_trees_bytes(dtype):
    cfg = ModelConfig(**dict(BASE, dtype=dtype))
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert weight_bytes_per_device(cfg) == sum(
        a.nbytes for a in jax.tree.leaves(params))
    k_pool, v_pool = make_kv_pool_arrays(cfg, 24, 8)
    assert kv_pool_bytes_per_device(
        cfg, num_pages=24, page_size=8, kv_dtype=dtype
    ) == k_pool.nbytes + v_pool.nbytes
    assert k_pool.shape == (10, 24 * 8, 2 * 64)


def test_memory_plan_counts_the_benchmark_configuration():
    """The published widths, cut as the configuration's file says: shapes
    only (jax.eval_shape), 8.94 GB of weights and a 3.22 GB pool."""
    cfg = config_from_hf_json(PUBLISHED)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tree = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(shapes))
    assert weight_bytes_per_device(cfg) == tree == 8_935_605_760
    assert shapes["layers"]["wg"].shape == (5, 16, 6144, 2048)
    assert kv_pool_bytes_per_device(
        cfg, num_pages=8192, page_size=16) == 3_221_225_472
    assert cfg.kv_values_per_token * 2 == 24_576


# ---------------------------------------------------------------------------
# config_from_hf_json
# ---------------------------------------------------------------------------

def published():
    """The catalog's `config`, from the benchmark's copy with its three cuts
    undone and the benchmark's own groups taken away."""
    with open(PUBLISHED) as f:
        hf = json.load(f)
    for key in ("source", "reduced", "assumed", "deployment", "expect",
                "scopes", "check", "serving", "num_experts_published",
                "expert_share_offset", "torch_dtype"):
        hf.pop(key)
    return dict(hf, num_hidden_layers=48, num_experts=128, vocab_size=153600)


def load(tmp_path, **changes):
    hf = dict(published(), **changes)
    path = tmp_path / "k-exaone" / "config.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(hf))
    return config_from_hf_json(str(path))


def test_config_from_hf_json_reads_the_catalogs_keys(tmp_path):
    cfg = load(tmp_path)
    assert cfg.name == "k-exaone" and cfg.num_layers == 48
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim) == (6144, 64, 8, 128)
    assert cfg.layer_types == PATTERN * 4 and cfg.sliding_window == 128
    assert cfg.pattern == (3, (WINDOWED,) * 3 + (GLOBAL,))
    assert (cfg.first_k_dense, cfg.dense_intermediate_size) == (1, 18432)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.intermediate_size) == (128, 8, 2048)
    assert cfg.shared_intermediate_size == 2048
    assert cfg.moe_scoring == "sigmoid" and cfg.routed_scaling_factor == 2.5
    assert cfg.num_experts_routed == 0 and cfg.num_router_experts == 128
    assert cfg.rope_theta == 1e6 and cfg.rope_by_kind == ()
    assert cfg.qk_norm and cfg.unrotated_kinds == (GLOBAL,)
    assert cfg.nextn_predict_layers == 1
    assert cfg.vocab_size == 153600 and not cfg.tie_word_embeddings
    assert cfg.max_context == 262144 and cfg.rms_norm_eps == 1e-5
    assert not cfg.is_latent and not cfg.by_kind
    hash(cfg)  # a static argument of every jitted step
    # the benchmark's cut: six layers, 16 held of 128, an eighth of the rows
    cut = config_from_hf_json(PUBLISHED)
    assert cut.layer_types == PATTERN[:6] and cut.kind_of(0) == WINDOWED
    assert (cut.num_experts, cut.num_experts_routed, cut.expert_offset) == (
        16, 128, 0)
    assert cut.vocab_size == 19200
    for width in ("hidden_size", "num_heads", "num_kv_heads", "head_dim",
                  "dense_intermediate_size", "intermediate_size",
                  "shared_intermediate_size", "num_experts_per_tok",
                  "sliding_window", "rope_theta"):
        assert getattr(cut, width) == getattr(cfg, width), width


@pytest.mark.parametrize("changes, word", [
    ({"n_group": 4}, "n_group"),
    ({"topk_group": 2}, "topk_group"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"mlp_layer_types": ["dense", "sparse", "dense"] + ["sparse"] * 45},
     "mlp_layer_types"),
    ({"mlp_layer_types": ["sparse"] * 48}, "first_k_dense_replace"),
    ({"first_k_dense_replace": 2}, "first_k_dense_replace"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     "rope_parameters"),
    ({"layer_types": ["chunked_attention"] * 48}, "layer_types"),
    ({"sliding_window": None}, "sliding_window"),
    ({"num_experts_published": 64}, "num_experts_routed"),
])
def test_config_from_hf_json_refuses_by_key(tmp_path, changes, word):
    with pytest.raises(UnsupportedConfigError, match=word):
        load(tmp_path, **changes)


@pytest.mark.parametrize("changes, word", [
    ({"kv_lora_rank": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
      "v_head_dim": 8, "head_dim": 8}, "qk_norm"),
    ({"unrotated_kinds": ("mamba",)}, "unrotated_kinds"),
    ({"moe_scoring": "softmax"}, "num_experts_routed"),
    ({"first_k_dense": 10}, "first_k_dense"),
])
def test_model_config_refuses_by_field(changes, word):
    with pytest.raises(UnsupportedConfigError, match=word):
        ModelConfig(**dict(BASE, **changes))


# ---------------------------------------------------------------------------
# refusals, by name
# ---------------------------------------------------------------------------

def engine(cfg, params, mesh=None, **kw):
    defaults = dict(max_batch=2, page_size=8, num_pages=32,
                    max_pages_per_seq=8, prefill_buckets=(8, 32))
    defaults.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**defaults),
                           kv_dtype=jnp.float32, mesh=mesh)


def mesh_of(axes):
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:2]), tuple(axes))


@pytest.mark.parametrize("error, word, kw, axes, changes", [
    (UnsupportedConfigError, "num_nextn_predict_layers",
     {"speculative_k": 2}, None, {}),
    (WindowedAttentionUnsupported, "speculative verify",
     {"speculative_k": 2}, None, {"nextn_predict_layers": 0}),
    (WindowedAttentionUnsupported, "kv_quantize",
     {"kv_quantize": "int8"}, None, {}),
    (RoutedTreeUnsupported, "held share", {}, ("tp",), {}),
    (RoutedTreeUnsupported, "held share", {}, ("ep",), {}),
    (RoutedTreeUnsupported, "tp / ep / pp / sp mesh", {}, ("tp",),
     {"num_experts_routed": 0, "expert_offset": 0}),
    (RoutedTreeUnsupported, "tp / ep / pp / sp mesh", {}, ("pp",),
     {"num_experts_routed": 0, "expert_offset": 0}),
    (RoutedTreeUnsupported, "tp / ep / pp / sp mesh", {}, ("sp",),
     {"num_experts_routed": 0, "expert_offset": 0}),
])
def test_engine_refuses_by_name(model, error, word, kw, axes, changes):
    cfg, params = model
    if len(jax.devices()) < 2 and axes:
        pytest.skip("needs two devices")
    with pytest.raises(error, match=word):
        engine(cfg.replace(**changes), params,
               mesh=mesh_of(axes) if axes else None, **kw)


def test_int8_weights_and_checkpoints_are_refused_by_name(model):
    from kafka_tpu.models import quantize_params
    from kafka_tpu.models.loader import convert_hf_state_dict

    cfg, params = model
    with pytest.raises(NotImplementedError, match="dense_layers"):
        quantize_params(params, cfg)
    with pytest.raises(NotImplementedError, match="exaone_moe"):
        convert_hf_state_dict({}, cfg)


def test_hyper_refuses_a_model_that_is_not_kexaone_shaped(model):
    cfg, _ = model
    for changes in ({"qk_norm": False}, {"unrotated_kinds": ()},
                    {"tie_word_embeddings": True}):
        with pytest.raises(ValueError, match="K-EXAONE"):
            kexaone.hyper(cfg.replace(**changes))


# ---------------------------------------------------------------------------
# the device's names
# ---------------------------------------------------------------------------

def test_qk_norm_is_a_registered_scope_in_the_compiled_program(model):
    from kafka_tpu.tracing import DEVICE_SCOPES

    cfg, params = model
    assert "qk_norm" in DEVICE_SCOPES
    text = jax.jit(lambda p, i, q: forward(p, cfg, i, q)[0]).lower(
        params, jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None]
    ).compile().as_text()
    for scope in ("qk_norm", "attn_window", "moe_shared", "moe_router"):
        assert f"/{scope}/" in text, scope
    # a model without QK-norm has no such scope
    plain = ModelConfig(name="plain", vocab_size=128, dtype="float32")
    text = jax.jit(lambda p, i, q: forward(p, plain, i, q)[0]).lower(
        init_params(plain, jax.random.PRNGKey(0)),
        jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None]
    ).compile().as_text()
    assert "/qk_norm/" not in text


def test_flash_prefill_halves_its_q_block_for_the_wide_row_only():
    """Restated in PR 44 for the block sized by BYTES: a q block's rows are
    one lane tile wide whatever the merged row is, so the wide row halves
    nothing any more.  64 / 8 x 128 held 8 positions (the [1024, 1024] tile
    the chip refused at 16, PR 43) and holds 64 or more; no geometry that
    ran before holds fewer than it did."""
    from kafka_tpu.ops.pallas.flash_prefill import prefill_plan, q_block_rows

    served = {  # (Hq, Hkv, D, diff): the block before PR 44
        (64, 8, 128, False): 8,     # K-EXAONE
        (32, 4, 128, False): 32,    # Yi, Mellum2
        (40, 20, 64, True): 16,     # Phi-4-mini-flash
        (32, 8, 64, False): 32,     # llama-3.2-1b (chip_smoke)
    }
    for (hq, hkv, d, diff), before in served.items():
        for bucket in (64, 256, 512, 2048):
            plan = prefill_plan(bucket, hq, hkv, d, 2, diff=diff)
            qb = plan["q_block"]
            assert qb & (qb - 1) == 0 and bucket % qb == 0
            assert qb >= min(bucket, before)
            assert plan["group_lanes"] == 128  # no row wider than a tile
            assert qb <= q_block_rows(hq, 128, 2)  # the byte rule's cap
    assert prefill_plan(512, 64, 8, 128, 2)["q_block"] >= 64
    assert q_block_rows(256, 128, 2) == 16      # never under a bf16 tile
    # a bucket the cap does not divide takes the largest power of two
    # under it that does
    assert prefill_plan(192, 32, 4, 128, 2)["q_block"] == 64


# ---------------------------------------------------------------------------
# the kernels at 64 / 8 x 128, compiled for a described v5e (no chip)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


HQ, HKV, D, PS, P, B = 64, 8, 128, 16, 2048, 32


def _sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile_flash_prefill(one_chip, rows, hq, hkv, window):
    from kafka_tpu.ops.pallas import paged_prefill_attention

    sds = partial(_sds, one_chip)
    pool = sds((8192 * PS, hkv * D), jnp.bfloat16)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(
            lambda q, k, v, pr, st, cl: paged_prefill_attention(
                q, k, v, pr, st, cl, page_size=PS, window=window)
        ).lower(sds((rows, hq, D), jnp.bfloat16), pool, pool,
                sds((P,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32)
                ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # no block-diagonal expansion over the merged row, in or out (PR 44)
    assert f"[{rows * hq},{hkv * D}]" not in text
    return text


@pytest.mark.parametrize("window", [None, 128], ids=["global", "window128"])
@pytest.mark.parametrize("rows", [64, 256, 512])
def test_flash_prefill_compiles_at_64_over_8_heads(one_chip, rows, window):
    _compile_flash_prefill(one_chip, rows, HQ, HKV, window)


@pytest.mark.parametrize("window", [None, 1024], ids=["global", "window1024"])
def test_flash_prefill_compiles_yis_2048_row_bucket(one_chip, window):
    """32 / 4 x 128 (Yi; Mellum2 with its 1,024-key window) at the largest
    bucket either serves: the q block the byte rule gives it is 128
    positions, four times what it held."""
    _compile_flash_prefill(one_chip, 2048, 32, 4, window)


@pytest.mark.parametrize("window", [None, 128], ids=["global", "window128"])
def test_paged_decode_compiles_at_64_over_8_heads(one_chip, window):
    from kafka_tpu.ops.pallas import (
        paged_decode_attention,
        paged_decode_attention_window,
    )

    sds = partial(_sds, one_chip)
    pool = sds((8192 * PS, HKV * D), jnp.bfloat16)
    if window is None:
        fn = lambda q, k, v, pt, sl: paged_decode_attention(  # noqa: E731
            q, k, v, pt, sl, page_size=PS)
    else:
        fn = lambda q, k, v, pt, sl: paged_decode_attention_window(  # noqa: E731
            q, k, v, pt, sl, window=window, page_size=PS)
    # (the suite pins "highest"; the served program keeps DEFAULT, and the
    # kernel's bf16 MXU operands take no float32 contraction)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn).lower(
            sds((B, HQ, D), jnp.bfloat16), pool, pool, sds((B, P), jnp.int32),
            sds((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_token_dispatch_compiles_in_mellum2s_1536_row_launch(
        one_chip, monkeypatch):
    """The logit check's launch of `mellum2-12b-a2.5b` whole, at its real
    widths: XLA's gather of the 1,536 x 2,304 bf16 rows into expert order
    asked for 16.41 MiB of the 16 MiB of scoped VMEM a fusion has (PR 45, on
    the chip and here alike; models/ffn.py GATHER_VMEM_WINDOW)."""
    path = os.path.join(BENCH, "configs", "mellum2-12b-a2.5b.json")
    with open(path) as f:
        srv = json.load(f)["serving"]
    cfg = config_from_hf_json(path).replace(
        dtype=srv["dtype"], attention_backend="pallas")
    ps, rows = srv["page_size"], 1536

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    pools = on_chip(jax.eval_shape(
        lambda: make_kv_pool_arrays(cfg, 128, ps)))
    scalar = _sds(one_chip, (), jnp.int32)
    # (the program asks the backend whether to interpret its kernels)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.default_matmul_precision("default"):
        text = jax.jit(
            partial(paged_step.prefill_chunk, page_size=ps),
            static_argnums=(1,),
        ).lower(params, cfg, *pools, _sds(one_chip, (100,), jnp.int32),
                _sds(one_chip, (rows,), jnp.int32), scalar, scalar
                ).compile().as_text()
    assert text.count("gmm") >= 3  # the grouped matmuls, not the dense form
