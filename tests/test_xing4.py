"""Xing4.0-29B-A4B on the served path (ISSUE 56; `xing4_0`): a residual
stream of `hc_mult` rows a token mixed around every sublayer by per-token
mappings (sigmoid pre and post, an n x n matrix made doubly stochastic by
Sinkhorn rounds), around latent attention with a query low-rank under a
YaRN-scaled rotation, a dense lead and sigmoid-routed experts.

CPU, float32, tiny widths (n = 4 and n = 2, C = 64, 2 dense + 4 routed layers,
8 experts top-2, an original context of 32 so that every compared position
lies past it), seeded weights, against the plain reference
`benchmarks/references/xing4.py` (token-parallel, no cache, expanded keys and
values, imports nothing of kafka_tpu).  The kernels run interpreted.

TOLERANCES.  `forward` and the reference do the same float32 arithmetic in
another order: they agree to ~3e-6 relative RMS of the logits.  REF_TOL = 1e-4
leaves 30x room.  A MECHANISM taken out of the reference must move the logits
past the tolerance the chip's check uses (`ref.TOLERANCE`) at these sizes too,
under the seeded initialiser; the PRECISION variant (bfloat16 mappings) is
small at 6 layers of 64 and is held to 10 x REF_TOL here (its reading at the
published widths is PERF.md's).  Engine tests compare TOKENS, greedy, against
the uncached forward: exact.
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, forward, init_params
from kafka_tpu.models import residual
from kafka_tpu.models.config import (
    GLOBAL, RopeParams, UnsupportedConfigError, config_from_hf_json,
)
from kafka_tpu.models.loader import convert_hf_state_dict
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_TOL = 1e-4
CELL = "xing4.0-29b-a4b.chat-decode"

# the catalog row's `config` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072,
}
CUT = dict(num_hidden_layers=8)


def _load(folder, name):
    path = os.path.join(ROOT, "benchmarks", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, os.path.join(ROOT, "benchmarks"))  # drivers import paged_step
ref = _load("references", "xing4")
drv = _load("drivers", "xing4_pool")
YARN = RopeParams(rope_type="yarn", rope_theta=10000.0, factor=64.0,
                  original_max_position=32, beta_fast=32.0, beta_slow=1.0,
                  attention_factor=1.0, mscale_all_dim=1.0)


def tiny_cfg(n=4, backend="xla", **kw):
    base = dict(
        name="tiny-xing4", vocab_size=300, hidden_size=64,
        intermediate_size=24, num_layers=6, num_heads=4, num_kv_heads=4,
        head_dim=8, rope_theta=10000.0, rms_norm_eps=1e-6,
        rope_by_kind=((GLOBAL, YARN),), kv_lora_rank=32, q_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_interleave=True, first_k_dense=2, dense_intermediate_size=96,
        shared_intermediate_size=24, num_experts=8, num_experts_per_tok=2,
        moe_scoring="sigmoid", routed_scaling_factor=2.0,
        nextn_predict_layers=1, hc_mult=n, dtype="float32",
        tie_word_embeddings=False, attention_backend=backend)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


_UNCACHED = {}


def assert_greedy_consistent(cfg, params, prompt, out, pad=160):
    """`out` is the greedy continuation of `prompt` under ONE uncached
    forward, padded to a fixed length so that the module compiles it once."""
    seq = list(prompt) + list(out)
    assert len(seq) <= pad
    fn = _UNCACHED.setdefault(cfg, jax.jit(lambda p, x: jnp.argmax(forward(
        p, cfg, x, jnp.arange(pad, dtype=jnp.int32)[None])[0][0], axis=-1)))
    preds = np.asarray(fn(params, jnp.asarray(
        [seq + [0] * (pad - len(seq))], jnp.int32)))
    for i in range(len(prompt) - 1, len(seq) - 1):
        assert preds[i] == seq[i + 1], (
            f"divergence at position {i}: engine={seq[i + 1]} ref={preds[i]}")


def rel_rms(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return (np.sqrt(np.mean((a - b) ** 2, axis=-1))
            / np.sqrt(np.mean(b ** 2, axis=-1)))


def tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 300, n)]


# ---------------------------------------------------------------------------
# (a) the configuration: every key honoured or refused by name
# ---------------------------------------------------------------------------

def _cfg_of(tmp_path, **over):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(dict(PUBLISHED, **over)))
    return config_from_hf_json(str(path))


def test_config_from_hf_json_honours_every_key(tmp_path):
    cfg = _cfg_of(tmp_path)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps) == (4, 20, 1e-6)
    assert (cfg.hc_res_clamp_min, cfg.hc_res_clamp_max) == (-30.0, 30.0)
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads) == (3584, 40, 32)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank) == (768, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        128, 64, 128)
    assert cfg.head_dim == 64 and cfg.rope_interleave  # the HF default
    assert (cfg.first_k_dense, cfg.dense_intermediate_size) == (2, 9216)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (64, 4)
    assert (cfg.intermediate_size, cfg.shared_intermediate_size) == (
        1024, 1024)
    assert (cfg.moe_scoring, cfg.routed_scaling_factor) == ("sigmoid", 2.0)
    assert cfg.nextn_predict_layers == 1 and not cfg.tie_word_embeddings
    assert cfg.vocab_size == 131072 and cfg.max_context == 262144
    assert cfg.by_kind and cfg.kinds == (GLOBAL,) and not cfg.layer_types
    # the rotation: YaRN as the GLOBAL kind's table, NOT the Llama-3 form
    assert cfg.rope_scaling_factor is None
    rp = cfg.rope_of(GLOBAL)
    assert (rp.rope_type, rp.factor, rp.original_max_position) == (
        "yarn", 64.0, 4096)
    assert (rp.beta_fast, rp.beta_slow, rp.rope_theta) == (32.0, 1.0, 10000.0)
    assert rp.attention_factor == 1.0 and rp.mscale_all_dim == 1.0
    # the softmax scale: 192^-1/2 x (0.1 ln 64 + 1)^2 = 0.07217 x 2.0048
    m = 0.1 * np.log(64.0) + 1.0
    assert cfg.latent_softmax_scale() == pytest.approx(192 ** -0.5 * m * m)
    assert round(cfg.latent_softmax_scale(), 5) == 0.14468
    # pool row: Kanana-2's (512 + 64 padded to a 128-lane tile)
    assert cfg.kv_row_widths(GLOBAL) == (512, 128)
    assert _cfg_of(tmp_path, **CUT).kv_values_per_token * 2 == 10240
    # a config without the stream's keys has one row and no such op
    plain = _cfg_of(tmp_path, hc_mult=1)
    assert plain.hc_mult == 1


@pytest.mark.parametrize("over,key", [
    (dict(rope_scaling=dict(PUBLISHED["rope_scaling"], type="linear")),
     "rope_scaling type 'linear'"),
    (dict(rope_scaling=dict(PUBLISHED["rope_scaling"], mscale=0.707)),
     "mscale = 0.707"),
    (dict(rope_scaling=dict(PUBLISHED["rope_scaling"], truncate=False)),
     "rope_scaling keys"),
    (dict(layer_types=["full_attention"] * 40), "windowed kind"),
    (dict(swa_rope_theta=10000), "windowed kind"),
    (dict(kv_lora_rank=None, q_lora_rank=None), "hc_mult = 4"),
    (dict(hc_mult=0), "hc_mult = 0"),
    (dict(hc_sinkhorn_iters=0), "hc_sinkhorn_iters"),
    (dict(topk_method="greedy"), "topk_method"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(n_group=8), "n_group"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(moe_layer_freq=2), "moe_layer_freq"),
], ids=["rope_type", "mscale", "rope_key", "layer_types", "swa", "not_latent",
        "hc_mult_0", "no_rounds", "topk_method", "scoring", "groups",
        "unnormalised", "bias", "moe_freq"])
def test_config_refuses_by_key(tmp_path, over, key):
    with pytest.raises(UnsupportedConfigError, match=key):
        _cfg_of(tmp_path, **over)


def test_the_stream_stands_on_latent_attention_without_a_state():
    with pytest.raises(UnsupportedConfigError, match="hc_mult = 2"):
        ModelConfig(hc_mult=2)  # grouped-query attention
    with pytest.raises(UnsupportedConfigError, match="state-holding"):
        tiny_cfg(layer_types=("conv",) * 6, conv_L_cache=3)


def _mesh(**axes):
    from kafka_tpu.parallel import MeshConfig, make_mesh

    return make_mesh(MeshConfig(**axes))


ENGINE = dict(max_batch=4, page_size=16, num_pages=64, max_pages_per_seq=16,
              prefill_buckets=(16, 64), multi_step=4, attention_backend="xla")


@pytest.mark.parametrize("mesh", [dict(tp=2), dict(pp=2)], ids=["tp", "pp"])
def test_engine_refuses_a_mesh_by_name(model, mesh):
    cfg, params = model
    with pytest.raises(UnsupportedConfigError, match="hc_mult = 4 on a mesh"):
        InferenceEngine(cfg, params, EngineConfig(**ENGINE),
                        mesh=_mesh(**mesh))


def test_speculative_k_is_refused_with_the_modules_reason(model):
    cfg, params = model
    with pytest.raises(UnsupportedConfigError,
                       match="multi-token-prediction module is recorded and "
                             "not built"):
        InferenceEngine(cfg, params,
                        EngineConfig(**dict(ENGINE, speculative_k=2)))


# ---------------------------------------------------------------------------
# (b) the mappings
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 4])
def test_sinkhorn_is_doubly_stochastic_for_inputs_clamped_at_both_ends(n):
    cfg = tiny_cfg(n=n)
    rng = np.random.RandomState(3)
    logits = rng.normal(0, 3, (5, 7, n * n)).astype(np.float32)
    logits[0, 0] = 100.0                      # every entry over the clamp
    logits[0, 1] = -100.0                     # every entry under it
    logits[0, 2, ::2] = 45.0                  # both ends in one matrix
    logits[0, 2, 1::2] = -45.0
    logits[0, 3] = 2.0 * np.eye(n).reshape(-1) + 31.0
    m = residual._sinkhorn(jnp.asarray(logits), cfg)
    mat = np.stack([np.stack([np.asarray(v) for v in row], -1) for row in m],
                   -2)  # [..., i, j]
    assert mat.shape == (5, 7, n, n) and np.isfinite(mat).all()
    assert (mat >= 0).all()
    # clamped at either end or at both, the rounds have converged: rows AND
    # columns sum to 1
    np.testing.assert_allclose(mat[0, :4].sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(mat[0, :4].sum(-2), 1.0, atol=1e-4)
    # logits of N(0, 3^2) have NOT after 20 rounds (rows are a few percent
    # off; the columns, normalised last, are exact): the program runs the 20
    # the config asks for and no more
    np.testing.assert_allclose(mat.sum(-2), 1.0, atol=1e-4)
    np.testing.assert_allclose(mat.sum(-1), 1.0, atol=0.05)
    # and it is the reference's, entry for entry
    hp = dict(ref.hyper(cfg))
    want = np.asarray(ref.sinkhorn(
        jnp.asarray(logits).reshape(-1, n, n), hp)).reshape(mat.shape)
    np.testing.assert_allclose(mat, want, atol=1e-6)
    # the clamp binds: 100 and 31 + 2 I give what 30 gives
    flat = residual._sinkhorn(jnp.full((n * n,), 30.0), cfg)
    assert np.asarray(m[0][0])[0, 0] == pytest.approx(
        float(flat[0][0]), abs=1e-6)


def test_all_the_rounds_are_in_the_program(model):
    cfg, _ = model
    jaxpr = str(jax.make_jaxpr(lambda x: residual._sinkhorn(x, cfg))(
        jnp.zeros((3, 16))))
    # a round: n row reciprocals and n column reciprocals
    assert jaxpr.count(" div ") == 2 * 4 * cfg.hc_sinkhorn_iters == 160
    assert "while" not in jaxpr and "cond" not in jaxpr  # no early exit


def test_one_row_with_unit_mappings_is_the_plain_residual():
    """n = 1, H_pre = H_res = H_post = 1: the reference's stream is `h +
    F(norm(h))`, which is what `forward` runs for `hc_mult` 1 (no leaf, no
    op)."""
    cfg = tiny_cfg(n=1)
    params = init_params(cfg, jax.random.PRNGKey(1))
    assert not any(k.startswith("hc_") for k in params["layers"])
    ids = tokens(70, seed=1)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, jnp.asarray(ids)[None],
                            jnp.arange(70)[None])

    def unit(n_layers):
        # sigmoid(40) = 1; 2 sigmoid(0) = 1; Sinkhorn of a 1 x 1 matrix = 1
        out = {}
        for site in ("attn", "mlp"):
            out[f"hc_{site}_phi"] = jnp.zeros((n_layers, 64, 3))
            out[f"hc_{site}_bias"] = jnp.tile(
                jnp.asarray([40.0, 0.0, 0.0]), (n_layers, 1))
            out[f"hc_{site}_alpha"] = jnp.zeros((n_layers, 3))
            out[f"hc_{site}_norm"] = jnp.ones((n_layers, 64))
        return out

    with_maps = dict(params,
                     layers={**params["layers"], **unit(4)},
                     dense_layers={**params["dense_layers"], **unit(2)})
    pos = list(range(40, 70))
    got = ref.reference_logits(with_maps, ref.hyper(cfg), ids, pos)
    assert rel_rms(np.asarray(logits[0])[pos], got["logits"]).max() < REF_TOL


def test_the_mix_helpers_are_the_equations(model):
    cfg, params = model
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    X = jax.random.normal(jax.random.PRNGKey(4), (9, 4, 64))
    y = jax.random.normal(jax.random.PRNGKey(5), (9, 64))
    hp = ref.hyper(cfg)
    with jax.default_matmul_precision("highest"):
        pre, post, res = ref._mappings(X, lp, "mlp", hp)
        u, maps = residual._hc_in(X.reshape(1, 9, 256), lp, "mlp", cfg)
        out = residual._hc_out(X.reshape(1, 9, 256), y[None], maps, "mlp")
    np.testing.assert_allclose(
        u[0], jnp.einsum("sn,snc->sc", pre, X), atol=1e-5)
    want = (jnp.einsum("sij,sjc->sic", res, X)
            + post[:, :, None] * y[:, None, :])
    np.testing.assert_allclose(out[0].reshape(9, 4, 64), want, atol=1e-5)
    # one row a token: h and h + y, and nothing else
    plain = tiny_cfg(n=1)
    h = X[:, 0][None]
    assert residual._hc_in(h, {}, "attn", plain) == (h, None)
    np.testing.assert_array_equal(residual._hc_out(h, y[None], None, "mlp"),
                                  h + y[None])


# ---------------------------------------------------------------------------
# (c) the model against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [4, 2])
def test_full_forward_logits(model, n):
    cfg = tiny_cfg(n=n)
    params = model[1] if n == 4 else init_params(cfg, jax.random.PRNGKey(2))
    ids = tokens(80)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, jnp.asarray(ids)[None],
                            jnp.arange(80)[None])
    pos = list(range(40, 80))  # past the original 32
    got = ref.reference_logits(params, ref.hyper(cfg), ids, pos)
    assert rel_rms(np.asarray(logits[0])[pos], got["logits"]).max() < REF_TOL
    assert np.isfinite(got["raw_router_gap"]).all()
    assert np.isinf(got["router_gap"]).all()


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("n", [4, 2])
def test_prefill_then_decode_through_the_paged_latent_pool(
        model, backend, n, monkeypatch):
    cfg = tiny_cfg(n=n, backend=backend)
    params = model[1] if n == 4 else init_params(cfg, jax.random.PRNGKey(2))
    monkeypatch.setattr(drv, "CHUNK", 32)  # three launches, the last short
    ids = tokens(80 + 11)
    with jax.default_matmul_precision("highest"):
        served = drv.served_logits(params, cfg, np.asarray(ids), 80,
                                   page_size=16, pages_per_seq=6)
    pos = list(range(79, 91))  # every position past the original 32
    got = ref.reference_logits(params, ref.hyper(cfg), ids, pos)
    assert served.shape == (12, 300)
    assert rel_rms(served, got["logits"]).max() < REF_TOL
    # no position is skipped: the picks are held still instead
    assert np.isinf(got["router_gap"]).all()
    assert got["picks"].shape == (4, 91, 2)


def test_forcing_changes_nothing_in_float32_and_reaches_the_program(
        model, monkeypatch):
    cfg, params = model
    monkeypatch.setattr(drv, "CHUNK", 32)
    ids = tokens(80 + 3)
    kw = dict(page_size=16, pages_per_seq=6)
    with jax.default_matmul_precision("highest"):
        own = drv.served_logits(params, cfg, np.asarray(ids), 80, force=False,
                                **kw)
        held = drv.served_logits(params, cfg, np.asarray(ids), 80, **kw)
        # other experts than the reference's: the program takes THEM
        picks = ref.reference_logits(params, ref.hyper(cfg), ids,
                                     [79])["picks"]
        other = drv.served_logits(params, cfg, np.asarray(ids), 80,
                                  picks=(picks + 1) % 8, **kw)
    assert rel_rms(held, own).max() < REF_TOL
    assert rel_rms(other, own).min() > ref.TOLERANCE["value"]
    # and the reference forced with its own picks is the reference
    pos = list(range(79, 83))
    free = ref.reference_logits(params, ref.hyper(cfg), ids, pos)
    again = ref.reference_logits(params, ref.hyper(cfg), ids, pos,
                                 picks=free["picks"])
    np.testing.assert_array_equal(free["logits"], again["logits"])
    with pytest.raises(ValueError, match="page boundary"):
        drv.served_logits(params, cfg, np.asarray(ids), 77, **kw)


PRECISION = {"bf16_mappings"}


def _variant_errors(cfg, params, shift=0.0, only=None):
    ids = tokens(80)
    pos = list(range(40, 80))
    if shift:
        # B_res + 40 on every entry: the clamp binds on all of them
        def shifted(stack):
            return {k: (v.at[:, 8:].add(shift) if k.endswith("_bias") else v)
                    for k, v in stack.items()}
        params = dict(params, layers=shifted(params["layers"]),
                      dense_layers=shifted(params["dense_layers"]))
    hp = ref.hyper(cfg)
    base = ref.reference_logits(params, hp, ids, pos)["logits"]
    return {name: rel_rms(ref.reference_logits(params, v, ids, pos)["logits"],
                          base)
            for name, v in ref.variants(hp).items()
            if only is None or name in only}


def test_reference_variants_exceed_the_tolerance(model):
    """Each mechanism taken out of the reference fails the check the chip
    runs, at the tiny size, under the seeded initialiser."""
    errs = _variant_errors(*model)
    assert set(errs) == {
        "static_mappings", "softmax_not_sinkhorn", "one_sinkhorn_round",
        "no_clamp", "post_not_doubled", "pre_not_squashed", "widen_row0",
        "collapse_row0", "attn_maps_at_mlp", "no_mscale_in_scale",
        "yarn_off", "query_latent_not_normed", "bias_ignored_in_choice",
        "scale_one", "bf16_mappings"}
    tol = ref.TOLERANCE["value"]
    for name, e in errs.items():
        if name == "no_clamp":
            assert e.max() == 0.0  # nothing reaches +-30 as seeded
        elif name in PRECISION:
            assert e.max() > 10 * REF_TOL, (name, e.max())
        else:
            assert e.max() > tol, (name, e.max())
    # the clamp, where it binds (B_res shifted by +40)
    shifted = _variant_errors(*model, shift=40.0, only={"no_clamp"})
    assert shifted["no_clamp"].max() > tol


def test_the_papers_initial_values_would_blind_the_check(model):
    """alpha = 0.01, H_res's bias near the identity: the dynamic term and
    Sinkhorn's rounds sit under the tolerance, which is why the seeded
    initialiser draws what it draws."""
    cfg, params = model

    def paper(stack):
        out = dict(stack)
        for site in ("attn", "mlp"):
            n_layers = stack[f"hc_{site}_bias"].shape[0]
            out[f"hc_{site}_alpha"] = jnp.full((n_layers, 3), 0.01)
            out[f"hc_{site}_bias"] = jnp.tile(jnp.concatenate(
                [jnp.zeros(8), 8.0 * jnp.eye(4).reshape(-1)]), (n_layers, 1))
        return out

    blind = dict(params, layers=paper(params["layers"]),
                 dense_layers=paper(params["dense_layers"]))
    hidden = ("static_mappings", "softmax_not_sinkhorn", "one_sinkhorn_round")
    errs = _variant_errors(cfg, blind, only=hidden)
    tol = ref.TOLERANCE["value"]
    for name in hidden:
        # (the median: at a position or two the small change flips a router
        # choice, which moves the logits whatever caused it)
        assert np.median(errs[name]) < tol / 2, (name, errs[name])
    # and the served program still equals the reference there
    ids = tokens(80)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(blind, cfg, jnp.asarray(ids)[None],
                            jnp.arange(80)[None])
    pos = list(range(40, 80))
    got = ref.reference_logits(blind, ref.hyper(cfg), ids, pos)
    assert rel_rms(np.asarray(logits[0])[pos], got["logits"]).max() < REF_TOL


def test_the_reference_imports_nothing_of_the_program():
    import ast

    for folder, name in (("references", "xing4"),):
        with open(os.path.join(ROOT, "benchmarks", folder, name + ".py")) as f:
            tree = ast.parse(f.read())
        mods = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names} | {
            n.module for n in ast.walk(tree)
            if isinstance(n, ast.ImportFrom)}
        assert not any(m and m.split(".")[0] == "kafka_tpu" for m in mods)
        assert {m.split(".")[0] for m in mods if m} <= {
            "__future__", "math", "functools", "typing", "jax", "numpy"}


# ---------------------------------------------------------------------------
# (d) the engine: the stream never leaves `forward`
# ---------------------------------------------------------------------------

def make_engine(model, **kw):
    cfg, params = model
    ecfg = EngineConfig(**dict(ENGINE, **kw))
    return InferenceEngine(
        cfg.replace(attention_backend=ecfg.attention_backend), params, ecfg,
        kv_dtype=jnp.float32)


def run(eng, model, prompt, key, n=6):
    req = eng.generate(prompt, max_new_tokens=n, temperature=0.0,
                       prefix_key=key)
    assert_greedy_consistent(*model, prompt, req.output_ids)
    assert eng.self_check() == []
    return req


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_prefix_cache_hit_gives_the_cold_run(model, backend):
    eng = make_engine(model, attention_backend=backend)
    # 6 layers x (32 + 8 padded to a 128-lane tile) values x 4 B
    assert eng.kv_bytes_per_token == 6 * (32 + 128) * 4
    shared = tokens(100, seed=7)
    a = run(eng, model, shared + tokens(5, seed=8), "a")
    assert a.cached_tokens == 0
    b = run(eng, model, shared + tokens(9, seed=9), "b")
    assert b.cached_tokens == 96
    cold = make_engine(model, attention_backend=backend)
    again = run(eng, model, shared + tokens(9, seed=9), "b2")
    fresh = run(cold, model, shared + tokens(9, seed=9), "b2")
    assert again.cached_tokens >= 96 and fresh.cached_tokens == 0
    assert again.output_ids == fresh.output_ids
    snap = eng.metrics.snapshot(engine=eng)["engine"]
    assert snap["residual_streams"] == 4
    # rows x sites, padding included: 12 sites a row of every launch
    assert snap["hc_site_rows"] > 0 and snap["hc_site_rows"] % 12 == 0
    assert snap["hc_site_rows"] >= 12 * snap["prefill_rows_dispatched"]
    # a traced request's spans say how many rows a token
    assert eng._prefill_attrs(b)["residual_streams"] == 4
    assert eng._pass_attrs(steps=1)["residual_streams"] == 4


def test_one_row_counts_no_site_and_stamps_no_span():
    cfg = tiny_cfg(n=1)
    eng = make_engine((cfg, init_params(cfg, jax.random.PRNGKey(1))))
    req = eng.generate(tokens(20), max_new_tokens=4, temperature=0.0)
    snap = eng.metrics.snapshot(engine=eng)["engine"]
    assert snap["residual_streams"] == 1 and snap["hc_site_rows"] == 0
    assert "residual_streams" not in eng._prefill_attrs(req)
    assert "residual_streams" not in eng._pass_attrs(steps=1)


def test_engine_batched_prefill_fused_decode_and_preempt(model):
    eng = make_engine(model)
    cfg, params = model
    prompts = [tokens(30 + i, seed=40 + i) for i in range(3)]
    reqs = [GenRequest(request_id=f"r{i}", prompt_ids=p, max_new_tokens=48,
                       temperature=0.0, prefix_key=f"k{i}")
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    # a fixed number of iterations, every fetch landed after each: what the
    # victim holds is a function of the steps taken, not of timing
    for _ in range(6):
        eng.step()
        eng._drain(block=True)
    victim = reqs[0]
    assert victim.state == "active" and 2 <= len(victim.output_ids) < 48
    eng._preempt(victim)
    assert victim.seq is None and victim.slot == -1
    eng.run_to_completion()
    for r, p in zip(reqs, prompts):
        assert len(r.output_ids) == 48
        assert_greedy_consistent(cfg, params, p, r.output_ids)
    labels = {k[0] for k in eng._programs.built}
    assert "bprefill[64x4]" in labels and "multi_decode[4]" in labels
    assert eng.self_check() == [] and eng.metrics.requests_preempted == 1


# ---------------------------------------------------------------------------
# (e) memory, scopes, loader, the benchmark's entries
# ---------------------------------------------------------------------------

def test_memory_plan_counts_the_tree_and_the_pool(tmp_path, model):
    from kafka_tpu.runtime import planner

    cut = _cfg_of(tmp_path, **CUT)
    for cfg in (model[0], tiny_cfg(n=2), cut):
        shapes = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0)))
        held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(shapes))
        assert planner.weight_bytes_per_device(cfg) == held
    assert round(planner.weight_bytes_per_device(cut) / 1e9, 2) == 11.33
    plan = planner.plan_memory(
        cut, num_pages=8192, page_size=16, max_pages_per_seq=1024,
        max_batch=32, prefill_bucket=512, grammar_table_bytes=0)
    k_pool, v_pool = jax.eval_shape(
        lambda: make_kv_pool_arrays(cut, 8192, 16))
    rows = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves((k_pool, v_pool)))
    # 8 layers x 640 values x 2 B x 131,072 slots
    assert plan.kv_pool_bytes == rows == 8 * 640 * 2 * 8192 * 16
    assert plan.fits
    # the launch's activations count the carry's four rows a token
    launch = dict(max_batch=1, prefill_bucket=512, window=16)
    assert (planner.activation_bytes_estimate(cut, **launch)
            - planner.activation_bytes_estimate(cut.replace(hc_mult=1),
                                                **launch)
            == 512 * 3584 * (7 * 2 + 4 * 4))
    # and the configuration's file is that cut, key for key
    path = os.path.join(ROOT, "benchmarks", "configs", "xing4.0-29b-a4b.json")
    filed = config_from_hf_json(path)
    assert filed.replace(name=cut.name) == cut
    with open(path) as f:
        spec = json.load(f)
    assert list(spec["reduced"]) == ["num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key not in spec["reduced"]:
            assert spec[key] == value, key
    assert spec["num_hidden_layers"] == 8
    assert spec["scopes"] == ["attn_latent_proj", "moe_shared", "hc_map",
                              "hc_mix"]
    assert spec["check"] == {"reference": "xing4", "driver": "xing4_pool",
                             "n_prefill": 4608, "n_decode": 47,
                             "pages_per_seq": 292}
    # every compared decode step sits past the rotation's original context
    assert spec["check"]["n_prefill"] > 4096
    assert 292 * 16 >= 4608 + 47
    kan = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                      "kanana-2-30b-a3b.json")))
    assert spec["serving"] == kan["serving"]
    assert len(spec["serving"]["system_prompt"].encode()) == 4175


def test_hc_scopes_reach_the_hlo_and_hold_the_residual_adds(model):
    from kafka_tpu.tracing import DEVICE_SCOPES

    cfg, params = model
    step = _load("", "paged_step")
    k, v = make_kv_pool_arrays(cfg, 9, 16, jnp.float32)

    def text_of(cfg, params, k, v):
        return jax.jit(step.decode_step, static_argnums=(1,),
                       static_argnames=("page_size",)).lower(
            params, cfg, k, v, jnp.ones((1, 4), jnp.int32), jnp.asarray([5]),
            jnp.asarray([3]), jnp.asarray([True]),
            page_size=16).compile().as_text()

    text = text_of(cfg, params, k, v)
    for scope in ("hc_map", "hc_mix", "attn_qkv", "attn_core",
                  "attn_latent_proj", "attn_out", "mlp", "moe_experts",
                  "moe_shared", "embed", "head"):
        assert f"/{scope}/" in text, scope
        assert scope in DEVICE_SCOPES
    # one row a token: no such scope in the program
    plain = tiny_cfg(n=1)
    p1 = init_params(plain, jax.random.PRNGKey(1))
    k1, v1 = make_kv_pool_arrays(plain, 9, 16, jnp.float32)
    assert "/hc_m" not in text_of(plain, p1, k1, v1)


def test_the_loader_maps_published_names_and_drops_the_prediction_module():
    cfg = tiny_cfg(num_layers=3, first_k_dense=1, num_experts=2,
                   num_experts_per_tok=1)
    rng = np.random.RandomState(0)
    h, hq, r, rq, dn, dr, dv, n = 64, 4, 32, 24, 16, 8, 16, 4
    maps = 2 * n + n * n

    def w(*shape):
        return rng.normal(0, 0.1, shape).astype(np.float32)

    state = {"model.embed_tokens.weight": w(300, h),
             "model.norm.weight": w(h), "lm_head.weight": w(300, h)}
    # one layer past num_hidden_layers and an `mtp.` tree: the module's
    for i in range(4):
        p = f"model.layers.{i}."
        state.update({
            p + "input_layernorm.weight": w(h),
            p + "post_attention_layernorm.weight": w(h),
            p + "self_attn.q_a_proj.weight": w(rq, h),
            p + "self_attn.q_a_layernorm.weight": w(rq),
            p + "self_attn.q_b_proj.weight": w(hq * (dn + dr), rq),
            p + "self_attn.kv_a_proj_with_mqa.weight": w(r + dr, h),
            p + "self_attn.kv_a_layernorm.weight": w(r),
            p + "self_attn.kv_b_proj.weight": w(hq * (dn + dv), r),
            p + "self_attn.o_proj.weight": w(h, hq * dv),
        })
        for site in ("attn_hc", "mlp_hc"):
            state.update({
                p + f"{site}.hc_fn.weight": w(maps, n * h),
                p + f"{site}.hc_base": w(maps),
                p + f"{site}.hc_scale": w(3),
                p + f"{site}.hc_norm.weight": w(n * h),
            })
        if i < 1:
            for name, shape in (("gate_proj", (96, h)), ("up_proj", (96, h)),
                                ("down_proj", (h, 96))):
                state[p + f"mlp.{name}.weight"] = w(*shape)
        else:
            state[p + "mlp.gate.weight"] = w(2, h)
            state[p + "mlp.gate.e_score_correction_bias"] = w(2)
            for e in range(2):
                for name, shape in (("gate_proj", (24, h)),
                                    ("up_proj", (24, h)),
                                    ("down_proj", (h, 24))):
                    state[p + f"mlp.experts.{e}.{name}.weight"] = w(*shape)
            for name, shape in (("gate_proj", (24, h)), ("up_proj", (24, h)),
                                ("down_proj", (h, 24))):
                state[p + f"mlp.shared_experts.{name}.weight"] = w(*shape)
    state["model.mtp.0.enorm.weight"] = w(h)
    state["model.layers.3.eh_proj.weight"] = w(h, 2 * h)
    params = convert_hf_state_dict(state, cfg)
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)
    attn = params["attn"][GLOBAL]
    np.testing.assert_array_equal(
        attn["wqa"][2], state["model.layers.2.self_attn.q_a_proj.weight"].T)
    np.testing.assert_array_equal(
        attn["wqb"][1],
        state["model.layers.1.self_attn.q_b_proj.weight"].T.reshape(
            rq, hq, dn + dr))
    np.testing.assert_array_equal(
        params["dense_layers"]["hc_attn_phi"][0],
        state["model.layers.0.attn_hc.hc_fn.weight"].T)
    np.testing.assert_array_equal(
        params["layers"]["hc_mlp_bias"][1],
        state["model.layers.2.mlp_hc.hc_base"])
    # the converted tree runs
    logits, _ = forward(params, cfg, jnp.asarray([tokens(12)]),
                        jnp.arange(12)[None])
    assert np.isfinite(np.asarray(logits)).all()


def test_new_per_layer_entries_list_the_new_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {"dev_hc_share", "hc_stream_roofline", "yarn_mla_attn_roofline"}
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in new}
    assert set(listed) == new
    for m in listed.values():
        assert m["workloads"] == [CELL], m["name"]
        assert m["moves"] == "tpot_p50_ms"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
    # and no older metric's list gained the cell
    first = min(i for i, m in enumerate(bench["per_layer"])
                if m["name"] in new)
    for m in bench["per_layer"][:first]:
        assert CELL not in m.get("workloads", ()), m["name"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "xing4.0-29b-a4b", "chat-decode", 1)
    config = next(c for c in bench["configs"]
                  if c["name"] == "xing4.0-29b-a4b")
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"].endswith("Xing4.0-29B-A4B/blob/main/config.json")
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
        cell = json.load(f)
    assert cell["params"] == {"clients": 32, "stagger_s": 0.45}
    assert (cell["limits"]["ttft_ms"], cell["limits"]["tpot_ms"]) == (
        3000, 100)


def test_hc_roofline_counts_the_same_bytes_whatever_implements_the_site():
    hc = _load("", "hc_roofline")
    n, c = 4, 3584
    flops, nbytes = hc.site(32, n, c)
    # (2n + 2) C values a row + Phi and the stream norm's weight, bf16
    assert nbytes == 2 * (32 * 10 * c + n * c * 25)
    assert flops == 32 * (2 * n * c * 24 + 2 * n * c * 7)
    # rows scale the stream's bytes alone
    _, more = hc.site(512, n, c)
    assert more - nbytes == 2 * 480 * 10 * c
