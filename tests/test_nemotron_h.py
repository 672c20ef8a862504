"""NVIDIA-Nemotron-3-Nano-30B-A3B on the served path (ISSUE 60; `nemotron_h`):
a layer is ONE sublayer (a Mamba-2 mixer standing alone, grouped-query
attention that does not rotate, or a routed feed-forward of ungated
squared-ReLU experts), one norm and one residual add each, the leaves stacked
per kind for both halves, the SSD kernels tiling heads narrower than a lane
tile.

CPU, float32, tiny widths (pattern `MEM*EME`, heads of P = 4 and 8, 2 groups,
8 experts top-2 with 4 held), seeded weights, against the plain reference
`benchmarks/references/nemotronh.py` (the recurrence token by token, imports
nothing of kafka_tpu).  The kernels run interpreted.

TOLERANCES.  `forward` and the reference do the same float32 arithmetic in
another order: they agree to ~1e-6 relative RMS of the logits.  REF_TOL =
1e-4 leaves 100x room.  A MECHANISM taken out of the reference must move the
logits past the tolerance the chip's check uses (`ref.TOLERANCE`), at these
sizes too; the PRECISION variants (a bfloat16 accumulator, a bfloat16 state)
are small at 64 wide and are held to 10 x REF_TOL here (their readings at the
published widths are PERF.md's).  The kernels against the token-by-token
recurrence: KERNEL_TOL = 5e-5 absolute on outputs and states of order 1.
Engine tests compare TOKENS, greedy, against the uncached forward: exact.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, forward, init_params
from kafka_tpu.models.config import (
    GLOBAL, MAMBA2, MOE, UnsupportedConfigError, config_from_hf_json,
    holds_rows, holds_state,
)
from kafka_tpu.models.cache import StatePlan, _read_state, _write_state
from kafka_tpu.models.loader import convert_hf_state_dict
from kafka_tpu.models.mixers import MIXERS
from kafka_tpu.models.quant import quantize_params
from kafka_tpu.ops.pallas import ssd as sk
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime.engine import RecurrentStateUnsupported
from kafka_tpu.runtime.kv_cache import default_state_slots, make_kv_pool_arrays
from kafka_tpu.runtime.step_programs import StepPrograms

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_TOL = 1e-4
KERNEL_TOL = 5e-5

# the catalog row's `config` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}
CUT = dict(num_hidden_layers=16, n_routed_experts=64, vocab_size=65536,
           n_routed_experts_published=128, expert_share_offset=0)
LETTERS = {"M": MAMBA2, "*": GLOBAL, "E": MOE}


def _load(folder, name):
    path = os.path.join(ROOT, "benchmarks", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references", "nemotronh")
drv = _load("drivers", "nemotronh_pool")


def tiny_cfg(pattern="MEM*EME", backend="xla", **kw):
    base = dict(
        name="tiny-nemotronh", vocab_size=300, hidden_size=64,
        intermediate_size=48, num_layers=len(pattern), num_heads=4,
        num_kv_heads=1, head_dim=16,
        layer_types=tuple(LETTERS[c] for c in pattern),
        unrotated_kinds=(GLOBAL,), ssd_heads=4, ssd_head_dim=8,
        ssd_d_state=16, ssd_groups=2, ssd_conv_kernel=4, num_experts=4,
        num_experts_per_tok=2, num_experts_routed=8, expert_offset=0,
        moe_scoring="sigmoid", routed_scaling_factor=2.5,
        shared_intermediate_size=96, mlp_act="relu2", dtype="float32",
        tie_word_embeddings=False, attention_backend=backend)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


_UNCACHED = {}


def assert_greedy_consistent(cfg, params, prompt, out, pad=192):
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
    pattern = PUBLISHED["hybrid_override_pattern"]
    assert cfg.layer_types == tuple(LETTERS[c] for c in pattern)
    assert (cfg.layers_of(MAMBA2), cfg.layers_of(MOE),
            cfg.layers_of(GLOBAL)) == (23, 23, 6)
    assert (cfg.state_layers, cfg.kv_layers, cfg.routed_layers) == (23, 6, 23)
    assert cfg.lone_layers and cfg.kind_leaves and cfg.lead_tree
    assert not cfg.by_kind and not cfg.is_latent
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) \
        == (2688, 32, 2, 128)
    assert (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_d_state, cfg.ssd_groups,
            cfg.ssd_conv_kernel) == (64, 64, 128, 8, 4)
    assert cfg.ssd_conv_dim == 6144
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.intermediate_size,
            cfg.shared_intermediate_size) == (128, 6, 1856, 3712)
    assert cfg.moe_scoring == "sigmoid" and cfg.routed_scaling_factor == 2.5
    assert cfg.mlp_act == "relu2" and cfg.unrotated_kinds == (GLOBAL,)
    assert cfg.rms_norm_eps == 1e-5 and not cfg.tie_word_embeddings
    assert cfg.vocab_size == 131072 and cfg.max_context == 262144
    # which half a kind has is the kind's: a mixer OR a feed-forward
    assert [cfg.mixer_of(k) for k in (MAMBA2, GLOBAL, MOE)] == [
        "mamba2", "gqa", None]
    assert [cfg.has_ffn(k) for k in (MAMBA2, GLOBAL, MOE)] == [
        False, False, True]
    assert holds_state(MAMBA2) and not holds_rows(MAMBA2)
    assert not holds_state(MOE) and not holds_rows(MOE)
    assert MIXERS["mamba2"].scope == "ssd_proj"
    assert not MIXERS["mamba2"].positional
    # the cut: two lead layers, two trips of a seven-layer body
    cut = _cfg_of(tmp_path, **CUT)
    assert cut.pattern == (2, tuple(LETTERS[c] for c in "MEM*EME"))
    assert (cut.state_layers, cut.kv_layers, cut.routed_layers) == (7, 2, 7)
    assert (cut.num_experts, cut.num_router_experts, cut.expert_offset) \
        == (64, 128, 0)
    assert cut.state_shapes() == (("conv", (8, 2304)), ("ssd", (4096, 128)))
    assert cut.state_bytes_per_slot == 7 * (64 * 64 * 128 + 3 * 6144) * 4
    assert cut.kv_row_widths(GLOBAL) == (256, 256)
    assert cut.kv_row_widths(MAMBA2) == cut.kv_row_widths(MOE) == ()
    assert cut.kv_values_per_token * 2 == 2048


@pytest.mark.parametrize("over,key", [
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
    (dict(mlp_hidden_act="gelu"), "mlp_hidden_act"),
    (dict(mamba_hidden_act="relu"), "mamba_hidden_act"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(mlp_bias=True), "mlp_bias"),
    (dict(use_bias=True), "use_bias"),
    (dict(use_conv_bias=False), "use_conv_bias"),
    (dict(n_group=2), "n_group"),
    (dict(topk_group=2), "topk_group"),
    (dict(sliding_window=4096), "sliding_window"),
    (dict(residual_in_fp32=True), "residual_in_fp32"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(hybrid_override_pattern="ME-*" * 13), "hybrid_override_pattern"),
    (dict(hybrid_override_pattern="MEM*"), "hybrid_override_pattern"),
    (dict(hybrid_override_pattern="ME" * 26), "full_attention"),
    (dict(hybrid_override_pattern="M" * 52), "moe"),
    (dict(hybrid_override_pattern="M*" * 26), "moe"),
    (dict(n_routed_experts=0), "n_routed_experts"),
    (dict(n_groups=7), "groups"),
    (dict(conv_kernel=1), "taps"),
    (dict(hc_mult=4), "hc_mult"),
], ids=lambda v: v if isinstance(v, str) else "-".join(v))
def test_config_refuses_by_key(tmp_path, over, key):
    with pytest.raises(UnsupportedConfigError, match=key):
        _cfg_of(tmp_path, **over)


def test_the_lone_kinds_need_their_keys_and_every_other_act_is_refused():
    with pytest.raises(UnsupportedConfigError, match="unknown kinds"):
        ModelConfig(num_layers=2, layer_types=(MAMBA2, MOE))
    with pytest.raises(UnsupportedConfigError, match="mlp_act"):
        ModelConfig(mlp_act="relu2")  # no one-sublayer pattern
    with pytest.raises(UnsupportedConfigError, match="mlp_act 'gelu'"):
        tiny_cfg(mlp_act="gelu")
    with pytest.raises(UnsupportedConfigError, match="sigmoid"):
        tiny_cfg(moe_scoring="softmax", num_experts_routed=0, num_experts=8)
    with pytest.raises(UnsupportedConfigError, match="one-sublayer"):
        tiny_cfg(qk_norm=True)
    with pytest.raises(UnsupportedConfigError, match="one-sublayer"):
        tiny_cfg(first_k_dense=1, dense_intermediate_size=64)
    # int8 ungated experts: refused by name
    cfg = tiny_cfg()
    with pytest.raises(NotImplementedError, match="one-sublayer"):
        quantize_params(init_params(cfg, jax.random.PRNGKey(0)), cfg)


def test_layer_accounting_is_the_parents_for_every_other_configuration():
    from kafka_tpu.models.config import CONFIGS

    for name, cfg in CONFIGS.items():
        assert not cfg.lone_layers and cfg.mlp_act == "silu", name
        assert cfg.routed_layers == (cfg.num_layers if cfg.is_moe else 0)
        assert all(cfg.has_ffn(k) and cfg.mixer_of(k) for k in cfg.kinds)
    for name in ("falcon-h1-34b", "lfm2-8b-a1b", "kanana-2-30b-a3b"):
        cfg = config_from_hf_json(os.path.join(
            ROOT, "benchmarks", "configs", name + ".json"))
        assert not cfg.lone_layers
        assert cfg.routed_layers == (
            cfg.num_layers - cfg.first_k_dense if cfg.is_moe else 0)
        programs = StepPrograms(cfg, None, 16, 2, 4)
        assert programs.experts_held() == cfg.num_experts * cfg.routed_layers
        assert programs.ssd_rows(2, 128) == (
            2 * 128 * cfg.num_layers if cfg.ssd_heads else 0)


# ---------------------------------------------------------------------------
# (b) the kernels at heads narrower than a lane tile
# ---------------------------------------------------------------------------

def test_the_kernels_tile_heads_of_64_by_pairs():
    # Nemotron-H: 8 heads a group and a grid step, two a 128-lane tile
    assert sk.heads_a_step(64, 8, 64, 128) == 8
    assert sk.heads_a_tile(64, 8) == 2 and sk.tiles(64, 8, 64, 128)
    # Falcon-H1: a head is a tile, as it was
    assert sk.heads_a_step(32, 2, 128, 256) == 16
    assert sk.heads_a_tile(128, 16) == 1 and sk.tiles(32, 2, 128, 256)
    # what the chip cannot tile runs the scan there (and anywhere here)
    assert not sk.tiles(4, 2, 8, 16) and sk.heads_a_tile(8, 2) == 1
    assert not sk.tiles(64, 8, 64, 64) and not sk.tiles(6, 2, 64, 128)


def _ssd_inputs(B, S, H, G, P, N, slots=6, seed=0):
    rng = np.random.RandomState(seed)
    f = jnp.float32
    return (jnp.asarray(rng.randn(2, slots, H * P, N), f) * 0.1,
            jnp.asarray(rng.randn(B, S, H, P), f) * 0.5,
            jnp.asarray(rng.randn(B, S, G, N), f) * 0.3,
            jnp.asarray(rng.randn(B, S, G, N), f) * 0.3,
            -jnp.asarray(rng.rand(B, S, H), f) * 0.3)


@pytest.mark.parametrize("P,N,H,G", [(64, 128, 8, 2), (8, 16, 4, 2)],
                         ids=["pairs-a-tile", "below-the-tile"])
def test_chunk_and_step_kernels_equal_the_recurrence(P, N, H, G):
    leaf, x, Bm, Cm, g = _ssd_inputs(2, 256, H, G, P, N)
    plan = StatePlan(lens=jnp.asarray([200, 256], jnp.int32),
                     src=jnp.asarray([1, 2], jnp.int32),
                     dst=jnp.asarray([3, 4], jnp.int32),
                     snap=jnp.asarray([5, 0], jnp.int32),
                     fresh=jnp.asarray([False, True]))
    got = {kernel: sk.ssd(leaf, 1, plan, x, Bm, Cm, g, kernel=kernel,
                          read_state=_read_state, write_state=_write_state)
           for kernel in (False, True)}
    real = np.arange(256)[None, :] < np.asarray(plan.lens)[:, None]
    assert np.abs(np.asarray(got[True][0] - got[False][0])[real]).max() \
        < KERNEL_TOL
    np.testing.assert_allclose(got[True][1], got[False][1], atol=KERNEL_TOL)
    assert not np.array_equal(got[True][1][1, 3], leaf[1, 3])
    assert np.array_equal(got[True][1][0], leaf[0])  # the other layer
    # decode: lane 0 steps, lane 1 idles and keeps its block
    plan = StatePlan(lens=jnp.asarray([1, 0], jnp.int32))
    step = {kernel: sk.ssd(leaf, 1, plan, x[:, :1], Bm[:, :1], Cm[:, :1],
                           g[:, :1], kernel=kernel, read_state=_read_state,
                           write_state=_write_state)
            for kernel in (False, True)}
    np.testing.assert_allclose(step[True][0][0], step[False][0][0],
                               atol=KERNEL_TOL)
    np.testing.assert_allclose(step[True][1], step[False][1], atol=KERNEL_TOL)
    assert np.array_equal(step[True][1][1, 1], leaf[1, 1])


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernels_compile_for_the_v5e_at_the_published_widths(
        one_chip, monkeypatch):
    """`ssd_chunk` and `ssd_step` at 64 heads x 64 x 128 in 8 groups, and the
    grouped matmul over an up matrix stored out x in: no copy of the whole
    expert stack ahead of the product (a [H, 1856] stack was copied whole:
    models/ffn.ACTIVATIONS)."""
    from kafka_tpu.ops.pallas.grouped_matmul import grouped_matmul

    def of(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16
    H, G, P, N, L, slots = 64, 8, 64, 128, 7, 129
    leaf = of(f32, L, slots, H * P, N)
    chunk = jax.jit(lambda *a: sk.ssd_chunk(*a, groups=G, chunk=128)).lower(
        leaf, of(i32), *[of(i32, 4)] * 4, of(f32, 4, 512, H * P),
        of(f32, 4, 512, G * N), of(f32, 4, 512, G * N),
        of(f32, 4, 512, H)).compile()
    assert "ssd_chunk" in chunk.as_text()
    step = jax.jit(lambda *a: sk.ssd_step(*a, groups=G)).lower(
        leaf, of(i32), of(i32, 32), of(f32, 32, H * P), of(f32, 32, G * N),
        of(f32, 32, G * N), of(f32, 32, H)).compile()
    assert "ssd_step" in step.as_text()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # (the suite multiplies at "highest"; the served program does not)
    with jax.default_matmul_precision("bfloat16"):
        up = jax.jit(lambda x, w, sizes, layer: grouped_matmul(
            x, w, sizes, layer, 128, transposed=True)).lower(
            of(bf16, 256, 2688), of(bf16, 2, 8, 1856, 2688), of(i32, 8),
            of(i32)).compile()
    assert up.memory_analysis().temp_size_in_bytes < 2 * 8 * 1856 * 2688


# ---------------------------------------------------------------------------
# (c) forward against the reference; the halves a kind has
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pattern", ["MEM*EME", "MEMEM*EMEMEM*EME", "E*M"])
def test_full_forward_logits(pattern):
    cfg = tiny_cfg(pattern, expert_offset=4)
    params = init_params(cfg, jax.random.PRNGKey(3))
    ids = tokens(40, seed=5)
    want = ref.reference_logits(params, ref.hyper(cfg), ids,
                                list(range(32, 40)))
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, jnp.asarray(ids)[None],
                         jnp.arange(40)[None])
    assert rel_rms(got[0, 32:], want["logits"]).max() < REF_TOL
    assert 0.3 < np.sqrt(np.mean(want["logits"] ** 2)) < 3.0
    if pattern == "MEMEM*EMEMEM*EME":
        assert cfg.pattern == (2, tuple(LETTERS[c] for c in "MEM*EME"))


def _scoped(cfg, params, s=4):
    """{(scope, primitive): count} over the traced forward pass, scopes by
    their last name (sub-jaxprs walked)."""
    jaxpr = jax.make_jaxpr(lambda p, x: forward(
        p, cfg, x, jnp.arange(s, dtype=jnp.int32)[None]))(
        params, jnp.zeros((1, s), jnp.int32))
    counts = {}

    def walk(j, outer=""):
        for eqn in j.eqns:
            # (a sub-jaxpr's stack is relative to the equation that holds it)
            own = [n for n in str(eqn.source_info.name_stack).split("/")
                   if n and "->" not in n
                   and not n.startswith(("jit(", "jvp(", "vmap("))]
            scope = own[-1] if own else outer
            key = (scope, eqn.primitive.name)
            counts[key] = counts.get(key, 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, scope)

    walk(jaxpr.jaxpr)
    return counts


def test_a_layer_runs_the_half_it_has_one_norm_one_add(model):
    """`MEM*EME` is one period, so its seven layers are unrolled: three M,
    one `*` and three E layers trace one norm and one residual add each, and
    nothing of the half a kind does not have."""
    cfg, params = model
    assert cfg.pattern[0] == 0 and len(cfg.pattern[1]) == 7
    counts = _scoped(cfg, params)
    assert counts[("attn_norm", "rsqrt")] == 4   # 3 M + 1 *
    assert counts[("mlp_norm", "rsqrt")] == 3    # 3 E
    # the residual adds: under the scope of the half the layer has
    assert counts[("attn_out", "add")] == 1
    assert counts[("moe_experts", "add")] == 3
    assert counts[("moe_router", "dot_general")] == 3
    assert counts[("ssd_scan", "scan")] == 3
    assert not any(scope == "mlp" for scope, _ in counts)
    # two projections an M layer, four an attention layer: nothing else
    assert counts[("ssd_proj", "dot_general")] == 6
    assert counts[("attn_qkv", "dot_general")] == 3
    # an ungated block: up and down, no gate product (dense form: the
    # experts' two einsums and the combine; the shared expert's two)
    assert counts[("moe_experts", "dot_general")] == 3 * 3
    assert counts[("moe_shared", "dot_general")] == 3 * 2
    tree = jax.tree.map(lambda a: a.shape, params)
    assert set(tree) == {"embed", "final_norm", "layers", "attn", "ffn",
                         "lm_head"}
    assert tree["layers"] == {"ln": (7, 64)}
    assert set(tree["attn"]) == {MAMBA2, GLOBAL} and set(tree["ffn"]) == {MOE}
    assert set(tree["ffn"][MOE]) == {"router", "router_bias", "wu", "wd",
                                     "ws_u", "ws_d"}
    # both matrices of an ungated expert [held, f, H]
    assert tree["ffn"][MOE]["wu"] == tree["ffn"][MOE]["wd"] == (3, 4, 48, 64)
    assert tree["attn"][MAMBA2]["w_in"] == (3, 64, 32 + 96 + 4)
    assert tree["attn"][GLOBAL]["wq"] == (1, 64, 4, 16)


def test_the_two_chips_shares_add_up_to_the_uncut_layer():
    """The routed layers over ALL 8 experts = share 0 (experts 0-3) + share 1
    (experts 4-7), the shared expert, the mixers and the residual counted
    once: checked on the logits' pre-image, layer by layer, through a model
    whose E layers are the only difference."""
    whole = tiny_cfg(num_experts=8, num_experts_routed=0, expert_offset=0)
    wp = init_params(whole, jax.random.PRNGKey(2))
    from kafka_tpu.models.ffn import _moe_block

    x = jax.random.normal(jax.random.PRNGKey(9), (2, 5, 64), jnp.float32)
    lp = {k: v[1] for k, v in wp["ffn"][MOE].items()}
    with jax.default_matmul_precision("highest"):
        full, read = _moe_block(x, lp, whole)
        parts = []
        for lo in (0, 4):
            cfg = tiny_cfg(expert_offset=lo)
            share = dict(lp, wu=lp["wu"][lo:lo + 4], wd=lp["wd"][lo:lo + 4])
            out, held = _moe_block(x, share, cfg)
            assert held == 4
            parts.append(out)
        shared = tiny_cfg(expert_offset=0)
        none = dict(lp, wu=jnp.zeros_like(lp["wu"][:4]),
                    wd=jnp.zeros_like(lp["wd"][:4]))
        once, _ = _moe_block(x, none, shared)  # the shared expert alone
    assert read == 8
    np.testing.assert_allclose(parts[0] + parts[1] - once, full, atol=2e-5)
    assert float(jnp.abs(once).mean()) > 0.1


def _variant_errors(cfg, params):
    ids = tokens(171, seed=1)
    hp = ref.hyper(cfg)
    positions = list(range(159, 171))
    want = ref.reference_logits(params, hp, ids, positions)["logits"]
    return {name: rel_rms(ref.reference_logits(
        params, v, ids, positions)["logits"], want)
        for name, v in ref.variants(hp).items()}


def test_reference_variants_exceed_the_tolerance(model):
    cfg, params = model
    errors = _variant_errors(cfg, params)
    tol = ref.TOLERANCE["value"]
    precision = {"bf16_accumulate", "bf16_accumulate_256", "bf16_state"}
    assert set(errors) >= precision | {
        "relu_for_relu2", "silu_for_relu2", "no_shared_expert",
        "chosen_without_bias", "routed_scale_one", "not_renormalised",
        "no_d_skip", "no_dt_bias", "no_conv_bias", "groups_interleaved",
        "norm_ungrouped", "norm_before_gate", "rotation_on",
        "second_norm_at_5", "order_swapped_at_5",
        "conv_tail_zeroed_at_chunk", "state_lost_at_chunk"}
    # (some position's error is over the tolerance: what `compare_logits`
    # fails by; the precision variants past 10 x REF_TOL)
    for name, err in errors.items():
        floor = 10 * REF_TOL if name in precision else tol
        assert err.max() > floor, (name, err.max())


def test_the_reference_imports_nothing_of_the_program():
    for folder, name in (("references", "nemotronh"),):
        with open(os.path.join(ROOT, "benchmarks", folder,
                               name + ".py")) as f:
            text = f.read()
        assert "import kafka_tpu" not in text
        assert "from kafka_tpu" not in text


# ---------------------------------------------------------------------------
# (d) launches through pages and state slots + decode = the full pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,P,N", [
    ("xla", 8, 16), ("pallas", 4, 16), ("pallas", 64, 128)],
    ids=["xla", "pallas-below-the-tile", "pallas-at-the-tile"])
def test_prefill_then_decode_through_pages_and_state(backend, P, N):
    """The driver's launches (112 rows in a bucket of 128, leaving a
    snapshot; 48 rows a row a launch, the first resumed from it, on forced
    picks), then decode in the lane's slot.  Pallas: `ssd_chunk`, flash
    prefill and the grouped matmul in one program, `ssd_step` and paged
    decode at 4 / 1 heads, interpreted."""
    cfg = tiny_cfg(backend=backend, ssd_head_dim=P, ssd_d_state=N)
    params = init_params(cfg, jax.random.PRNGKey(0))
    ids = tokens(171, seed=1)
    want = ref.reference_logits(params, ref.hyper(cfg), ids,
                                list(range(159, 171)))
    with jax.default_matmul_precision("highest"):
        got = drv.served_logits(params, cfg, ids, 160, page_size=16,
                                pages_per_seq=40)
        free = drv.served_logits(params, cfg, ids, 160, page_size=16,
                                 pages_per_seq=40, force=False)
    assert rel_rms(got, want["logits"]).max() < REF_TOL
    # in float32 the program's own picks ARE the reference's
    assert rel_rms(free, want["logits"]).max() < REF_TOL
    assert np.isinf(want["router_gap"]).all()
    assert want["picks"].shape == (3, 171, 2)


def test_the_check_fails_by_name_where_the_state_is_not_float32(
        model, monkeypatch):
    from kafka_tpu.runtime import kv_cache

    cfg, params = model
    real = kv_cache.make_kv_pool_arrays

    def rounded(*a, **kw):
        k, v = real(*a, **kw)
        return k, dict(v, ssd=v["ssd"].astype(jnp.bfloat16))

    monkeypatch.setattr(kv_cache, "make_kv_pool_arrays", rounded)
    with pytest.raises(drv.SsdStateError, match="float32"):
        with jax.default_matmul_precision("highest"):
            drv.served_logits(params, cfg, tokens(165, seed=4), 160,
                              page_size=16, pages_per_seq=40)


def _prefill(params, cfg, ids, sizes, zero_at=None):
    """Prefill `ids` in launches of `sizes` rows (bucket 64), lane slot 0;
    `zero_at`: the launch that starts there reads slot 2, never written.
    Slot 0 starts out holding garbage: a launch at position 0 is `fresh`."""
    k_pool, v_pool = make_kv_pool_arrays(cfg, 41, 16, state_slots=3)
    v_pool = dict(v_pool, conv=v_pool["conv"].at[:, 0].set(7.0),
                  ssd=v_pool["ssd"].at[:, 0].set(7.0))
    page_row = jnp.arange(1, 41, dtype=jnp.int32)
    pre = jax.jit(drv.prefill_chunk, static_argnums=(1,),
                  static_argnames=("page_size",))
    start = 0
    for n in sizes:
        chunk = np.zeros(64, np.int32)
        chunk[:n] = ids[start:start + n]
        src = 2 if start == zero_at else 0
        logits, k_pool, v_pool = pre(
            params, cfg, k_pool, v_pool, page_row, jnp.asarray(chunk),
            jnp.int32(start), jnp.int32(n), jnp.int32(src), jnp.int32(0),
            jnp.int32(1), page_size=16)
        start += n
    return np.asarray(logits), k_pool, v_pool


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("sizes", [[40, 24], [7, 33, 24], [63, 1]],
                         ids=["40+24", "7+33+24", "63+1"])
def test_launches_equal_one_launch(model, backend, sizes):
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    ids = tokens(64, seed=2)
    want = ref.reference_logits(params, ref.hyper(cfg), ids, [63])["logits"][0]
    with jax.default_matmul_precision("highest"):
        one, _, v1 = _prefill(params, cfg, ids, [64])
        got, _, v = _prefill(params, cfg, ids, sizes)
    assert rel_rms(one, want) < REF_TOL and rel_rms(got, want) < REF_TOL
    for leaf in ("conv", "ssd"):
        np.testing.assert_allclose(v[leaf][:, 0], v1[leaf][:, 0],
                                   rtol=1e-4, atol=1e-5)
        # the snapshot slot holds what the lane's does
        assert np.array_equal(v[leaf][:, 0], v[leaf][:, 1])


def test_zeroed_tail_or_state_at_a_launch_boundary_fails(model):
    cfg, params = model
    ids = tokens(64, seed=2)
    want = ref.reference_logits(params, ref.hyper(cfg), ids, [63])["logits"][0]
    with jax.default_matmul_precision("highest"):
        bad, _, _ = _prefill(params, cfg, ids, [62, 2], zero_at=62)
    assert rel_rms(bad, want) > ref.TOLERANCE["value"]


# ---------------------------------------------------------------------------
# (e) inactive lanes, snapshots, the engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_inactive_lanes_leave_state_untouched(model, backend):
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    k_pool, v_pool = make_kv_pool_arrays(cfg, 33, 16, state_slots=4)
    assert set(v_pool) == {"v", "conv", "ssd"}
    # rows for the one attention layer, state for the three Mamba-2 layers
    assert k_pool.shape[0] == v_pool["v"].shape[0] == 1
    assert v_pool["conv"].shape[0] == v_pool["ssd"].shape[0] == 3
    v_pool = dict(v_pool, **{
        leaf: jax.random.normal(jax.random.PRNGKey(3), v_pool[leaf].shape)
        for leaf in ("conv", "ssd")})
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    _, _, v_new = jax.jit(drv.decode_step, static_argnums=(1,),
                          static_argnames=("page_size",))(
        params, cfg, k_pool, v_pool, table, jnp.asarray([5, 6]),
        jnp.asarray([3, 9]), jnp.asarray([True, False]), page_size=16)
    for leaf in ("conv", "ssd"):
        old, new = v_pool[leaf], v_new[leaf]
        assert np.array_equal(new[:, 1:], old[:, 1:]), leaf
        assert not np.array_equal(new[:, 0], old[:, 0]), leaf
    fn = StepPrograms(cfg, None, 16, 2, 4).batched_prefill(16, 2)
    z2 = jnp.zeros(2, jnp.int32)
    _, v_new, _ = fn(
        params, jnp.copy(k_pool), jax.tree.map(jnp.copy, v_pool), table,
        jnp.ones((2, 16), jnp.int32), z2, jnp.asarray([9, 7]),
        jnp.zeros(2), z2, jnp.ones(2), jnp.zeros(2, jnp.uint32),
        jnp.asarray([True, False]), jnp.asarray([0, 1]), jnp.asarray([3, 2]))
    for leaf in ("conv", "ssd"):
        old, new = v_pool[leaf], v_new[leaf]
        assert np.array_equal(new[:, 1], old[:, 1]), leaf
        # lane 0's state went to its slot AND to its snapshot slot
        assert np.array_equal(new[:, 0], new[:, 3]), leaf
        assert not np.array_equal(new[:, 0], old[:, 0]), leaf


ENGINE = dict(max_batch=4, page_size=16, num_pages=64, max_pages_per_seq=16,
              prefill_buckets=(16, 64), multi_step=4, attention_backend="xla")


def make_engine(model, **kw):
    cfg, params = model
    ecfg = EngineConfig(**dict(ENGINE, **kw))
    return InferenceEngine(
        cfg.replace(attention_backend=ecfg.attention_backend), params, ecfg)


def run(eng, model, prompt, key, n=6):
    req = eng.generate(prompt, max_new_tokens=n, temperature=0.0,
                       prefix_key=key)
    assert_greedy_consistent(*model, prompt, req.output_ids)
    assert eng.self_check() == []
    return req


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_snapshot_hit_gives_the_cold_run(model, backend):
    eng = make_engine(model, attention_backend=backend)
    assert eng.state_pool.n_slots == default_state_slots(4) == 17
    assert eng.kv_bytes_per_token == 1 * 2 * 16 * 4  # one layer holds rows
    shared = tokens(100, seed=7)
    a = run(eng, model, shared + tokens(5, seed=8), "a")
    assert a.cached_tokens == 0 and eng.state_restores == 0
    b = run(eng, model, shared + tokens(9, seed=9), "b")
    assert b.cached_tokens == 64 and eng.state_restores == 1
    assert b.state_restored is not None and a.state_restored is None
    c = run(eng, model, shared + tokens(3, seed=10), "c")
    assert c.cached_tokens == 96 and eng.state_restores == 2
    cold = make_engine(model, attention_backend=backend)
    again = run(eng, model, shared + tokens(9, seed=9), "b2")
    fresh = run(cold, model, shared + tokens(9, seed=9), "b2")
    assert again.cached_tokens == 96 and fresh.cached_tokens == 0
    assert again.output_ids == fresh.output_ids
    sec = eng.state_section()
    assert sec["state_bytes_per_slot"] == 3 * (3 * 96 + 32 * 16) * 4
    # the gauges, the counters and the spans' attributes
    snap = eng.metrics.snapshot(engine=eng)["engine"]
    assert (snap["state_layers"], snap["row_layers"],
            snap["routed_layers"]) == (3, 1, 3)
    assert (snap["ssd_chunk_trips"] > 0) == (backend == "pallas")
    assert snap["ssd_state_bytes"] > 0
    assert snap["ssd_state_bytes"] % (2 * 4 * 3 * 4 * 8 * 16) == 0
    # rows x Mamba-2 layers of every dispatched launch, padding included
    assert snap["ssd_rows_dispatched"] == 3 * snap["prefill_rows_dispatched"]
    assert snap["moe_experts_held"] % (4 * 3) == 0
    assert 0 < snap["moe_experts_read"] <= snap["moe_experts_held"]
    attrs = eng._prefill_attrs(b)
    assert (attrs["state_layers"], attrs["row_layers"],
            attrs["routed_layers"]) == (3, 1, 3)
    assert attrs["state_snapshot"] == b.state_restored


def test_engine_batched_prefill_fused_decode_and_preempt(model):
    eng = make_engine(model)
    cfg, params = model
    prompts = [tokens(30 + i, seed=40 + i) for i in range(3)]
    reqs = [GenRequest(request_id=f"r{i}", prompt_ids=p, max_new_tokens=64,
                       temperature=0.0, prefix_key=f"k{i}")
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    # a FIXED number of scheduler iterations, every fetch landed after each:
    # what the victim holds is then a function of the steps taken, not of
    # how many dispatches ran ahead of the fetches under load
    for _ in range(6):
        eng.step()
        eng._drain(block=True)
    victim = reqs[0]
    assert victim.state == "active"
    assert 2 <= len(victim.output_ids) < 64
    eng._preempt(victim)
    assert victim.seq is None and victim.slot == -1
    eng.run_to_completion()
    for r, p in zip(reqs, prompts):
        assert len(r.output_ids) == 64
        assert_greedy_consistent(cfg, params, p, r.output_ids)
    labels = {k[0] for k in eng._programs.built}
    assert "bprefill[64x4]" in labels and "multi_decode[4]" in labels
    assert eng.self_check() == [] and eng.metrics.requests_preempted == 1


def _mesh(**axes):
    from kafka_tpu.parallel import MeshConfig, make_mesh

    return make_mesh(MeshConfig(**axes))


@pytest.mark.parametrize("path,kw,mesh,why", [
    ("speculative verify", dict(speculative_k=2), None, "rolled back"),
    ("int8 pool", dict(kv_quantize="int8"), None, "float32 state slots"),
    ("pp / tp / ep mesh", {}, dict(tp=2), "state slots live on one device"),
    ("pp / tp / ep mesh", {}, dict(pp=2), "state slots live on one device"),
], ids=["speculative", "int8", "tp", "pp"])
def test_engine_refuses_by_name(model, path, kw, mesh, why):
    cfg, params = model
    with pytest.raises(RecurrentStateUnsupported, match=path) as err:
        InferenceEngine(cfg, params, EngineConfig(**dict(ENGINE, **kw)),
                        mesh=None if mesh is None else _mesh(**mesh))
    assert why in str(err.value)


# ---------------------------------------------------------------------------
# (f) the memory plan, the configuration's file, the loader
# ---------------------------------------------------------------------------

def test_memory_plan_counts_the_tree_the_pool_and_the_slots(tmp_path, model):
    from kafka_tpu.runtime import planner

    cut = _cfg_of(tmp_path, **CUT)
    for cfg in (model[0], cut):
        shapes = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0)))
        held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(shapes))
        assert planner.weight_bytes_per_device(cfg) == held
    # per-kind stacks: 7 layers hold experts, not 16
    shapes = jax.eval_shape(lambda: init_params(cut, jax.random.PRNGKey(0)))
    assert shapes["ffn"][MOE]["wu"].shape == (7, 64, 1856, 2688)
    assert shapes["attn"][MAMBA2]["w_in"].shape == (7, 2688, 10304)
    assert shapes["attn"][GLOBAL]["wk"].shape == (2, 2688, 2, 128)
    assert shapes["layers"]["ln"].shape == (16, 2688)
    assert round(planner.weight_bytes_per_device(cut) / 1e9, 2) == 10.57
    slots = default_state_slots(32)
    assert slots == 129
    plan = planner.plan_memory(
        cut, num_pages=8192, page_size=16, max_pages_per_seq=1024,
        max_batch=32, prefill_bucket=512, state_slots=slots,
        grammar_table_bytes=0)
    k_pool, v_pool = jax.eval_shape(lambda: make_kv_pool_arrays(
        cut, 8192, 16, state_slots=slots))
    rows = k_pool.size * 2 + v_pool["v"].size * 2
    # 2 row-holding layers x 2 x 256 values x 2 B x 131,072 slots
    assert plan.kv_pool_bytes == rows == 2 * 2 * 256 * 2 * 8192 * 16
    assert v_pool["conv"].shape == (7, slots, 8, 2304)
    assert v_pool["ssd"].shape == (7, slots, 4096, 128)
    held = (v_pool["conv"].size + v_pool["ssd"].size) * 4
    assert plan.state_bytes == held == slots * cut.state_bytes_per_slot
    assert plan.fits
    # the cost model counts an ungated expert's two matrices, seven layers
    model_ = planner.dispatch_cost_model(cut)
    assert model_.expert_bytes == 7 * 64 * 2 * 2688 * 1856 * 2
    # and the configuration's file is that cut, to the byte
    path = os.path.join(ROOT, "benchmarks", "configs",
                        "nemotron-3-nano-30b-a3b.json")
    filed = config_from_hf_json(path)
    assert filed.replace(name=cut.name) == cut
    with open(path) as f:
        spec = json.load(f)
    assert list(spec["reduced"]) == ["num_hidden_layers", "n_routed_experts",
                                     "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in spec["reduced"]:
            assert spec[key] == value, key
    assert spec["scopes"] == ["ssd_proj", "ssd_conv", "ssd_gate", "ssd_scan",
                              "moe_shared"]
    falcon = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                         "falcon-h1-34b.json")))
    assert dict(spec["serving"], num_pages=5120) == falcon["serving"]
    assert "attention_backend" not in spec["serving"]  # `auto` resolves it
    assert spec["check"] == {
        "reference": "nemotronh", "driver": "nemotronh_pool",
        "n_prefill": 1536, "n_decode": 47, "pages_per_seq": 100}
    assert (1536 - ref.RUN_IN) % 16 == 0 and drv.RUN_IN == ref.RUN_IN == 48


def test_the_loader_maps_the_published_names(model):
    """A tiny dict of `backbone.*` names, written from the program's tree the
    way the checkpoint holds it ([out, in] matrices, the convolution's taps
    [C, 1, taps], ALL the published experts), loads to that tree: the held
    share asks for experts 4..7 alone."""
    cfg = tiny_cfg(expert_offset=4)
    whole = tiny_cfg(num_experts=8, num_experts_routed=0, expert_offset=0)
    params = init_params(whole, jax.random.PRNGKey(5))
    n = np.asarray
    state = {"backbone.embeddings.weight": n(params["embed"]),
             "backbone.norm_f.weight": n(params["final_norm"]),
             "lm_head.weight": n(params["lm_head"]).T}
    seen = {}
    for i, kind in enumerate(cfg.layer_types):
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        pre = f"backbone.layers.{i}."
        state[pre + "norm.weight"] = n(params["layers"]["ln"][i])
        if kind == MAMBA2:
            lp = {k: n(v[nth]) for k, v in params["attn"][kind].items()}
            state[pre + "mixer.in_proj.weight"] = lp["w_in"].T
            state[pre + "mixer.conv1d.weight"] = lp["conv_w"].T[:, None, :]
            state[pre + "mixer.conv1d.bias"] = lp["conv_b"]
            for hf, ours in (("A_log", "A_log"), ("D", "D"),
                             ("dt_bias", "dt_bias"),
                             ("norm.weight", "ln_ssd")):
                state[pre + "mixer." + hf] = lp[ours]
            state[pre + "mixer.out_proj.weight"] = lp["w_out"].T
        elif kind == GLOBAL:
            lp = {k: n(v[nth]) for k, v in params["attn"][kind].items()}
            state[pre + "mixer.q_proj.weight"] = lp["wq"].reshape(64, -1).T
            state[pre + "mixer.k_proj.weight"] = lp["wk"].reshape(64, -1).T
            state[pre + "mixer.v_proj.weight"] = lp["wv"].reshape(64, -1).T
            state[pre + "mixer.o_proj.weight"] = lp["wo"].reshape(-1, 64).T
        else:
            lp = {k: n(v[nth]) for k, v in params["ffn"][kind].items()}
            state[pre + "mixer.gate.weight"] = lp["router"].T
            state[pre + "mixer.gate.e_score_correction_bias"] = \
                lp["router_bias"]
            for e in range(8):
                state[pre + f"mixer.experts.{e}.up_proj.weight"] = lp["wu"][e]
                state[pre + f"mixer.experts.{e}.down_proj.weight"] = \
                    lp["wd"][e].T
            state[pre + "mixer.shared_experts.up_proj.weight"] = lp["ws_u"].T
            state[pre + "mixer.shared_experts.down_proj.weight"] = \
                lp["ws_d"].T
    got = convert_hf_state_dict(state, cfg)
    want = dict(params, ffn={MOE: dict(
        params["ffn"][MOE], wu=params["ffn"][MOE]["wu"][:, 4:],
        wd=params["ffn"][MOE]["wd"][:, 4:])})
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    del state["backbone.layers.3.mixer.k_proj.weight"]
    with pytest.raises(KeyError, match="k_proj"):
        convert_hf_state_dict(state, cfg)


# ---------------------------------------------------------------------------
# (g) the scopes reach the compiled program; (h) the benchmark's entries
# ---------------------------------------------------------------------------

def test_scopes_reach_the_hlo_and_no_scan_over_rows_on_the_kernels(model):
    from kafka_tpu.tracing import DEVICE_SCOPES

    cfg, params = model
    k, v = make_kv_pool_arrays(cfg, 9, 16, state_slots=3)
    text = jax.jit(drv.decode_step, static_argnums=(1,),
                   static_argnames=("page_size",)).lower(
        params, cfg, k, v, jnp.ones((1, 4), jnp.int32), jnp.asarray([5]),
        jnp.asarray([3]), jnp.asarray([True]),
        page_size=16).compile().as_text()
    for scope in ("ssd_proj", "ssd_conv", "ssd_gate", "ssd_scan", "attn_qkv",
                  "attn_core", "attn_out", "moe_router", "moe_experts",
                  "moe_shared", "attn_norm", "mlp_norm"):
        assert f"/{scope}/" in text, scope
        assert scope in DEVICE_SCOPES
    assert "/mlp/" not in text and "/ssm_" not in text


def test_new_per_layer_entries_list_the_new_cell_alone():
    cell = "nemotron-3-nano-30b-a3b.chat-decode"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {"dev_lone_ssd_share", "ssd64_step_roofline",
           "ssd64_chunk_roofline", "gqa16_attn_roofline",
           "ep2_experts_read_share"}
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in new}
    assert set(listed) == new
    for m in listed.values():
        assert m["workloads"] == [cell], m["name"]
        assert m["moves"] == "tpot_p50_ms"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
    # and no older metric's list gained the cell
    first = min(i for i, m in enumerate(bench["per_layer"])
                if m["name"] in new)
    for m in bench["per_layer"][:first]:
        assert cell not in m.get("workloads", ()), m["name"]
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "nemotron-3-nano-30b-a3b", "chat-decode", 1)
    # (the twelfth cell; later PRs append theirs after it)
    assert bench["workloads"][11] == entry
    assert bench["configs"][11]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
