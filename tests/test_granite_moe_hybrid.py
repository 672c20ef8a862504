"""granite-4.0-h-small on the served path (ISSUE 63; `granitemoehybrid`): every
layer TWO sublayers, a Mamba-2 mixer of ONE group (or grouped-query attention
that does not rotate, under a published softmax scale) and BEHIND it a
softmax-routed feed-forward with a shared expert, each added at
`residual_multiplier`; a held share of the experts under the softmax rule;
`embedding_multiplier` and `logits_scaling` around a tied head.

CPU, float32, tiny widths (4 layers `m a m m`, 8 heads of 8 x 16 in one group,
GQA 4 / 2 x 16 at scale 1 / 32, 8 routed experts top-3 with 4 held), seeded
weights, against the plain reference `benchmarks/references/
granitemoehybrid.py` (the recurrence token by token, imports nothing of
kafka_tpu).  The kernels run interpreted.

TOLERANCES.  `forward` and the reference do the same float32 arithmetic in
another order: they agree to ~1e-6 relative RMS of the logits.  REF_TOL =
1e-4 leaves 100x room.  A MECHANISM taken out of the reference must move the
logits past the tolerance the chip's check uses (`ref.TOLERANCE`), at these
sizes too; the PRECISION variants (a bfloat16 accumulator, state or router
logits) are small at 64 wide and are held to REF_TOL here (their readings at
the published widths are PERF.md's).  The kernels against the token-by-token
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
from kafka_tpu.models.cache import StatePlan, _read_state, _write_state
from kafka_tpu.models.config import (
    GLOBAL, MAMBA, MAMBA2, MOE, UnsupportedConfigError, _tail_layout,
    config_from_hf_json, holds_rows, holds_state,
)
from kafka_tpu.models.ffn import (
    _moe_block, _routing_weights, moe_dispatch_form,
)
from kafka_tpu.models.loader import convert_hf_state_dict
from kafka_tpu.models.mixers import MIXERS
from kafka_tpu.models.quant import quantize_params
from kafka_tpu.ops.pallas import ssd as sk
from kafka_tpu.runtime import EngineConfig, InferenceEngine
from kafka_tpu.runtime.engine import RecurrentStateUnsupported
from kafka_tpu.runtime.kv_cache import default_state_slots, make_kv_pool_arrays
from kafka_tpu.runtime.step_programs import StepPrograms

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_TOL = 1e-4
KERNEL_TOL = 5e-5
CELL = "granite-4.0-h-small.chat-decode"

# the catalog row's `config` (model-configs guide, architectures.jsonl)
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": PERIOD * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536,
    "tie_word_embeddings": True, "vocab_size": 100352,
}
CUT = dict(num_hidden_layers=10, num_local_experts=36, vocab_size=50176,
           num_local_experts_published=72, expert_share_offset=0)
WORDS = {"m": MAMBA2, "a": GLOBAL}


def _load(folder, name):
    path = os.path.join(ROOT, "benchmarks", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references", "granitemoehybrid")
drv = _load("drivers", "granitemoehybrid_pool")


def tiny_cfg(pattern="mamm", backend="xla", **kw):
    base = dict(
        name="tiny-granite", vocab_size=300, hidden_size=64,
        intermediate_size=32, num_layers=len(pattern), num_heads=4,
        num_kv_heads=2, head_dim=16,
        layer_types=tuple(WORDS[c] for c in pattern),
        unrotated_kinds=(GLOBAL,), ssd_heads=8, ssd_head_dim=8,
        ssd_d_state=16, ssd_groups=1, ssd_conv_kernel=4, num_experts=4,
        num_experts_per_tok=3, num_experts_routed=8, expert_offset=0,
        shared_intermediate_size=48, embedding_multiplier=12.0,
        lm_head_multiplier=1 / 16, residual_multiplier=0.22,
        attention_multiplier=1 / 32, dtype="float32",
        tie_word_embeddings=True, attention_backend=backend)
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
    assert cfg.layer_types == tuple(
        MAMBA2 if w == "mamba" else GLOBAL for w in PERIOD * 4)
    assert (cfg.layers_of(MAMBA2), cfg.layers_of(GLOBAL)) == (36, 4)
    assert (cfg.state_layers, cfg.kv_layers, cfg.routed_layers) == (36, 4, 40)
    assert cfg.mixer_then_ffn and cfg.kind_leaves and cfg.lead_tree
    assert not cfg.lone_layers and not cfg.by_kind and not cfg.is_latent
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) \
        == (4096, 32, 8, 128)
    assert (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_d_state, cfg.ssd_groups,
            cfg.ssd_conv_kernel) == (128, 64, 128, 1, 4)
    assert cfg.ssd_conv_dim == 8448
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.intermediate_size,
            cfg.shared_intermediate_size) == (72, 10, 768, 1536)
    assert cfg.moe_scoring == "softmax" and cfg.mlp_act == "silu"
    assert cfg.unrotated_kinds == (GLOBAL,)
    # the four scalars: `logits_scaling` DIVIDES
    assert (cfg.embedding_multiplier, cfg.lm_head_multiplier,
            cfg.residual_multiplier, cfg.attention_multiplier) == (
        12.0, 1 / 16, 0.22, 0.0078125)
    assert cfg.softmax_scale == 1 / 128 != 128 ** -0.5
    assert cfg.rms_norm_eps == 1e-5 and cfg.tie_word_embeddings
    assert cfg.vocab_size == 100352 and cfg.max_context == 131072
    # both kinds have BOTH halves: a mixer and the feed-forward behind it
    assert [cfg.mixer_of(k) for k in (MAMBA2, GLOBAL)] == ["mamba2", "gqa"]
    assert cfg.has_ffn(MAMBA2) and cfg.has_ffn(GLOBAL)
    assert holds_state(MAMBA2) and not holds_rows(MAMBA2)
    assert MIXERS["mamba2"].scope == "ssd_proj"
    # the cut: ONE whole period of ten, unrolled
    cut = _cfg_of(tmp_path, **CUT)
    assert cut.pattern == (0, tuple(
        MAMBA2 if w == "mamba" else GLOBAL for w in PERIOD))
    assert (cut.state_layers, cut.kv_layers, cut.routed_layers) == (9, 1, 10)
    assert (cut.num_experts, cut.num_router_experts, cut.expert_offset) \
        == (36, 72, 0)
    assert cut.state_shapes() == (("conv", (8, 3168)), ("ssd", (8192, 128)))
    assert cut.state_bytes_per_slot == 9 * (128 * 64 * 128 + 3 * 8448) * 4
    assert cut.kv_row_widths(GLOBAL) == (1024, 1024)
    assert cut.kv_row_widths(MAMBA2) == ()
    assert cut.kv_values_per_token * 2 == 4096


def test_the_word_mamba_is_translated_by_model_type(tmp_path):
    """Under `granitemoehybrid` the published word "mamba" builds MAMBA2
    layers; the same letters are `phi4flash`'s Mamba-1 kind, whose reader
    still builds MAMBA, and under no model_type are they read as a kind."""
    assert MAMBA == "mamba" != MAMBA2
    cfg = _cfg_of(tmp_path)
    assert MAMBA not in cfg.layer_types and not cfg.hybrid_decoder
    phi = config_from_hf_json(os.path.join(
        ROOT, "benchmarks", "configs", "phi-4-mini-flash-reasoning.json"))
    assert MAMBA in phi.layer_types and MAMBA2 not in phi.layer_types
    assert phi.hybrid_decoder and not phi.mixer_then_ffn
    with pytest.raises(UnsupportedConfigError, match="unknown kinds"):
        _cfg_of(tmp_path, model_type="llama")


@pytest.mark.parametrize("over,key", [
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(mamba_conv_bias=False), "mamba_conv_bias"),
    (dict(normalization_function="layernorm"), "normalization_function"),
    (dict(position_embedding_type="rope"), "position_embedding_type"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings"),
    (dict(layer_types=["mamba", "moe"] * 20), "layer_types"),
    (dict(layer_types=PERIOD), "layer_types"),
    (dict(layer_types=["mamba"] * 40), "full_attention"),
    (dict(mamba_expand=3), "mamba_expand"),
    (dict(num_local_experts=0), "num_local_experts"),
    (dict(logits_scaling=0), "logits_scaling"),
    (dict(mamba_n_groups=3), "groups"),
    (dict(mamba_d_conv=1), "taps"),
    (dict(hc_mult=4), "hc_mult"),
    (dict(num_local_experts=36, num_local_experts_published=72,
          expert_share_offset=40), "expert_offset"),
], ids=lambda v: v if isinstance(v, str) else "-".join(v))
def test_config_refuses_by_key(tmp_path, over, key):
    with pytest.raises(UnsupportedConfigError, match=key):
        _cfg_of(tmp_path, **over)


def test_the_layout_is_judged_by_what_the_program_needs():
    # a lone SSD mixer with a DENSE feed-forward behind it has no tree
    with pytest.raises(UnsupportedConfigError, match="ROUTED"):
        tiny_cfg(num_experts=0, num_experts_routed=0)
    with pytest.raises(UnsupportedConfigError, match="mixer-then-feed"):
        tiny_cfg(qk_norm=True)
    with pytest.raises(UnsupportedConfigError, match="mixer-then-feed"):
        tiny_cfg(first_k_dense=1, dense_intermediate_size=64)
    with pytest.raises(UnsupportedConfigError, match="mlp_act"):
        tiny_cfg(mlp_act="relu2")
    # a share under the softmax rule is this layout's alone
    with pytest.raises(UnsupportedConfigError, match="softmax rule"):
        ModelConfig(num_experts=4, num_experts_routed=8)
    # the two new scalars where they are not built
    with pytest.raises(UnsupportedConfigError, match="attention_multiplier"):
        ModelConfig(attention_multiplier=-1.0)
    with pytest.raises(UnsupportedConfigError, match="attention_multiplier"):
        ModelConfig(attention_multiplier=0.1, kv_lora_rank=8,
                    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8)
    # sigmoid routing, a whole set of experts and no scalars: still the layout
    cfg = tiny_cfg(moe_scoring="sigmoid", num_experts=8, num_experts_routed=0,
                   residual_multiplier=1.0, attention_multiplier=0.0)
    assert cfg.mixer_then_ffn and cfg.softmax_scale is None


def test_layer_accounting_is_the_parents_for_every_other_configuration():
    from kafka_tpu.models.config import CONFIGS

    for name, cfg in CONFIGS.items():
        assert not cfg.mixer_then_ffn, name
        assert cfg.softmax_scale is None and cfg.residual_multiplier == 1.0
    for name in ("falcon-h1-34b", "nemotron-3-nano-30b-a3b", "lfm2-8b-a1b",
                 "mixtral-8x7b"):
        cfg = config_from_hf_json(os.path.join(
            ROOT, "benchmarks", "configs", name + ".json"))
        assert not cfg.mixer_then_ffn
        assert cfg.softmax_scale is None and cfg.residual_multiplier == 1.0
    nem = config_from_hf_json(os.path.join(
        ROOT, "benchmarks", "configs", "nemotron-3-nano-30b-a3b.json"))
    assert nem.lone_layers and not nem.has_ffn(MAMBA2) and nem.has_ffn(MOE)


# ---------------------------------------------------------------------------
# (b) the kernels at 128 heads of 64 x 128 in ONE group; the tail's layout
# ---------------------------------------------------------------------------

def test_a_grid_step_holds_half_the_one_group():
    # Granite: 128 heads in one group; 64 a grid step (2 MB of state), two
    # a 128-lane tile: C B^T of the one group is taken twice a chunk
    assert sk.heads_a_step(128, 1, 64, 128) == 64
    assert 64 * 64 * 128 * 4 == sk.STATE_BLOCK_BYTES
    assert sk.heads_a_tile(64, 64) == 2 and sk.tiles(128, 1, 64, 128)
    # the others as they were
    assert sk.heads_a_step(64, 8, 64, 128) == 8
    assert sk.heads_a_step(32, 2, 128, 256) == 16
    # the conv tail of 3 x 8,448 lies over 8 rows of 3,168 = 24.75 lane tiles
    assert _tail_layout(3, 8448) == (8, 3168) and 3168 % 128 == 96
    assert _tail_layout(3, 6144) == (8, 2304) and 2304 % 128 == 0


def _ssd_inputs(B, S, H, G, P, N, slots=6, seed=0):
    rng = np.random.RandomState(seed)
    f = jnp.float32
    return (jnp.asarray(rng.randn(2, slots, H * P, N), f) * 0.1,
            jnp.asarray(rng.randn(B, S, H, P), f) * 0.5,
            jnp.asarray(rng.randn(B, S, G, N), f) * 0.3,
            jnp.asarray(rng.randn(B, S, G, N), f) * 0.3,
            -jnp.asarray(rng.rand(B, S, H), f) * 0.3)


def test_chunk_and_step_kernels_equal_the_recurrence_at_128_heads_one_group():
    """`ssd_chunk` and `ssd_step`, interpreted, against `_scan_xla` at the
    published geometry: a padded last chunk (200 of 256 rows), a lane that
    resumes from a snapshot slot and leaves one, a fresh lane, an idle
    decode lane."""
    H, G, P, N = 128, 1, 64, 128
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
    # lane 0's state after its 200th row went to its slot AND its snapshot
    assert np.array_equal(got[True][1][1, 3], got[True][1][1, 5])
    assert not np.array_equal(got[True][1][1, 3], leaf[1, 3])
    assert np.array_equal(got[True][1][0], leaf[0])  # the other layer
    plan = StatePlan(lens=jnp.asarray([1, 0], jnp.int32))
    step = {kernel: sk.ssd(leaf, 1, plan, x[:, :1], Bm[:, :1], Cm[:, :1],
                           g[:, :1], kernel=kernel, read_state=_read_state,
                           write_state=_write_state)
            for kernel in (False, True)}
    np.testing.assert_allclose(step[True][0][0], step[False][0][0],
                               atol=KERNEL_TOL)
    np.testing.assert_allclose(step[True][1], step[False][1], atol=KERNEL_TOL)
    assert np.array_equal(step[True][1][1, 1], leaf[1, 1])


def test_the_tail_of_3_by_8448_round_trips_a_snapshot_and_a_restore():
    """A (8, 3168) slot leaf is three rows of 8,448 channels, written by one
    launch to the lane's slot and its snapshot and read back by the next:
    the rows a restore hands the convolution are the rows the launch left."""
    from kafka_tpu.models.mixers.state import _tail_conv_silu

    rows, width = 3, 8448
    shape = _tail_layout(rows, width)
    leaf = jnp.zeros((2, 4) + shape, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, width), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (4, width), jnp.float32)
    plan = StatePlan(lens=jnp.asarray([11], jnp.int32),
                     src=jnp.asarray([0], jnp.int32),
                     dst=jnp.asarray([1], jnp.int32),
                     snap=jnp.asarray([2], jnp.int32),
                     fresh=jnp.asarray([True]))
    _, leaf = _tail_conv_silu(x, w, None, leaf, 1, plan)
    want = np.asarray(x[0, 8:11])  # the last three REAL rows of eleven
    for slot in (1, 2):
        np.testing.assert_array_equal(
            np.asarray(leaf[1, slot]).reshape(rows, width), want)
    assert not np.asarray(leaf[0]).any() and not np.asarray(leaf[1, 0]).any()
    # the restore: a launch resumed from the snapshot equals one long launch
    resumed = StatePlan(lens=jnp.asarray([5], jnp.int32),
                        src=jnp.asarray([2], jnp.int32),
                        dst=jnp.asarray([3], jnp.int32),
                        snap=jnp.asarray([0], jnp.int32),
                        fresh=jnp.asarray([False]))
    tail = jnp.concatenate([x[:, 11:], jnp.zeros((1, 11, width))], axis=1)
    got, leaf = _tail_conv_silu(tail, w, None, leaf, 1, resumed)
    whole, _ = _tail_conv_silu(x, w, None, None, 1,
                               StatePlan(lens=jnp.asarray([16], jnp.int32)))
    np.testing.assert_allclose(got[0, :5], whole[0, 11:], atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(leaf[1, 3]).reshape(rows, width), np.asarray(x[0, 13:]))


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
    """`ssd_chunk` and `ssd_step` at 128 heads x 64 x 128 in ONE group (64
    heads a grid step), the grouped matmul over 36 held experts of f = 768
    and the paged-decode kernel at 32 / 8 x 128 under the published scale."""
    from kafka_tpu.ops.pallas import paged_decode_attention
    from kafka_tpu.ops.pallas.grouped_matmul import grouped_matmul, tile_rows

    def of(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16
    H, G, P, N, L, slots = 128, 1, 64, 128, 9, 65
    leaf = of(f32, L, slots, H * P, N)
    chunk = jax.jit(lambda *a: sk.ssd_chunk(*a, groups=G, chunk=128)).lower(
        leaf, of(i32), *[of(i32, 4)] * 4, of(f32, 4, 512, H * P),
        of(f32, 4, 512, G * N), of(f32, 4, 512, G * N),
        of(f32, 4, 512, H)).compile()
    assert "ssd_chunk" in chunk.as_text()
    step = jax.jit(lambda *a: sk.ssd_step(*a, groups=G)).lower(
        leaf, of(i32), of(i32, 16), of(f32, 16, H * P), of(f32, 16, G * N),
        of(f32, 16, G * N), of(f32, 16, H)).compile()
    assert "ssd_step" in step.as_text()
    tile = tile_rows(16 * 10, 72)
    rows = -(-160 // tile) * tile
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # (the suite multiplies at "highest"; the served program does not; no
    # copy of the expert stack, nor of a layer's, ahead of the product)
    with jax.default_matmul_precision("bfloat16"):
        up = jax.jit(lambda x, w, sizes, layer: grouped_matmul(
            x, w, sizes, layer, tile)).lower(
            of(bf16, rows, 4096), of(bf16, 10, 36, 4096, 768), of(i32, 36),
            of(i32)).compile()
        attn = jax.jit(lambda q, k, v, t, n: paged_decode_attention(
            q, k, v, t, n, page_size=16, scale=1 / 128,
            interpret=False)).lower(
            of(bf16, 16, 32, 128), of(bf16, 8192 * 16, 1024),
            of(bf16, 8192 * 16, 1024), of(i32, 16, 1024),
            of(i32, 16)).compile()
    assert up.memory_analysis().temp_size_in_bytes < 36 * 4096 * 768
    assert "tpu_custom_call" in attn.as_text()


# ---------------------------------------------------------------------------
# (c) forward against the reference; the two adds; the routed block
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pattern", ["mamm", "mmmmmammmm", "mammmamm", "am"])
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
    if pattern == "mammmamm":
        assert cfg.pattern == (0, tuple(WORDS[c] for c in "mamm"))  # a scan


def _scoped(cfg, params, s=4):
    """{(scope, primitive): count} over the traced forward pass, scopes by
    their last name (sub-jaxprs walked)."""
    jaxpr = jax.make_jaxpr(lambda p, x: forward(
        p, cfg, x, jnp.arange(s, dtype=jnp.int32)[None]))(
        params, jnp.zeros((1, s), jnp.int32))
    counts = {}

    def walk(j, outer=""):
        for eqn in j.eqns:
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


def test_a_layer_runs_both_halves_and_scales_each_add_in_its_scope(model):
    """`mamm` is one period, so its four layers are unrolled: every layer
    traces two norms and two residual adds, each add with ONE multiply by
    `residual_multiplier` beside it in the add's own scope; with the
    multiplier 1.0 the multiplies are gone and nothing else moves."""
    cfg, params = model
    assert cfg.pattern == (0, tuple(WORDS[c] for c in "mamm"))
    counts = _scoped(cfg, params)
    assert counts[("attn_norm", "rsqrt")] == counts[("mlp_norm", "rsqrt")] == 4
    assert counts[("moe_experts", "add")] == 4
    assert counts[("moe_router", "dot_general")] == 4
    assert counts[("ssd_scan", "scan")] == 3
    assert counts[("ssd_proj", "dot_general")] == 6
    assert counts[("attn_qkv", "dot_general")] == 3
    assert counts[("moe_shared", "dot_general")] == 4 * 3  # a gated block
    assert not any(scope == "mlp" for scope, _ in counts)
    plain = tiny_cfg(residual_multiplier=1.0)
    base = _scoped(plain, params)
    moved = {k: counts.get(k, 0) - base.get(k, 0)
             for k in set(counts) | set(base)
             if counts.get(k, 0) != base.get(k, 0)}
    # one multiply an add: 3 mixers under ssd_proj, attention's under
    # attn_out, the four routed blocks' under moe_experts
    assert moved == {("ssd_proj", "mul"): 3, ("attn_out", "mul"): 1,
                     ("moe_experts", "mul"): 4}
    tree = jax.tree.map(lambda a: a.shape, params)
    assert set(tree) == {"embed", "final_norm", "layers", "attn"}
    assert set(tree["attn"]) == {MAMBA2, GLOBAL}
    assert set(tree["layers"]) == {"ln_attn", "ln_mlp", "router", "wg", "wu",
                                   "wd", "ws_g", "ws_u", "ws_d"}
    # a softmax router of the router's full width and NO selection bias; the
    # held experts; the mixers stacked per kind
    assert tree["layers"]["router"] == (4, 64, 8)
    assert tree["layers"]["wg"] == (4, 4, 64, 32)
    assert tree["layers"]["ws_d"] == (4, 48, 64)
    assert tree["attn"][MAMBA2]["w_in"] == (3, 64, 64 + 96 + 8)
    assert tree["attn"][GLOBAL]["wk"] == (1, 64, 2, 16)


def test_the_scale_reaches_every_attention_path_and_no_other_model(model):
    """The published softmax scale in the uncached pass, the XLA decode walk
    and the static-window gather (the Pallas kernels: section (d)); a model
    without one traces `d ** -0.5` as it always did."""
    cfg, params = model
    ids = tokens(24, seed=3)
    hp = ref.hyper(cfg)
    want = ref.reference_logits(params, hp, ids, [23])["logits"]
    wrong = ref.reference_logits(
        params, dict(hp, scale_rsqrt_head_dim=True), ids, [23])["logits"]
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, jnp.asarray(ids)[None],
                         jnp.arange(24)[None])
        off, _ = forward(params, cfg.replace(attention_multiplier=0.0),
                         jnp.asarray(ids)[None], jnp.arange(24)[None])
    assert rel_rms(got[0, 23:], want).max() < REF_TOL
    assert rel_rms(off[0, 23:], wrong).max() < REF_TOL
    assert rel_rms(wrong, want).max() > ref.TOLERANCE["value"]


def test_the_two_chips_shares_add_up_under_the_softmax_rule():
    """The routed block over ALL 8 experts = share 0 (experts 0-3) + share 1
    (experts 4-7) with the shared expert counted once: the softmax is over a
    row's three picks wherever they are held, in both dispatch forms."""
    whole = tiny_cfg(num_experts=8, num_experts_routed=0, expert_offset=0)
    wp = init_params(whole, jax.random.PRNGKey(2))
    lp = {k: v[1] for k, v in wp["layers"].items()}
    names = ("wg", "wu", "wd")
    for rows in (10, 400):  # dense, token
        x = jax.random.normal(jax.random.PRNGKey(9), (2, rows // 2, 64),
                              jnp.float32)
        with jax.default_matmul_precision("highest"):
            full, read = _moe_block(x, lp, whole)
            parts = []
            for lo in (0, 4):
                cfg = tiny_cfg(expert_offset=lo)
                share = dict(lp, **{n: lp[n][lo:lo + 4] for n in names})
                out, tally = _moe_block(x, share, cfg, count_picks=True)
                parts.append(out)
                # (experts read, the picks of three a row that fell here)
                assert tally.shape == (2,)
                assert 0 < int(tally[1]) < rows * 3 and int(tally[0]) <= 4
                held = int(tally[1])
            none = dict(lp, **{n: jnp.zeros_like(lp[n][:4]) for n in names})
            once, _ = _moe_block(x, none, tiny_cfg())  # the shared expert
        form = moe_dispatch_form(rows, 4, 3, False, 8)
        assert form == ("token" if rows == 400 else "dense")
        np.testing.assert_allclose(parts[0] + parts[1] - once, full,
                                   atol=2e-4)
        assert float(jnp.abs(once).mean()) > 0.1
        assert 0 < held < rows * 3


def test_the_choice_leaf_chooses_and_never_weighs():
    """"router_choice" (no published tree holds it) names a row's experts; the
    weights stay the softmax over the chosen experts' own logits; without
    the leaf the rule is what it always was."""
    t = jax.random.normal(jax.random.PRNGKey(0), (5, 16), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8), jnp.float32)
    logits = np.asarray(t @ router)
    plain = np.asarray(_routing_weights(t, router, 3))
    for row in range(5):
        top = np.argsort(-logits[row])[:3]
        e = np.exp(logits[row, top] - logits[row, top].max())
        np.testing.assert_allclose(plain[row, top], e / e.sum(), rtol=1e-5)
    picks = np.asarray([2, 5, 7])
    choice = jnp.zeros(8).at[picks].set(1000.0)
    idx, w = _routing_weights(t, router, 3, True, choice)
    assert (np.sort(np.asarray(idx), -1) == picks).all()
    for row in range(5):
        own = logits[row, np.asarray(idx[row])]
        e = np.exp(own - own.max())
        np.testing.assert_allclose(w[row], e / e.sum(), rtol=1e-5)
    zero = _routing_weights(t, router, 3, False, jnp.zeros(8))
    np.testing.assert_allclose(zero, plain, rtol=1e-6)


def test_dispatch_forms_at_the_cells_rows():
    # 16 lanes over 36 of 72, top-10: token (expected unread 0.091)
    assert moe_dispatch_form(16, 36, 10, False, 72) == "token"
    assert (1 - 10 / 72) ** 16 == pytest.approx(0.0914, abs=1e-4)
    # 32 rows: dense (0.008); the check's one-row launches: dense
    assert moe_dispatch_form(32, 36, 10, False, 72) == "dense"
    assert (1 - 10 / 72) ** 32 == pytest.approx(0.0084, abs=1e-4)
    assert moe_dispatch_form(1, 36, 10, False, 72) == "dense"
    assert moe_dispatch_form(512, 36, 10, False, 72) == "token"
    from kafka_tpu.ops.pallas.grouped_matmul import tile_rows

    assert tile_rows(16 * 10, 72) % 8 == 0


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
    precision = {"bf16_accumulate", "bf16_accumulate_256", "bf16_state",
                 "bf16_router_logits"}
    # what ISSUE 63 lists as must-fail, and the rest of the mixer's and the
    # block's mechanisms
    assert set(errors) >= precision | {
        "no_residual_multiplier_mixer", "no_residual_multiplier_ffn",
        "scale_rsqrt_head_dim", "renormalised_over_held", "rotation_on",
        "no_logits_scaling", "no_embedding_multiplier",
        "conv_tail_zeroed_at_chunk", "state_lost_at_chunk",
        "softmax_over_all", "no_shared_expert", "up_half_gated", "no_d_skip",
        "no_dt_bias", "no_conv_bias", "norm_per_head", "norm_before_gate"}
    # (48 forced rows wash a 3-row tail out of everything but the slow
    # heads' states: zeroed 47 rows ahead of the compared positions it reads
    # 0.015 at these sizes; zeroed AT a launch boundary it fails the
    # tolerance: test_zeroed_tail_or_state_at_a_launch_boundary_fails)
    washed = {"conv_tail_zeroed_at_chunk"}
    for name, err in errors.items():
        floor = REF_TOL if name in precision else (
            10 * REF_TOL if name in washed else tol)
        assert err.max() > floor, (name, err.max())


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmarks", "references",
                           "granitemoehybrid.py")) as f:
        text = f.read()
    assert "import kafka_tpu" not in text and "from kafka_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


# ---------------------------------------------------------------------------
# (d) launches through pages and state slots + decode = the full pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,P,N", [
    ("xla", 8, 16), ("pallas", 8, 16), ("pallas", 64, 128)],
    ids=["xla", "pallas-below-the-tile", "pallas-at-the-tile"])
def test_prefill_then_decode_through_pages_and_state(backend, P, N):
    """The driver's launches (112 rows in a bucket of 128, leaving a
    snapshot; 48 rows a row a launch, the first resumed from it, on picks
    forced through the CHOICE leaf), then decode in the lane's slot.
    Pallas: `ssd_chunk` and flash prefill in one program, `ssd_step` and
    paged decode at 4 / 2 heads under the published scale, interpreted."""
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
    assert want["picks"].shape == (4, 171, 3)


def test_forced_picks_are_taken_through_the_choice_leaf(model):
    """Handed ANOTHER tree's picks, the served program takes them (its
    logits are the reference's under the same picks, not under its own)."""
    cfg, params = model
    ids = tokens(165, seed=6)
    hp = ref.hyper(cfg)
    own = ref.reference_logits(params, hp, ids, list(range(159, 165)))
    other = np.asarray(own["picks"]).copy()
    other[:, 112:] = (other[:, 112:] + 1) % 8  # every forced row: shifted
    want = ref.reference_logits(params, hp, ids, list(range(159, 165)),
                                picks=other)
    assert rel_rms(want["logits"], own["logits"]).max() > 0.01
    with jax.default_matmul_precision("highest"):
        got = drv.served_logits(params, cfg, ids, 160, page_size=16,
                                pages_per_seq=40, picks=other)
    assert rel_rms(got, want["logits"]).max() < REF_TOL


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
    `zero_at`: the launch that starts there reads slot 2, never written."""
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
        assert np.array_equal(v[leaf][:, 0], v[leaf][:, 1])


def test_zeroed_tail_or_state_at_a_launch_boundary_fails(model):
    cfg, params = model
    ids = tokens(64, seed=2)
    want = ref.reference_logits(params, ref.hyper(cfg), ids, [63])["logits"][0]
    with jax.default_matmul_precision("highest"):
        bad, _, _ = _prefill(params, cfg, ids, [62, 2], zero_at=62)
    assert rel_rms(bad, want) > ref.TOLERANCE["value"]


# ---------------------------------------------------------------------------
# (e) the engine: snapshots, the counters, the refusals
# ---------------------------------------------------------------------------

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
def test_engine_two_threads_share_a_prefix_and_the_counters_move(
        model, backend):
    eng = make_engine(model, attention_backend=backend)
    assert eng.state_pool.n_slots == default_state_slots(4) == 17
    assert eng.kv_bytes_per_token == 1 * 2 * 32 * 4  # one layer holds rows
    shared = tokens(100, seed=7)
    a = run(eng, model, shared + tokens(5, seed=8), "a")
    assert a.cached_tokens == 0 and eng.state_restores == 0
    b = run(eng, model, shared + tokens(9, seed=9), "b")
    assert b.cached_tokens == 64 and eng.state_restores == 1
    assert b.state_restored is not None and a.state_restored is None
    cold = make_engine(model, attention_backend=backend)
    again = run(eng, model, shared + tokens(9, seed=9), "b2")
    fresh = run(cold, model, shared + tokens(9, seed=9), "b2")
    assert again.cached_tokens == 96 and fresh.cached_tokens == 0
    assert again.output_ids == fresh.output_ids
    sec = eng.state_section()
    assert sec["state_bytes_per_slot"] == 3 * (3 * 96 + 64 * 16) * 4
    snap = eng.metrics.snapshot(engine=eng)["engine"]
    assert (snap["state_layers"], snap["row_layers"],
            snap["routed_layers"]) == (3, 1, 4)
    assert (snap["ssd_chunk_trips"] > 0) == (backend == "pallas")
    assert snap["ssd_rows_dispatched"] == 3 * snap["prefill_rows_dispatched"]
    # 4 lanes: the dense form reads every held expert, and the PROGRAM counts
    # the picks, because which of them fell on this share only it knows
    assert eng._programs.moe_dispatch(4) == "dense"
    assert eng._programs.tallies(4)
    assert snap["moe_experts_read"] == snap["moe_experts_held"] > 0
    assert snap["moe_experts_held"] % (4 * 4) == 0
    # one lane a pass here: 3 picks x 4 routed layers, some on experts 0-3
    passes = snap["moe_experts_held"] // (4 * 4)
    assert snap["moe_picks_routed"] == passes * 3 * 4
    assert 0 < snap["moe_picks_held"] < snap["moe_picks_routed"]


def test_engine_counts_picks_on_the_host_where_the_experts_are_held_whole():
    cfg = tiny_cfg(num_experts=8, num_experts_routed=0)
    model = cfg, init_params(cfg, jax.random.PRNGKey(1))
    eng = make_engine(model)
    assert not eng._programs.tallies(4)
    run(eng, model, tokens(20, seed=3), "w")
    snap = eng.metrics.snapshot(engine=eng)["engine"]
    assert snap["moe_picks_held"] == snap["moe_picks_routed"] > 0
    assert snap["moe_picks_routed"] * 8 == snap["moe_experts_held"] * 3
    assert eng._programs.picks_a_pass(2) == 2 * 3 * 4


def test_engine_counts_picks_where_it_dispatches_by_token():
    """16 lanes over 4 of 16 experts, top-2: the token form, whose program
    counts the experts read and the picks a pass."""
    cfg = tiny_cfg(num_experts_per_tok=2, num_experts_routed=16)
    model = cfg, init_params(cfg, jax.random.PRNGKey(1))
    eng = make_engine(model, max_batch=16, num_pages=96)
    assert eng._programs.moe_dispatch(16) == "token"
    assert eng._programs.tallies(16)
    run(eng, model, tokens(20, seed=3), "t")
    snap = eng.metrics.snapshot(engine=eng)["engine"]
    assert 0 < snap["moe_experts_read"] < snap["moe_experts_held"]
    assert 0 <= snap["moe_picks_held"] < snap["moe_picks_routed"]
    passes = snap["moe_experts_held"] // (4 * 4)
    assert snap["moe_picks_routed"] == passes * 2 * 4  # one lane active


def _mesh(**axes):
    from kafka_tpu.parallel import MeshConfig, make_mesh

    return make_mesh(MeshConfig(**axes))


@pytest.mark.parametrize("path,kw,mesh,why", [
    ("speculative verify", dict(speculative_k=2), None, "rolled back"),
    ("int8 pool", dict(kv_quantize="int8"), None, "float32 state slots"),
    ("KV tier", dict(kv_host_tier_mb=64), None, "without its snapshot"),
    ("pp / tp / ep mesh", {}, dict(tp=2), "state slots live on one device"),
    ("pp / tp / ep mesh", {}, dict(ep=2), "state slots live on one device"),
    ("pp / tp / ep mesh", {}, dict(pp=2), "state slots live on one device"),
], ids=["speculative", "int8-pool", "tier", "tp", "ep", "pp"])
def test_engine_refuses_by_name(model, path, kw, mesh, why):
    cfg, params = model
    with pytest.raises(RecurrentStateUnsupported, match=path) as err:
        InferenceEngine(cfg, params, EngineConfig(**dict(ENGINE, **kw)),
                        mesh=None if mesh is None else _mesh(**mesh))
    assert why in str(err.value)


def test_int8_weights_and_the_loader_refuse_the_tree_by_name(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="mixer-then-feed-forward"):
        quantize_params(params, cfg)
    with pytest.raises(NotImplementedError, match="granitemoehybrid"):
        convert_hf_state_dict({}, cfg)
    # and a sharded attention path has no published scale
    from kafka_tpu.models.mixers.gqa import _attention_core

    q = jnp.zeros((1, 2, 4, 16))
    with pytest.raises(NotImplementedError, match="softmax scale"):
        _attention_core(q, q[:, :, :2], q[:, :, :2],
                        cfg.replace(prefill_ring=True), jnp.zeros((1, 2)),
                        None, None, None, None, None, None, 0)


# ---------------------------------------------------------------------------
# (f) the memory plan and the configuration's file
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
    shapes = jax.eval_shape(lambda: init_params(cut, jax.random.PRNGKey(0)))
    assert shapes["layers"]["wg"].shape == (10, 36, 4096, 768)
    assert shapes["layers"]["wd"].shape == (10, 36, 768, 4096)
    assert shapes["layers"]["router"].shape == (10, 4096, 72)
    assert "router_bias" not in shapes["layers"]
    assert shapes["layers"]["ws_g"].shape == (10, 4096, 1536)
    assert shapes["attn"][MAMBA2]["w_in"].shape == (9, 4096, 16768)
    assert shapes["attn"][GLOBAL]["wk"].shape == (1, 4096, 8, 128)
    assert shapes["embed"].shape == (50176, 4096) and "lm_head" not in shapes
    assert round(planner.weight_bytes_per_device(cut) / 1e9, 2) == 9.51
    slots = default_state_slots(16)
    assert slots == 65
    plan = planner.plan_memory(
        cut, num_pages=8192, page_size=16, max_pages_per_seq=1024,
        max_batch=16, prefill_bucket=512, state_slots=slots,
        grammar_table_bytes=0)
    k_pool, v_pool = jax.eval_shape(lambda: make_kv_pool_arrays(
        cut, 8192, 16, state_slots=slots))
    rows = k_pool.size * 2 + v_pool["v"].size * 2
    # ONE row-holding layer x 2 x 1,024 values x 2 B x 131,072 slots
    assert plan.kv_pool_bytes == rows == 1 * 2 * 1024 * 2 * 8192 * 16
    assert v_pool["conv"].shape == (9, slots, 8, 3168)
    assert v_pool["ssd"].shape == (9, slots, 8192, 128)
    held = (v_pool["conv"].size + v_pool["ssd"].size) * 4
    assert plan.state_bytes == held == slots * cut.state_bytes_per_slot
    assert plan.fits
    planned = plan.weight_bytes + plan.kv_pool_bytes + plan.state_bytes
    assert round(planned / 1e9, 2) == 12.56
    model_ = planner.dispatch_cost_model(cut)
    assert model_.expert_bytes == 10 * 36 * 3 * 4096 * 768 * 2
    # and the configuration's file is that cut, to the byte
    path = os.path.join(ROOT, "benchmarks", "configs",
                        "granite-4.0-h-small.json")
    filed = config_from_hf_json(path)
    assert filed.replace(name=cut.name) == cut
    with open(path) as f:
        spec = json.load(f)
    assert list(spec["reduced"]) == ["num_hidden_layers", "num_local_experts",
                                     "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in spec["reduced"]:
            assert spec[key] == value, key
    assert spec["scopes"] == ["ssd_proj", "ssd_conv", "ssd_gate", "ssd_scan",
                              "moe_shared"]
    nem = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                      "nemotron-3-nano-30b-a3b.json")))
    # Nemotron's serving shape at 16 lanes, with the Pallas kernels PINNED:
    # `auto` sends a 4,096 x 1,024 merged product to XLA (Mixtral's geometry)
    serving = dict(spec["serving"], max_batch=32)
    assert serving.pop("attention_backend") == "pallas"
    assert serving == nem["serving"]
    assert spec["check"] == {
        "reference": "granitemoehybrid", "driver": "granitemoehybrid_pool",
        "n_prefill": 1536, "n_decode": 47, "pages_per_seq": 100}
    assert (1536 - ref.RUN_IN) % 16 == 0 and drv.RUN_IN == ref.RUN_IN == 48
    assert set(spec["assumed"]) >= {"in_proj_split", "gated_norm", "routing",
                                    "residual_form", "seeded_initialiser"}
    assert "EIGHT chips" in spec["deployment"]


def test_the_seeded_initialiser_draws_against_the_multipliers(model):
    """A8: each leaf `attention_multiplier` or `residual_multiplier` scales
    is drawn at its fan-in deviation divided by it, so scores and both
    sublayers' outputs are of order 1 under the published scalars."""
    cfg, params = model
    std = lambda a: float(jnp.std(a.astype(jnp.float32)))  # noqa: E731
    attn, layers = params["attn"], params["layers"]
    # NOT the embedding: tied, it would echo the last token (init_params)
    assert std(params["embed"]) == pytest.approx(64 ** -0.5, rel=0.05)
    assert std(attn[GLOBAL]["wq"]) == pytest.approx(64 ** -0.5, rel=0.1)
    # W_k x 2 head_dim^-1/2 / attention_multiplier = x 16 at 16 wide, 1 / 32
    # (scores of deviation 2 under the published scale)
    assert std(attn[GLOBAL]["wk"]) == pytest.approx(16 * 64 ** -0.5, rel=0.1)
    assert std(attn[GLOBAL]["wo"]) == pytest.approx(
        64 ** -0.5 / 0.22, rel=0.1)
    assert std(attn[MAMBA2]["w_out"]) == pytest.approx(
        64 ** -0.5 / 0.22, rel=0.1)
    assert std(layers["wd"]) == pytest.approx(32 ** -0.5 / 0.22, rel=0.1)
    assert std(layers["ws_d"]) == pytest.approx(48 ** -0.5 / 0.22, rel=0.1)
    assert std(layers["wg"]) == pytest.approx(64 ** -0.5, rel=0.1)
    # without the scalars the draws are the lead-and-routed tree's own
    plain = init_params(tiny_cfg(residual_multiplier=1.0,
                                 attention_multiplier=0.0),
                        jax.random.PRNGKey(0))
    assert std(plain["layers"]["wd"]) == pytest.approx(32 ** -0.5, rel=0.1)
    assert std(plain["attn"][GLOBAL]["wk"]) == pytest.approx(
        64 ** -0.5, rel=0.1)


# ---------------------------------------------------------------------------
# (g) the scopes reach the compiled program; (h) the benchmark's entries
# ---------------------------------------------------------------------------

def test_scopes_reach_the_hlo_and_the_scalings_sit_in_their_adds_scope(model):
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
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = ["dev_ssd_g1_share", "ssd_g1_step_roofline",
           "ssd_g1_chunk_roofline", "gqa4_attn_roofline",
           "ep2_top10_experts_read_share", "ssd_g1_state_restore_share",
           "moe_pairs_per_expert"]
    # (appended together in PR 63: the seven entries from the 91st on;
    # later PRs append after them)
    assert [m["name"] for m in bench["per_layer"][90:97]] == new
    for m in bench["per_layer"][90:97]:
        assert m["workloads"] == [CELL], m["name"]
        assert m["moves"] == "tpot_p50_ms"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
    for m in bench["per_layer"][:90] + bench["per_layer"][97:]:
        assert CELL not in m.get("workloads", ()), m["name"]
    entry = bench["workloads"][12]
    assert (entry["name"], entry["config"], entry["traffic"],
            entry["chips"]) == (CELL, "granite-4.0-h-small", "chat-decode", 1)
    assert bench["workloads"][12] == entry  # the thirteenth cell
    config = bench["configs"][12]
    assert config["reduced"] == ["num_hidden_layers", "num_local_experts",
                                 "vocab_size"]
    assert config["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/"
        "config.json")
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
        cell = json.load(f)
    assert cell["params"] == {} and cell["chips"] == 1
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "chat-decode.json")) as f:
        traffic = json.load(f)
    assert (traffic["clients"], traffic["stagger_s"]) == (16, 0.9)


def test_the_pairs_reader_reads_the_new_counters_or_nothing():
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    reader = _load("layer_metrics", "moe_pairs_per_expert")
    ctx = {"before": {"engine": {"moe_picks_held": 100,
                                 "moe_experts_read": 50}},
           "after": {"engine": {"moe_picks_held": 100 + 800,
                                "moe_experts_read": 50 + 327}}}
    assert reader.read(ctx) == pytest.approx(800 / 327)
    parent = {"before": {"engine": {}}, "after": {"engine": {}}}
    assert reader.read(parent) is None
    idle = {"before": {"engine": {"moe_picks_held": 0,
                                  "moe_experts_read": 0}},
            "after": {"engine": {"moe_picks_held": 0,
                                 "moe_experts_read": 0}}}
    assert reader.read(idle) is None
