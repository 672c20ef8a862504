"""Falcon-H1-34B-Instruct on the served path (ISSUE 54; `falcon_h1`): every
layer holds pages AND a state slot, a Mamba-2 (SSD) mixer in parallel with
grouped-query attention on one normed input, the state updated in place by a
chunked prefill kernel and a decode-step kernel, under the family's muP
multipliers, on the homogeneous dense stack.

CPU, float32, tiny widths (2 groups, 4 heads, a query group of 5), seeded
weights, against the plain reference `benchmarks/references/falconh1.py` (the
recurrence token by token, imports nothing of kafka_tpu).  The kernels run
interpreted.

TOLERANCES.  `forward` and the reference do the same float32 arithmetic in
another order: they agree to ~1e-6 relative RMS of the logits.  REF_TOL =
1e-4 leaves 100x room.  A MECHANISM taken out of the reference must move the
logits past the tolerance the chip's check uses (`ref.TOLERANCE`), at these
sizes too, under the scaled initialiser; the two PRECISION variants (a
bfloat16 accumulator, a bfloat16 state) are small at 64 wide and are held to
10 x REF_TOL here (their readings at the published widths are PERF.md's).
The kernels against the token-by-token recurrence: KERNEL_TOL = 5e-5 absolute
on outputs of order 1-10 and states of order 1 (float32 sums in another
order over up to 128 rows).  Engine tests compare TOKENS, greedy, against the
uncached forward: exact.
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
    CONFIGS, PARALLEL, UnsupportedConfigError, config_from_hf_json,
    holds_rows, holds_state,
)
from kafka_tpu.models.cache import (
    HybridPathError, StatePlan, _read_state, _write_state,
)
from kafka_tpu.models.init_params import _init_parallel_params
from kafka_tpu.models.mixers.state import ssd_mup_vector
from kafka_tpu.ops.pallas import ssd as sk
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime.engine import RecurrentStateUnsupported
from kafka_tpu.runtime.kv_cache import default_state_slots, make_kv_pool_arrays
from kafka_tpu.runtime.metrics import STATE_METRIC_KEYS
from kafka_tpu.runtime.step_programs import StepPrograms

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_TOL = 1e-4
KERNEL_TOL = 5e-5

# the catalog row's `config` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120,
}
CUT = dict(num_hidden_layers=7, vocab_size=32640)


def _load(folder, name):
    path = os.path.join(ROOT, "benchmarks", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references", "falconh1")
drv = _load("drivers", "falconh1_pool")


def tiny_cfg(layers=3, backend="xla", **kw):
    base = dict(
        name="tiny-falconh1", vocab_size=300, hidden_size=64,
        intermediate_size=96, num_layers=layers, num_heads=5, num_kv_heads=1,
        head_dim=16, rope_theta=1e11, layer_types=(PARALLEL,) * layers,
        ssd_heads=4, ssd_head_dim=16, ssd_d_state=64, ssd_groups=2,
        ssd_conv_kernel=4, embedding_multiplier=5.656854249492381,
        lm_head_multiplier=0.0078125, attention_in_multiplier=1.0,
        attention_out_multiplier=0.0375, key_multiplier=0.011048543456039804,
        ssm_in_multiplier=0.25, ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
        dtype="float32", tie_word_embeddings=False,
        attention_backend=backend)
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
# (d) the configuration
# ---------------------------------------------------------------------------

def _cfg_of(tmp_path, **over):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(dict(PUBLISHED, **over)))
    return config_from_hf_json(str(path))


def test_config_from_hf_json_honours_every_key(tmp_path):
    cfg = _cfg_of(tmp_path)
    assert cfg.layer_types == (PARALLEL,) * 72
    assert (cfg.hidden_size, cfg.num_layers, cfg.vocab_size) == (
        5120, 72, 261120)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (20, 4, 128)
    assert cfg.q_per_kv == 5 and cfg.intermediate_size == 21504
    assert (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_d_state, cfg.ssd_groups,
            cfg.ssd_conv_kernel) == (32, 128, 256, 2, 4)
    assert cfg.ssd_conv_dim == 4096 + 2 * 2 * 256
    assert cfg.rope_theta == 1e11 and isinstance(cfg.rope_theta, float)
    assert cfg.rms_norm_eps == 1e-5 and cfg.max_context == 262144
    assert not cfg.tie_word_embeddings and not cfg.is_moe
    # every multiplier, where the equations put it
    assert cfg.embedding_multiplier == PUBLISHED["embedding_multiplier"]
    assert cfg.lm_head_multiplier == 1 / 128
    assert (cfg.attention_in_multiplier, cfg.attention_out_multiplier) == (
        1.0, 0.0375)
    assert cfg.key_multiplier == PUBLISHED["key_multiplier"]
    assert (cfg.ssm_in_multiplier, cfg.ssm_out_multiplier) == (
        0.25, PUBLISHED["ssm_out_multiplier"])
    assert cfg.ssm_multipliers == tuple(PUBLISHED["ssm_multipliers"])
    assert cfg.mlp_multipliers == tuple(PUBLISHED["mlp_multipliers"])
    # the homogeneous dense stack, one layer a period: the plain layer scan
    assert not cfg.lead_tree and not cfg.kind_leaves
    assert not cfg.hybrid_decoder and not cfg.by_kind
    assert cfg.pattern == (0, (PARALLEL,))
    # EVERY layer holds rows and a state
    assert cfg.has_state
    assert cfg.kv_layers == cfg.state_layers == cfg.num_layers == 72
    assert cfg.state_shapes() == (("conv", (8, 1920)), ("ssd", (4096, 256)))
    assert cfg.state_bytes_per_slot == 72 * (3 * 5120 + 4096 * 256) * 4
    assert cfg.kv_row_widths(PARALLEL) == (512, 512)
    assert cfg.kv_values_per_token == 72 * 2 * 512
    cut = _cfg_of(tmp_path, **CUT)
    assert cut.kv_layers == cut.state_layers == cut.num_layers == 7
    assert cut.state_bytes_per_slot == 7 * (4194304 + 61440) == 29790208
    assert cut.kv_values_per_token * 2 == 14336
    # absent multipliers are 1: nothing is applied
    bare = _cfg_of(tmp_path, **{k: 1 for k in (
        "embedding_multiplier", "lm_head_multiplier",
        "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier")}, ssm_multipliers=None, mlp_multipliers=None)
    assert bare.ssm_multipliers == () and bare.mlp_multipliers == ()
    assert ssd_mup_vector(bare) is None
    mup = ssd_mup_vector(cfg)
    assert len(mup) == 9248 and mup[0] == mup[4095] == cfg.ssm_multipliers[0]
    assert mup[4096] == 0.25 and mup[8192] == cfg.ssm_multipliers[2]
    assert mup[8704] == 0.5 and mup[9216] == mup[-1] == cfg.ssm_multipliers[4]


@pytest.mark.parametrize("over,key", [
    (dict(attn_layer_indices=[0, 2]), "attn_layer_indices"),
    (dict(rope_scaling={"factor": 2.0}), "rope_scaling"),
    (dict(mamba_use_mlp=False), "mamba_use_mlp"),
    (dict(mamba_rms_norm=False), "mamba_rms_norm"),
    (dict(mamba_norm_before_gate=True), "mamba_norm_before_gate"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(mlp_bias=True), "mlp_bias"),
    (dict(projectors_bias=True), "projectors_bias"),
    (dict(mamba_conv_bias=False), "mamba_conv_bias"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(mamba_d_ssm=5120), "mamba_d_ssm"),
    (dict(mamba_n_groups=3), "groups"),
    (dict(mamba_d_conv=1), "ssd_conv_kernel"),
    (dict(mamba_d_state=0), "ssd_d_state"),
    (dict(ssm_multipliers=[0.5, 0.5]), "ssm_multipliers"),
    (dict(mlp_multipliers=[0.5]), "mlp_multipliers"),
], ids=["some_layers", "rope_scaling", "no_mlp", "no_norm", "norm_first",
        "attn_bias", "proj_bias", "mlp_bias", "projectors_bias",
        "no_conv_bias", "gelu", "d_ssm", "groups", "one_tap", "no_state",
        "two_ssm_multipliers", "one_mlp_multiplier"])
def test_config_refuses_by_key(tmp_path, over, key):
    with pytest.raises(UnsupportedConfigError, match=key):
        _cfg_of(tmp_path, **over)


def test_the_parallel_kind_needs_its_key_and_stands_alone():
    with pytest.raises(UnsupportedConfigError, match="unknown kinds"):
        tiny_cfg(ssd_heads=0)
    with pytest.raises(UnsupportedConfigError, match="every layer"):
        tiny_cfg(layer_types=(PARALLEL, "full_attention", PARALLEL))
    with pytest.raises(UnsupportedConfigError, match="dense grouped-query"):
        tiny_cfg(num_experts=4)
    assert holds_rows(PARALLEL) and holds_state(PARALLEL)


# The layer accounting of every preset and every configuration file, pinned
# from the parent commit (4fd4bec), where `ROW_KINDS` and `STATE_KINDS` were
# two disjoint tuples: [kv_layers, state_layers, state_shapes,
# state_bytes_per_slot, the planner's attention flops a cached token].
PARENT_ACCOUNT = {
    "preset:debug-290m": [12, 0, [], 0, 49152.0],
    "preset:llama-3-70b": [80, 0, [], 0, 2621440.0],
    "preset:llama-3-8b": [32, 0, [], 0, 524288.0],
    "preset:llama-3.1-8b": [32, 0, [], 0, 524288.0],
    "preset:llama-3.2-1b": [16, 0, [], 0, 131072.0],
    "preset:llama-3.2-3b": [28, 0, [], 0, 344064.0],
    "preset:mixtral-8x7b": [32, 0, [], 0, 524288.0],
    "preset:tiny": [2, 0, [], 0, 512.0],
    "preset:tiny-gqa": [2, 0, [], 0, 1024.0],
    "preset:tiny-moe": [2, 0, [], 0, 1024.0],
    "preset:tiny-vision": [2, 0, [], 0, 512.0],
    "file:dots3-note-prev.json": [6, 0, [], 0, 1671168.0],
    "file:k-exaone-236b-a23b.json": [6, 0, [], 0, 196608.0],
    "file:kanana-2-30b-a3b.json": [6, 0, [], 0, 417792.0],
    "file:lfm2-8b-a1b.json": [3, 11, [["conv", [2, 2048]]], 180224, 24576.0],
    "file:mellum2-12b-a2.5b.json": [8, 0, [], 0, 131072.0],
    "file:mixtral-8x7b.json": [2, 0, [], 0, 32768.0],
    "file:phi-4-mini-flash-reasoning.json": [
        9, 9, [["conv", [3, 5120]], ["ssm", [16, 5120]]], 3502080, 163840.0],
    "file:solar-open2-250b.json": [
        2, 6, [["conv", [8, 9216]], ["delta", [8192, 128]]], 26935296,
        65536.0],
    "file:yi-1.5-9b-dp4.json": [20, 0, [], 0, 327680.0],
    "file:yi-1.5-9b.json": [20, 0, [], 0, 327680.0],
}
NEW_ACCOUNT = {
    "file:falcon-h1-34b.json": [
        7, 7, [["conv", [8, 1920]], ["ssd", [4096, 256]]], 29790208,
        2.0 * 7 * 20 * 256],
    # (PR 56: latent rows in all 8 layers, no state; 32 heads x (2 x 512 + 64))
    "file:xing4.0-29b-a4b.json": [8, 0, [], 0, 2.0 * 8 * 32 * 1088],
    # (PR 60: a layer is ONE sublayer: rows in 2 layers, a lone SSD mixer's
    # state in 7, neither in the 7 routed ones; 32 heads x 2 x 128)
    "file:nemotron-3-nano-30b-a3b.json": [
        2, 7, [["conv", [8, 2304]], ["ssd", [4096, 128]]], 15196160,
        2.0 * 2 * 32 * 256],
    # (PR 63: two sublayers a layer: rows in the ONE attention layer, a lone
    # SSD mixer's state in 9, a routed block behind all ten; 32 heads x 2 x
    # 128)
    "file:granite-4.0-h-small.json": [
        1, 9, [["conv", [8, 3168]], ["ssd", [8192, 128]]], 38661120,
        2.0 * 1 * 32 * 256],
    # (PR 66: rows in the 4 multi-head attention layers, a Gated DeltaNet
    # state laid [96, 5760] in 12; 30 heads x 2 x 128)
    "file:olmo-hybrid-7b.json": [
        4, 12, [["conv", [8, 4320]], ["delta", [96, 5760]]], 28200960,
        2.0 * 4 * 30 * 256],
}


def _configs():
    out = {"preset:" + name: cfg for name, cfg in CONFIGS.items()
           if name in {k[7:] for k in PARENT_ACCOUNT}}
    folder = os.path.join(ROOT, "benchmarks", "configs")
    for name in sorted(os.listdir(folder)):
        out["file:" + name] = config_from_hf_json(os.path.join(folder, name))
    return out


def test_layer_accounting_is_the_parents_for_every_other_configuration():
    from kafka_tpu.runtime.planner import dispatch_cost_model

    configs = _configs()
    assert set(configs) == set(PARENT_ACCOUNT) | set(NEW_ACCOUNT)
    both = []
    for name, cfg in configs.items():
        got = [cfg.kv_layers, cfg.state_layers,
               [[leaf, list(shape)] for leaf, shape in cfg.state_shapes()],
               cfg.state_bytes_per_slot,
               dispatch_cost_model(cfg).attn_flops_per_kv]
        assert got == {**PARENT_ACCOUNT, **NEW_ACCOUNT}[name], name
        kinds = set(cfg.layer_types)
        if any(holds_rows(k) and holds_state(k) for k in kinds):
            both.append(name)
            assert kinds == {PARALLEL}
            assert cfg.kv_layers == cfg.state_layers == cfg.num_layers
        elif cfg.has_state:
            assert cfg.kv_layers + cfg.state_layers <= cfg.num_layers
    # the new kind is the only one for which a layer counts in both
    assert both == ["file:falcon-h1-34b.json"]


# ---------------------------------------------------------------------------
# (k) the kernels and the XLA scan against the token-by-token recurrence
# ---------------------------------------------------------------------------

def _recurrence(x, Bm, Cm, g, S0):
    """The equation, one token at a time, in numpy float64: S [P, N] a head.
    x [B, T, H, P] is dt x.  Returns (y [B, T, H, P], S after the last
    row)."""
    x, Bm, Cm, g = (np.asarray(a, np.float64) for a in (x, Bm, Cm, g))
    B, T, H, _ = x.shape
    per = H // Bm.shape[2]
    S = np.asarray(S0, np.float64).copy()
    y = np.zeros(x.shape)
    for t in range(T):
        for b in range(B):
            for h in range(H):
                S[b, h] = (np.exp(g[b, t, h]) * S[b, h]
                           + np.outer(x[b, t, h], Bm[b, t, h // per]))
                y[b, t, h] = S[b, h] @ Cm[b, t, h // per]
    return y, S


def _rows(B, T, H, P, G, N, seed=0, decay=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = 0.3 * jax.random.normal(ks[0], (B, T, H, P))
    Bm = jax.nn.silu(jax.random.normal(ks[1], (B, T, G, N)))
    Cm = jax.nn.silu(jax.random.normal(ks[2], (B, T, G, N)))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    return x, Bm, Cm, g


def _ssd(leaf, plan, rows, kernel, layer=1):
    return sk.ssd(leaf, layer, plan, *rows, kernel=kernel,
                  read_state=_read_state, write_state=_write_state)


@pytest.mark.parametrize("chunk", [32, 64, 128])
@pytest.mark.parametrize("decay", [0.05, 1.0, 25.0],
                         ids=["slow", "unit", "past_1e-30_a_chunk"])
def test_chunk_kernel_and_scan_equal_the_recurrence(monkeypatch, chunk, decay):
    """Ragged `lens`, a lane from zeros, a lane from another slot, an
    inactive lane; at decay 25 a row's log-decay averages -20 and a CHUNK's
    cumulative decay passes 1e-30 within four rows (a quotient of
    exponentials would overflow): the kernel's exponents are differences and
    never positive."""
    monkeypatch.setattr(sk, "CHUNK", chunk)
    B, T, H, P, G, N = 3, 128, 4, 16, 2, 32
    rows = _rows(B, T, H, P, G, N, seed=chunk, decay=decay)
    lens = np.array([128, 70, 0])
    leaf0 = jax.random.normal(jax.random.PRNGKey(9), (2, 7, H * P, N))
    plan = StatePlan(lens=jnp.asarray(lens), src=jnp.array([4, 1, 2]),
                     dst=jnp.array([0, 1, 2]), snap=jnp.array([5, 6, 3]),
                     fresh=jnp.array([False, True, False]))
    if decay == 25.0:
        assert float(jnp.min(jnp.sum(rows[3][:, :chunk], axis=1))) < -69.0
    S0 = np.array(leaf0[1, jnp.array([4, 1, 2])]).reshape(B, H, P, N)
    S0[1] = 0.0  # the fresh lane
    want_y = np.zeros((B, T, H, P))
    want_S = S0.copy()
    for b in range(B):
        n = lens[b]
        if n:
            y, S = _recurrence(*(a[b:b + 1, :n] for a in rows), S0[b:b + 1])
            want_y[b, :n], want_S[b] = y[0], S[0]
    real = np.arange(T)[None, :] < lens[:, None]
    for kernel in (False, True):
        y, leaf = _ssd(leaf0, plan, rows, kernel)
        assert np.abs(np.asarray(y) - want_y)[real].max() < KERNEL_TOL
        for b in (0, 1):  # the lane's slot and its snapshot
            for slot in (int(plan.dst[b]), int(plan.snap[b])):
                got = np.asarray(leaf[1, slot]).reshape(H, P, N)
                assert np.abs(got - want_S[b]).max() < KERNEL_TOL
        # the source slot of lane 0, the other layer, and every slot of the
        # inactive lane (the kernel; the scan writes back what it read)
        assert np.array_equal(leaf[0], leaf0[0])
        assert np.array_equal(leaf[1, 4], leaf0[1, 4])
        assert np.array_equal(leaf[1, 2], leaf0[1, 2])
    assert np.array_equal(_ssd(leaf0, plan, rows, True)[1][1, 3],
                          leaf0[1, 3])


def test_the_kernels_tile_what_they_say_and_hold_a_group_a_step():
    assert sk.chunk_rows(512) == sk.chunk_rows(128) == 128
    assert sk.chunk_rows(64) == 64 and sk.chunk_rows(16) == 16
    assert sk.chunk_rows(192) is None and sk.chunk_rows(1) is None
    # the served widths: a whole group's 16 heads a grid step (2 MB of state)
    assert sk.heads_a_step(32, 2, 128, 256) == 16
    assert sk.heads_a_step(4, 2, 16, 64) == 2
    assert sk.heads_a_step(32, 1, 128, 256) == 16  # half a group of 32


def test_step_kernel_equals_the_recurrence_and_spares_idle_lanes():
    B, H, P, G, N = 3, 4, 16, 2, 32
    rows = _rows(B, 1, H, P, G, N, seed=3)
    leaf0 = jax.random.normal(jax.random.PRNGKey(9), (2, 5, H * P, N))
    plan = StatePlan(lens=jnp.array([1, 0, 1]))
    want_y, want_S = _recurrence(
        *rows, np.asarray(leaf0[0, :B]).reshape(B, H, P, N))
    for kernel in (False, True):
        y, leaf = _ssd(leaf0, plan, rows, kernel, layer=0)
        for b in (0, 2):
            assert np.abs(np.asarray(y[b]) - want_y[b]).max() < KERNEL_TOL
            assert np.abs(np.asarray(leaf[0, b]).reshape(H, P, N)
                          - want_S[b]).max() < KERNEL_TOL
        assert np.array_equal(leaf[0, 1], leaf0[0, 1])
        assert np.array_equal(leaf[:, 3:], leaf0[:, 3:])
        assert np.array_equal(leaf[1], leaf0[1])


def test_chunks_equal_one_chunk_and_a_stale_state_fails():
    """256 rows at once equal 128 + 128 through the slot (the kernel both
    times); resumed from the WRONG slot the second launch's rows move."""
    B, T, H, P, G, N = 1, 256, 4, 16, 2, 32
    rows = _rows(B, T, H, P, G, N, seed=5, decay=0.05)
    leaf0 = jnp.zeros((1, 3, H * P, N))
    full = StatePlan(lens=jnp.array([T]), src=jnp.array([0]),
                     dst=jnp.array([0]), snap=jnp.array([1]),
                     fresh=jnp.array([True]))
    y_one, leaf_one = _ssd(leaf0, full, rows, True, layer=0)

    def half(lo, leaf, src, fresh):
        plan = StatePlan(lens=jnp.array([128]), src=jnp.array([src]),
                         dst=jnp.array([0]), snap=jnp.array([1]),
                         fresh=jnp.array([fresh]))
        return _ssd(leaf, plan, [a[:, lo:lo + 128] for a in rows], True,
                    layer=0)

    y_a, leaf = half(0, leaf0, 0, True)
    y_b, leaf_two = half(128, leaf, 1, False)  # resumed from the snapshot
    two = np.concatenate([np.asarray(y_a), np.asarray(y_b)], axis=1)
    assert np.abs(two - np.asarray(y_one)).max() < KERNEL_TOL
    assert np.abs(np.asarray(leaf_two[0, 0] - leaf_one[0, 0])).max() \
        < KERNEL_TOL
    y_bad, _ = half(128, leaf, 2, False)  # slot 2 was never written
    assert np.abs(np.asarray(y_bad) - np.asarray(y_one)[:, 128:]).max() \
        > 1000 * KERNEL_TOL


# ---------------------------------------------------------------------------
# (a) forward against the reference
# ---------------------------------------------------------------------------

def test_full_forward_logits(model):
    cfg, params = model
    ids = tokens(40, seed=3)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, jnp.asarray([ids]),
                         jnp.arange(40)[None])
    want = ref.reference_logits(params, ref.hyper(cfg), ids, list(range(40)))
    assert rel_rms(got[0], want["logits"]).max() < REF_TOL


def test_attention_in_multiplier_is_applied(tmp_path):
    """1 as published, so that it is applied at all is shown with a value
    that is not (q, k and v all see it: the reference agrees, and the
    reference with it set to 1 does not)."""
    cfg = tiny_cfg(attention_in_multiplier=0.5)
    params = init_params(cfg, jax.random.PRNGKey(1))
    ids = tokens(40, seed=3)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, jnp.asarray([ids]),
                         jnp.arange(40)[None])
    hp = ref.hyper(cfg)
    want = ref.reference_logits(params, hp, ids, list(range(40)))["logits"]
    assert rel_rms(got[0], want).max() < REF_TOL
    variants = ref.variants(hp)
    assert "attention_in_multiplier_1" in variants
    off = ref.reference_logits(params, variants["attention_in_multiplier_1"],
                               ids, list(range(40)))["logits"]
    assert rel_rms(off, want).max() > ref.TOLERANCE["value"]


PRECISION = {"bf16_accumulate", "bf16_accumulate_256", "bf16_state"}


def _variant_errors(cfg, params):
    ids = tokens(171, seed=3)
    hp = ref.hyper(cfg)
    pos = list(range(159, 171))
    base = ref.reference_logits(params, hp, ids, pos)["logits"]
    return {name: rel_rms(ref.reference_logits(
        params, variant, ids, pos)["logits"], base)
        for name, variant in ref.variants(hp).items()}


def test_reference_variants_exceed_the_tolerance(model):
    """Every mechanism the reference can take out moves the logits of the 12
    positions behind a launch boundary at row 144 past the CHIP's tolerance
    (some position's error is over it: what `compare_logits` fails by) under
    the scaled initialiser; the precision variants past 10 x REF_TOL."""
    cfg, params = model
    errors = _variant_errors(cfg, params)
    for name, err in errors.items():
        floor = 10 * REF_TOL if name in PRECISION else ref.TOLERANCE["value"]
        assert err.max() > floor, (name, err.max())
    assert {"no_ssm_branch", "no_attention_branch", "embedding_multiplier_1",
            "lm_head_multiplier_1", "attention_out_multiplier_1",
            "key_multiplier_1", "ssm_in_multiplier_1", "ssm_out_multiplier_1",
            "ssm_multipliers_0_1", "ssm_multipliers_1_1",
            "ssm_multipliers_2_1", "ssm_multipliers_3_1",
            "ssm_multipliers_4_1", "mlp_multipliers_0_1",
            "mlp_multipliers_1_1", "decay_one", "no_d_skip", "no_conv_bias",
            "groups_swapped", "norm_ungrouped", "norm_before_gate",
            "conv_tail_zeroed_at_chunk", "state_lost_at_chunk",
            "rotation_off"} | PRECISION == set(errors)
    assert "attention_in_multiplier_1" not in errors  # 1 as published


def test_the_unscaled_initialiser_would_blind_the_check(model):
    """Why the initialiser is scaled: with every leaf at 1 / sqrt(fan_in)
    `key_multiplier` flattens the softmax and the mixers enter the residual
    at 0.04 and 0.09, so that the attention branch taken out, the rotation,
    the groups swapped and a tail or a state lost at a launch boundary all
    stay UNDER the chip's tolerance at every position, and `key_multiplier`
    at most of them (a fiftieth of what it moves under the scaled one).  The
    SSM branch is then seen through its D skip alone: the scan is not."""
    cfg, params = model
    unscaled = _init_parallel_params(
        cfg, jax.random.split(jax.random.PRNGKey(0), 10), jnp.float32,
        scaled=False)
    errors = _variant_errors(cfg, unscaled)
    tol = ref.TOLERANCE["value"]
    for name in ("no_attention_branch", "rotation_off", "groups_swapped",
                 "state_lost_at_chunk", "conv_tail_zeroed_at_chunk"):
        assert errors[name].max() < tol, (name, errors[name].max())
    scaled = _variant_errors(cfg, params)["key_multiplier_1"]
    assert np.median(errors["key_multiplier_1"]) < tol
    assert errors["key_multiplier_1"].max() < scaled.min() / 20


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmarks", "references",
                           "falconh1.py")) as f:
        text = f.read()
    assert "import kafka_tpu" not in text
    assert "from kafka_tpu" not in text


# ---------------------------------------------------------------------------
# (b) launches through pages and state slots + decode = the full pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_prefill_then_decode_through_pages_and_state(model, backend):
    """The driver's launches (144 rows in a bucket of 256, leaving a
    snapshot; 16 rows in a bucket of 128, resumed from it), then decode in
    the lane's slot.  Pallas: `ssd_chunk` and flash prefill in the SAME
    layer, `ssd_step` and paged decode at 5 / 1 heads, interpreted."""
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    ids = tokens(171, seed=1)
    want = ref.reference_logits(params, ref.hyper(cfg), ids,
                                list(range(159, 171)))
    with jax.default_matmul_precision("highest"):
        got = drv.served_logits(params, cfg, ids, 160, page_size=16,
                                pages_per_seq=12)
    assert rel_rms(got, want["logits"]).max() < REF_TOL
    assert np.isinf(want["router_gap"]).all()


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
                              page_size=16, pages_per_seq=12)


def _prefill(params, cfg, ids, sizes, zero_at=None):
    """Prefill `ids` in launches of `sizes` rows (bucket 64), lane slot 0;
    `zero_at`: the launch that starts there reads slot 2, never written.
    Slot 0 starts out holding garbage: a launch at position 0 is `fresh`."""
    k_pool, v_pool = make_kv_pool_arrays(cfg, 13, 16, state_slots=3)
    v_pool = dict(v_pool, conv=v_pool["conv"].at[:, 0].set(7.0),
                  ssd=v_pool["ssd"].at[:, 0].set(7.0))
    page_row = jnp.arange(1, 13, dtype=jnp.int32)
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
# (c) inactive lanes, snapshots, the engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_inactive_lanes_leave_state_untouched(model, backend):
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    k_pool, v_pool = make_kv_pool_arrays(cfg, 9, 16, state_slots=4)
    assert set(v_pool) == {"v", "conv", "ssd"}
    # rows AND state for every layer, under one layer index
    assert k_pool.shape[0] == v_pool["v"].shape[0] == 3
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
    assert eng.kv_bytes_per_token == 3 * 2 * 16 * 4  # every layer holds rows
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
    assert set(sec) == set(STATE_METRIC_KEYS)
    assert sec["state_bytes_per_slot"] == 3 * (3 * 320 + 64 * 64) * 4
    assert eng.metrics.snapshot(engine=eng)["state"] == sec
    # the counters: chunks by the kernel's own grid (none on XLA), and the
    # state bytes every decode pass read and wrote
    snap = eng.metrics.snapshot(engine=eng)["engine"]
    assert (snap["ssd_chunk_trips"] > 0) == (backend == "pallas")
    assert snap["ssd_state_bytes"] > 0
    assert snap["ssd_state_bytes"] % (2 * 4 * 3 * 4 * 16 * 64) == 0
    assert snap["delta_chunk_trips"] == snap["delta_state_bytes"] == 0
    # a traced request's engine.prefill span says which snapshot it restored
    assert eng._prefill_attrs(b)["state_snapshot"] == b.state_restored
    assert "state_snapshot" not in eng._prefill_attrs(a)


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
    # what the victim holds is then a function of the steps taken (one
    # batched prefill, then at most five fused dispatches of 4: 21 tokens),
    # not of how many dispatches ran ahead of the fetches under load
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


# ---------------------------------------------------------------------------
# (f) refusals by name
# ---------------------------------------------------------------------------

def _mesh(**axes):
    from kafka_tpu.parallel import MeshConfig, make_mesh

    return make_mesh(MeshConfig(**axes))


@pytest.mark.parametrize("path,kw,mesh,why", [
    ("speculative verify", dict(speculative_k=2), None, "rolled back"),
    ("int8 pool", dict(kv_quantize="int8"), None, "float32 state slots"),
    ("prefill_ring", {}, dict(sp=2), "last conv rows"),
    ("pp / tp / ep mesh", {}, dict(tp=2), "state slots live on one device"),
    ("KV tier", dict(kv_host_tier_mb=8), None, "snapshot"),
], ids=["speculative", "int8", "ring", "tp", "host_tier"])
def test_engine_refuses_by_name(model, path, kw, mesh, why):
    cfg, params = model
    assert cfg.has_state and not cfg.lead_tree and not cfg.is_latent
    with pytest.raises(RecurrentStateUnsupported, match=path) as err:
        InferenceEngine(cfg, params, EngineConfig(**dict(ENGINE, **kw)),
                        mesh=None if mesh is None else _mesh(**mesh))
    assert path in err.value.path and why in str(err.value)
    assert "differential" not in str(err.value)


def test_handoff_sleep_and_forward_backstops(model):
    cfg, params = model
    eng = make_engine(model)
    req = GenRequest(request_id="h", prompt_ids=[1, 2, 3], max_new_tokens=2)
    req.handoff = True
    with pytest.raises(RecurrentStateUnsupported, match="hand-off"):
        eng.submit(req)
    with pytest.raises(RecurrentStateUnsupported, match="sleep"):
        eng.sleep_to_object()
    ids, pos = jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None]
    with pytest.raises(HybridPathError, match="one device"):
        forward(params, cfg, ids, pos, mesh=_mesh(tp=2))
    with pytest.raises(NotImplementedError, match="roll"):
        StepPrograms(cfg, None, 16, 2, 4).verify(2)


# ---------------------------------------------------------------------------
# (e) the memory plan, at the cut's sizes by shape only
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
    assert round(planner.weight_bytes_per_device(cut) / 1e9, 2) == 6.69
    slots = default_state_slots(32)
    assert slots == 129
    plan = planner.plan_memory(
        cut, num_pages=5120, page_size=16, max_pages_per_seq=1024,
        max_batch=32, prefill_bucket=512, state_slots=slots,
        grammar_table_bytes=0)
    k_pool, v_pool = jax.eval_shape(lambda: make_kv_pool_arrays(
        cut, 5120, 16, state_slots=slots))
    rows = k_pool.size * 2 + v_pool["v"].size * 2
    # 7 row-holding layers x 2 x 512 values x 2 B x 81,920 slots
    assert plan.kv_pool_bytes == rows == 7 * 2 * 512 * 2 * 5120 * 16
    assert v_pool["conv"].shape == (7, slots, 8, 1920)
    assert v_pool["ssd"].shape == (7, slots, 4096, 256)
    held = (v_pool["conv"].size + v_pool["ssd"].size) * 4
    # no leaf is padded on the device: the plan is the arrays' bytes
    assert plan.state_bytes == held == slots * cut.state_bytes_per_slot
    assert plan.fits
    # and the configuration's file is that cut, to the byte
    path = os.path.join(ROOT, "benchmarks", "configs", "falcon-h1-34b.json")
    filed = config_from_hf_json(path)
    assert filed.replace(name=cut.name) == cut
    with open(path) as f:
        spec = json.load(f)
    assert list(spec["reduced"]) == ["num_hidden_layers", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in spec["reduced"]:
            assert spec[key] == value, key
    assert spec["scopes"] == ["ssd_proj", "ssd_conv", "ssd_gate", "ssd_scan"]
    yi = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                     "yi-1.5-9b.json")))
    assert spec["serving"]["system_prompt"] == yi["serving"]["system_prompt"]
    assert len(spec["serving"]["system_prompt"].encode()) == 4175
    assert "attention_backend" not in spec["serving"]  # `auto` resolves it


# ---------------------------------------------------------------------------
# (g) the scopes reach the compiled program; (h) the benchmark's entries
# ---------------------------------------------------------------------------

def test_ssd_scopes_reach_the_hlo(model):
    from kafka_tpu.tracing import DEVICE_SCOPES

    cfg, params = model
    k, v = make_kv_pool_arrays(cfg, 9, 16, state_slots=3)
    text = jax.jit(drv.decode_step, static_argnums=(1,),
                   static_argnames=("page_size",)).lower(
        params, cfg, k, v, jnp.ones((1, 4), jnp.int32), jnp.asarray([5]),
        jnp.asarray([3]), jnp.asarray([True]),
        page_size=16).compile().as_text()
    for scope in ("ssd_proj", "ssd_conv", "ssd_gate", "ssd_scan", "attn_qkv",
                  "attn_core", "attn_out", "mlp"):
        assert f"/{scope}/" in text, scope
        assert scope in DEVICE_SCOPES
    # the Mamba-1 scopes are another cell's (`dev_ssm_share` reads them)
    assert "/ssm_" not in text


def test_new_per_layer_entries_list_the_new_cell_alone():
    cell = "falcon-h1-34b.chat-decode"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {"dev_ssd_share", "ssd_step_roofline", "ssd_chunk_roofline",
           "ssd_state_restore_share", "gqa5_attn_roofline"}
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
        "falcon-h1-34b", "chat-decode", 1)
    # (the tenth cell; later PRs append theirs after it)
    assert bench["workloads"][9] == entry


def test_ssd_roofline_counts_from_the_calls_own_shapes():
    roof = _load("", "ssd_roofline")
    lanes, heads, P, G, N = 32, 32, 128, 2, 256
    dims = [(1,), (lanes,), (lanes, 1, heads * P), (lanes, 1, G * N),
            (lanes, 1, G * N), (lanes, 2, 1, 16), (7, 129, heads * P, N)]
    flops, nbytes = roof.step_call(dims)
    assert nbytes == 4 * lanes * (2 * heads * P * N + 2 * heads * P
                                  + 2 * G * N + heads)
    assert flops == 5 * lanes * heads * P * N
    dims = [(1,)] + [(4,)] * 4 + [(4, 512, heads * P), (4, 512, G * N),
                                  (4, 512, G * N), (4, 2, 512, 16),
                                  (7, 129, heads * P, N)]
    flops, nbytes = roof.chunk_call(dims)
    assert nbytes == 4 * 4 * (512 * (2 * heads * P + 2 * G * N + heads)
                              + 3 * heads * P * N)
    assert flops == 4 * 4 * 2 * (G * 128 * 128 * N + heads * (
        128 * 128 * P + 2 * 128 * P * N))
    assert roof.step_call([(3, 4)]) is None
