"""Solar-Open2-250B on the served path (ISSUE 50; `solar_open2`): gated
delta-rule linear attention whose state is a matrix a head in a state slot of
a third shape, updated in place by a chunked prefill kernel and a decode-step
kernel, beside gated full attention that does not rotate and a held share of
sigmoid-routed experts, on the lead-and-routed tree.

CPU, float32, tiny widths, seeded weights, against the plain reference
`benchmarks/references/solaropen2.py` (the recurrence token by token, imports
nothing of kafka_tpu).  The kernels run interpreted.

TOLERANCES.  `forward` and the reference do the same float32 arithmetic in
another order: they agree to ~1e-5 relative RMS of the logits.  REF_TOL =
1e-4 leaves 10x room and is far under what any missing mechanism costs at
these sizes (`test_reference_variants_exceed_tol`; the smallest is a state
rounded to bfloat16, 0.02).  The kernels against the token-by-token
recurrence: KERNEL_TOL = 2e-5 absolute on outputs of order 0.5 and states of
order 1 (float32 sums in another order over up to 128 rows).  Engine tests
compare TOKENS, greedy, against the uncached forward: exact.
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
    DELTA, GLOBAL, UnsupportedConfigError, config_from_hf_json,
)
from kafka_tpu.models.cache import (
    HybridPathError, StatePlan, _read_state, _write_state,
)
from kafka_tpu.ops.pallas import gated_delta as gd
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime.engine import RecurrentStateUnsupported
from kafka_tpu.runtime.kv_cache import default_state_slots, make_kv_pool_arrays
from kafka_tpu.runtime.metrics import STATE_METRIC_KEYS
from kafka_tpu.runtime.step_programs import StepPrograms

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_TOL = 1e-4
KERNEL_TOL = 2e-5

# the catalog row's `config` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8,
}
CUT = dict(num_hidden_layers=8, n_routed_experts=20,
           n_routed_experts_published=320, expert_share_offset=0,
           vocab_size=24576)


def _load(folder, name):
    path = os.path.join(ROOT, "benchmarks", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references", "solaropen2")
drv = _load("drivers", "solaropen2_pool")


def tiny_cfg(layers=8, backend="xla", **kw):
    base = dict(
        name="tiny-solaropen2", vocab_size=300, hidden_size=64,
        intermediate_size=24, num_layers=layers, num_heads=8, num_kv_heads=2,
        head_dim=16,
        layer_types=tuple(GLOBAL if i % 4 == 0 else DELTA
                          for i in range(layers)),
        delta_heads=4, delta_head_dim=16, delta_conv_kernel=4,
        delta_neg_eigval=True, attention_gate="elementwise",
        unrotated_kinds=(GLOBAL,), num_experts=4, num_experts_routed=8,
        expert_offset=4, num_experts_per_tok=3, moe_scoring="sigmoid",
        shared_intermediate_size=24, dtype="float32",
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
# (d) the configuration
# ---------------------------------------------------------------------------

def _cfg_of(tmp_path, **over):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(dict(PUBLISHED, **over)))
    return config_from_hf_json(str(path))


def test_config_from_hf_json_honours_every_key(tmp_path):
    cfg = _cfg_of(tmp_path)
    assert cfg.layer_types == tuple(
        GLOBAL if i % 4 == 0 else DELTA for i in range(48))
    assert (cfg.hidden_size, cfg.num_layers, cfg.vocab_size) == (
        4096, 48, 196608)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (64, 8, 128)
    assert (cfg.delta_heads, cfg.delta_head_dim, cfg.delta_conv_kernel) == (
        64, 128, 4)
    assert cfg.delta_neg_eigval and cfg.attention_gate == "elementwise"
    assert cfg.unrotated_kinds == (GLOBAL,)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (320, 8)
    assert cfg.intermediate_size == 1280  # the experts' width
    assert cfg.shared_intermediate_size == 1280 and cfg.first_k_dense == 0
    assert cfg.moe_scoring == "sigmoid" and cfg.routed_scaling_factor == 1.0
    assert cfg.rms_norm_eps == 1e-5 and cfg.max_context == 1048576
    assert not cfg.tie_word_embeddings
    assert cfg.lead_tree and cfg.kind_leaves
    assert not cfg.hybrid_decoder and not cfg.by_kind
    # the state is asked of the KIND of layer the model has: two leaves, the
    # tails' 3 x 24,576 values laid out over 8 rows
    assert cfg.has_state and cfg.state_layers == 36 and cfg.kv_layers == 12
    assert cfg.state_shapes() == (("conv", (8, 9216)),
                                  ("delta", (8192, 128)))
    assert cfg.state_bytes_per_slot == 36 * (3 * 24576 + 8192 * 128) * 4
    assert cfg.kv_values_per_token == 12 * 2 * 1024
    assert cfg.pattern == (0, (GLOBAL, DELTA, DELTA, DELTA))
    # the cut: two whole periods, a sixteenth of the experts
    cut = _cfg_of(tmp_path, **CUT)
    assert cut.pattern == (0, (GLOBAL, DELTA, DELTA, DELTA))
    assert cut.state_layers == 6 and cut.kv_layers == 2
    assert cut.state_bytes_per_slot == 26935296
    assert (cut.num_experts, cut.num_router_experts, cut.expert_offset) == (
        20, 320, 0)
    # honoured both ways: rotation on, beta in (0, 1), no gate
    alt = _cfg_of(tmp_path, use_rope=True, kda_allow_neg_eigval=False,
                  use_gqa_gate=False)
    assert alt.unrotated_kinds == () and not alt.delta_neg_eigval
    assert alt.attention_gate == ""


@pytest.mark.parametrize("over,key", [
    (dict(kda_use_full_proj=True), "kda_use_full_proj"),
    (dict(partial_rotary_factor=0.5), "partial_rotary_factor"),
    (dict(rope_scaling={"factor": 2.0}), "rope_scaling"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(n_group=4), "n_group"),
    (dict(gqa_layers=[]), "gqa_layers"),
    (dict(gqa_layers=[0, 3, 8]), "gqa_interval"),
    (dict(layer_types=["full_attention"] * 48), "layer_types"),
    (dict(linear_attn_config=dict(PUBLISHED["linear_attn_config"],
                                  num_kv_heads=8)), "num_kv_heads"),
    (dict(linear_attn_config=dict(PUBLISHED["linear_attn_config"],
                                  short_conv_kernel_size=1)),
     "delta_conv_kernel"),
    (dict(linear_attn_config=dict(PUBLISHED["linear_attn_config"],
                                  head_dim=0)), "delta_head_dim"),
], ids=["full_proj", "partial_rotary", "rope_scaling", "gelu", "attn_bias",
        "unnormalised", "softmax_scores", "groups", "no_attention",
        "off_interval", "layer_types", "grouped_kv", "one_tap", "no_head"])
def test_config_refuses_by_key(tmp_path, over, key):
    with pytest.raises(UnsupportedConfigError, match=key):
        _cfg_of(tmp_path, **over)


def test_delta_kind_needs_its_key_and_gates_know_their_attention():
    with pytest.raises(UnsupportedConfigError, match="unknown kinds"):
        tiny_cfg(delta_heads=0)
    with pytest.raises(UnsupportedConfigError, match="elementwise"):
        ModelConfig(attention_gate="elementwise", kv_lora_rank=8,
                    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8)
    with pytest.raises(UnsupportedConfigError, match="headwise"):
        ModelConfig(attention_gate="headwise")
    assert ModelConfig().state_shapes() == () and not ModelConfig().has_state


# ---------------------------------------------------------------------------
# (k) the kernels and the XLA scan against the token-by-token recurrence
# ---------------------------------------------------------------------------

def _recurrence(q, k, v, g, beta, S0):
    """The equation, one token at a time, in numpy float64: S [dk, dv] a
    head.  Returns (o [B, S, H, dv], S^T after the last row)."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    B, T, H, dk = q.shape
    S = np.swapaxes(np.asarray(S0, np.float64), -1, -2).copy()
    o = np.zeros(v.shape)
    for t in range(T):
        for b in range(B):
            for h in range(H):
                kt, bt = k[b, t, h], beta[b, t, h]
                M = (np.eye(dk) - bt * np.outer(kt, kt)) * np.exp(g[b, t, h])
                S[b, h] = M @ S[b, h] + bt * np.outer(kt, v[b, t, h])
                o[b, t, h] = S[b, h].T @ q[b, t, h]
    return o, np.swapaxes(S, -1, -2)


def _rows(B, T, H, D, seed=0, decay=1.0, beta_scale=2.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, T, H, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, D)))
    v = jax.random.normal(ks[2], (B, T, H, D))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (B, T, H, D)))
    beta = beta_scale * jax.nn.sigmoid(
        2.0 * jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def _delta(leaf, plan, rows, kernel, layer=1):
    return gd.gated_delta(leaf, layer, plan, *rows, kernel=kernel,
                          read_state=_read_state, write_state=_write_state)


@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("decay", [0.1, 1.0, 25.0],
                         ids=["slow", "unit", "past_1e-30_a_chunk"])
def test_chunk_kernel_and_scan_equal_the_recurrence(monkeypatch, chunk, decay):
    """Ragged `lens`, beta up to 2, a lane from zeros, a lane from another
    slot, an inactive lane; at decay 25 a row's log-decay averages -20 and a
    CHUNK's cumulative decay passes 1e-30 within four rows (the textbook
    division by it would overflow): the kernel's exponents are differences
    and never positive."""
    monkeypatch.setattr(gd, "CHUNK", chunk)
    B, T, H, D = 3, 128, 2, 16
    rows = _rows(B, T, H, D, seed=chunk, decay=decay)
    lens = np.array([128, 70, 0])
    leaf0 = jax.random.normal(jax.random.PRNGKey(9), (2, 7, H * D, D))
    plan = StatePlan(lens=jnp.asarray(lens), src=jnp.array([4, 1, 2]),
                     dst=jnp.array([0, 1, 2]), snap=jnp.array([5, 6, 3]),
                     fresh=jnp.array([False, True, False]))
    if decay == 25.0:
        assert float(jnp.min(jnp.sum(rows[3][:, :chunk], axis=1))) < -69.0
    S0 = np.array(leaf0[1, jnp.array([4, 1, 2])]).reshape(B, H, D, D)
    S0[1] = 0.0  # the fresh lane
    want_o = np.zeros((B, T, H, D))
    want_S = S0.copy()
    for b in range(B):
        n = lens[b]
        if n:
            o, S = _recurrence(*(a[b:b + 1, :n] for a in rows), S0[b:b + 1])
            want_o[b, :n], want_S[b] = o[0], S[0]
    real = np.arange(T)[None, :] < lens[:, None]
    for kernel in (False, True):
        o, leaf = _delta(leaf0, plan, rows, kernel)
        assert np.abs(np.asarray(o) - want_o)[real].max() < KERNEL_TOL
        for b in (0, 1):  # the lane's slot and its snapshot
            for slot in (int(plan.dst[b]), int(plan.snap[b])):
                got = np.asarray(leaf[1, slot]).reshape(H, D, D)
                assert np.abs(got - want_S[b]).max() < 5 * KERNEL_TOL
        # the source slot of lane 0, the other layer, and every slot of the
        # inactive lane (the kernel; the scan writes back what it read)
        assert np.array_equal(leaf[0], leaf0[0])
        assert np.array_equal(leaf[1, 4], leaf0[1, 4])
        assert np.array_equal(leaf[1, 2], leaf0[1, 2])
    assert np.array_equal(_delta(leaf0, plan, rows, True)[1][1, 3],
                          leaf0[1, 3])


def test_step_kernel_equals_the_recurrence_and_spares_idle_lanes():
    B, H, D = 3, 2, 16
    rows = _rows(B, 1, H, D, seed=3)
    leaf0 = jax.random.normal(jax.random.PRNGKey(9), (2, 5, H * D, D))
    plan = StatePlan(lens=jnp.array([1, 0, 1]))
    want_o, want_S = _recurrence(
        *rows, np.asarray(leaf0[0, :B]).reshape(B, H, D, D))
    for kernel in (False, True):
        o, leaf = _delta(leaf0, plan, rows, kernel, layer=0)
        for b in (0, 2):
            assert np.abs(np.asarray(o[b]) - want_o[b]).max() < KERNEL_TOL
            assert np.abs(np.asarray(leaf[0, b]).reshape(H, D, D)
                          - want_S[b]).max() < KERNEL_TOL
        assert np.array_equal(leaf[0, 1], leaf0[0, 1])
        assert np.array_equal(leaf[:, 3:], leaf0[:, 3:])
        assert np.array_equal(leaf[1], leaf0[1])


def test_chunks_equal_one_chunk_and_a_stale_state_fails():
    """128 rows at once equal 64 + 64 through the slot (the kernel both
    times); resumed from the WRONG slot the second launch's rows move."""
    B, T, H, D = 1, 128, 2, 16
    rows = _rows(B, T, H, D, seed=5, decay=0.05)
    leaf0 = jnp.zeros((1, 3, H * D, D))
    full = StatePlan(lens=jnp.array([T]), src=jnp.array([0]),
                     dst=jnp.array([0]), snap=jnp.array([1]),
                     fresh=jnp.array([True]))
    o_one, leaf_one = _delta(leaf0, full, rows, True, layer=0)

    def half(lo, leaf, src, fresh):
        plan = StatePlan(lens=jnp.array([64]), src=jnp.array([src]),
                         dst=jnp.array([0]), snap=jnp.array([1]),
                         fresh=jnp.array([fresh]))
        return _delta(leaf, plan, [a[:, lo:lo + 64] for a in rows], True,
                      layer=0)

    o_a, leaf = half(0, leaf0, 0, True)
    o_b, leaf_two = half(64, leaf, 1, False)  # resumed from the snapshot
    two = np.concatenate([np.asarray(o_a), np.asarray(o_b)], axis=1)
    assert np.abs(two - np.asarray(o_one)).max() < KERNEL_TOL
    assert np.abs(np.asarray(leaf_two[0, 0] - leaf_one[0, 0])).max() \
        < 5 * KERNEL_TOL
    o_bad, _ = half(64, leaf, 2, False)  # slot 2 was never written
    assert np.abs(np.asarray(o_bad) - np.asarray(o_one)[:, 64:]).max() \
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


def test_reference_variants_exceed_tol(model):
    """Every mechanism the reference can take out moves the logits by far
    more than REF_TOL: the comparison above can tell each."""
    cfg, params = model
    ids = tokens(56, seed=3)
    hp = ref.hyper(cfg)
    pos = list(range(36, 56))
    base = ref.reference_logits(params, hp, ids, pos)["logits"]
    names = set()
    for name, variant in ref.variants(hp).items():
        got = ref.reference_logits(params, variant, ids, pos)["logits"]
        assert np.median(rel_rms(got, base)) > 100 * REF_TOL, name
        names.add(name)
    assert {"decay_per_head", "beta_in_0_1", "no_output_gate", "no_gqa_gate",
            "rotation_on", "qk_unnormalised", "conv_tail_zeroed_at_chunk",
            "bf16_state", "bf16_accumulate"} <= names


def test_the_reference_imports_nothing_of_the_program():
    for folder, name in (("references", "solaropen2"),):
        with open(os.path.join(ROOT, "benchmarks", folder, name + ".py")) as f:
            text = f.read()
        assert "import kafka_tpu" not in text
        assert "from kafka_tpu" not in text


# ---------------------------------------------------------------------------
# (s) the share: sixteen chips' routed parts and the shared expert once
# ---------------------------------------------------------------------------

def test_shares_add_up_to_the_uncut_layer():
    """Through a GQA layer and a linear-attention layer of the SAME mixers,
    norms, router and shared expert: the routed parts the 16 shares give
    (2 of 32 experts each), with the shared expert counted once, add up to
    the uncut reference layer's feed-forward output, and the mixers are the
    same in every share."""
    whole = tiny_cfg(layers=2, layer_types=(GLOBAL, DELTA), num_experts=32,
                     num_experts_routed=0, expert_offset=0,
                     num_experts_per_tok=8)
    params = init_params(whole, jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 64))
    hp = ref.hyper(whole)
    none = jnp.zeros((24, 8), jnp.int32)

    def ffn(p, hp_, l):
        lp = {n: w[l] for n, w in p["layers"].items()}
        return np.asarray(ref._moe(x, lp, hp_, none, 24)[0])

    def shared_only(l):
        lp = {n: w[l] for n, w in params["layers"].items()}
        return np.asarray(ref._swiglu(x, lp["ws_g"], lp["ws_u"], lp["ws_d"]))

    with jax.default_matmul_precision("highest"):
        for l in (0, 1):
            total = np.zeros((24, 64))
            for chip in range(16):
                share = dict(params, layers=dict(
                    params["layers"],
                    **{n: params["layers"][n][:, 2 * chip:2 * chip + 2]
                       for n in ("wg", "wu", "wd")}))
                part = ffn(share, dict(hp, expert_offset=2 * chip), l)
                total += part - shared_only(l)
            np.testing.assert_allclose(total + shared_only(l),
                                       ffn(params, hp, l), atol=2e-5)
        # and the program's held share is the reference's, layer for layer
        held = tiny_cfg(layers=2, layer_types=(GLOBAL, DELTA), num_experts=2,
                        num_experts_routed=32, expert_offset=6,
                        num_experts_per_tok=8)
        cut = dict(params, layers=dict(
            params["layers"], **{n: params["layers"][n][:, 6:8]
                                 for n in ("wg", "wu", "wd")}))
        ids = tokens(24, seed=5)
        got, _ = forward(cut, held, jnp.asarray([ids]), jnp.arange(24)[None])
        want = ref.reference_logits(cut, ref.hyper(held), ids,
                                    list(range(24)))
    assert rel_rms(got[0], want["logits"]).max() < REF_TOL


# ---------------------------------------------------------------------------
# (b) launches through pages and state slots + decode = the full pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_prefill_then_decode_through_pages_and_state(model, backend):
    """The driver's launches (112 rows in a bucket of 128, leaving a
    snapshot; 16 launches of one row in a bucket of 64, the first resumed
    from it), then decode in the lane's slot, every one-row launch on the
    reference's picks.  Pallas: the chunk and step kernels, flash prefill
    and paged decode at 8 / 2 heads, interpreted."""
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    ids = tokens(154, seed=1)
    want = ref.reference_logits(params, ref.hyper(cfg), ids,
                                list(range(127, 154)))
    with jax.default_matmul_precision("highest"):
        got = drv.served_logits(params, cfg, ids, 128, page_size=16,
                                pages_per_seq=12)
    assert rel_rms(got, want["logits"]).max() < REF_TOL
    assert np.isinf(want["router_gap"]).all()
    assert np.isfinite(want["raw_router_gap"]).all()


def test_the_check_fails_by_name_where_the_state_is_not_float32(
        model, monkeypatch):
    """A delta state kept in bfloat16 is under the logits' tolerance at the
    published widths: the driver reads the slot and raises."""
    from kafka_tpu.runtime import kv_cache

    cfg, params = model
    real = kv_cache.make_kv_pool_arrays

    def rounded(*a, **kw):
        k, v = real(*a, **kw)
        return k, dict(v, delta=v["delta"].astype(jnp.bfloat16))

    monkeypatch.setattr(kv_cache, "make_kv_pool_arrays", rounded)
    with pytest.raises(drv.DeltaStateError, match="float32"):
        with jax.default_matmul_precision("highest"):
            drv.served_logits(params, cfg, tokens(140, seed=4), 128,
                              page_size=16, pages_per_seq=12)


def _prefill(params, cfg, ids, sizes, zero_at=None):
    """Prefill `ids` in launches of `sizes` rows (bucket 64), lane slot 0;
    `zero_at`: the launch that starts there reads slot 2, never written.
    Slot 0 starts out holding garbage: a launch at position 0 is `fresh`."""
    k_pool, v_pool = make_kv_pool_arrays(cfg, 13, 16, state_slots=3)
    v_pool = dict(v_pool, conv=v_pool["conv"].at[:, 0].set(7.0),
                  delta=v_pool["delta"].at[:, 0].set(7.0))
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
    for leaf in ("conv", "delta"):
        np.testing.assert_allclose(v[leaf][:, 0], v1[leaf][:, 0],
                                   rtol=1e-4, atol=1e-5)
        # the snapshot slot holds what the lane's does
        assert np.array_equal(v[leaf][:, 0], v[leaf][:, 1])


def test_zeroed_state_at_a_launch_boundary_fails(model):
    cfg, params = model
    ids = tokens(64, seed=2)
    want = ref.reference_logits(params, ref.hyper(cfg), ids, [63])["logits"][0]
    with jax.default_matmul_precision("highest"):
        bad, _, _ = _prefill(params, cfg, ids, [62, 2], zero_at=62)
    assert rel_rms(bad, want) > 100 * REF_TOL


# ---------------------------------------------------------------------------
# (c) inactive lanes, snapshots, the engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_inactive_lanes_leave_state_untouched(model, backend):
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    k_pool, v_pool = make_kv_pool_arrays(cfg, 9, 16, state_slots=4)
    assert set(v_pool) == {"v", "conv", "delta"}
    v_pool = dict(v_pool, **{
        leaf: jax.random.normal(jax.random.PRNGKey(3), v_pool[leaf].shape)
        for leaf in ("conv", "delta")})
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    _, _, v_new = jax.jit(drv.decode_step, static_argnums=(1,),
                          static_argnames=("page_size",))(
        params, cfg, k_pool, v_pool, table, jnp.asarray([5, 6]),
        jnp.asarray([3, 9]), jnp.asarray([True, False]), page_size=16)
    for leaf in ("conv", "delta"):
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
    for leaf in ("conv", "delta"):
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
    assert eng.kv_bytes_per_token == 2 * 2 * 32 * 4  # the 2 softmax layers
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
    assert sec["state_bytes_per_slot"] == 6 * (3 * 192 + 64 * 16) * 4
    assert eng.metrics.snapshot(engine=eng)["state"] == sec
    # the counters: chunks by the kernel's own grid (none on XLA), and the
    # state bytes every decode pass read and wrote
    snap = eng.metrics.snapshot(engine=eng)["engine"]
    assert (snap["delta_chunk_trips"] > 0) == (backend == "pallas")
    assert snap["delta_state_bytes"] > 0
    assert snap["delta_state_bytes"] % (2 * 4 * 6 * 4 * 16 * 16) == 0


def test_engine_batched_prefill_fused_decode_and_preempt(model):
    eng = make_engine(model)
    cfg, params = model
    prompts = [tokens(30 + i, seed=40 + i) for i in range(3)]
    reqs = [GenRequest(request_id=f"r{i}", prompt_ids=p, max_new_tokens=64,
                       temperature=0.0, prefix_key=f"k{i}")
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    while any(len(r.output_ids) < 2 for r in reqs):
        eng.step()
    eng._drain(block=True)
    victim = next(r for r in reqs if r.state == "active")
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
    ("pp / tp / ep mesh", {}, dict(ep=2), "state slots live on one device"),
    ("KV tier", dict(kv_host_tier_mb=8), None, "snapshot"),
], ids=["speculative", "int8", "ring", "tp", "ep", "host_tier"])
def test_engine_refuses_by_name(model, path, kw, mesh, why):
    cfg, params = model
    assert cfg.has_state and cfg.lead_tree and not cfg.is_latent
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
    assert round(planner.weight_bytes_per_device(cut) / 1e9, 2) == 7.80
    slots = default_state_slots(32)
    plan = planner.plan_memory(
        cut, num_pages=5120, page_size=16, max_pages_per_seq=1024,
        max_batch=32, prefill_bucket=512, state_slots=slots,
        grammar_table_bytes=0)
    k_pool, v_pool = jax.eval_shape(lambda: make_kv_pool_arrays(
        cut, 5120, 16, state_slots=slots))
    rows = k_pool.size * 2 + v_pool["v"].size * 2
    # 2 row-holding layers x 2 x 1,024 values x 2 B x 81,920 slots
    assert plan.kv_pool_bytes == rows == 2 * 2 * 1024 * 2 * 5120 * 16
    assert v_pool["conv"].shape == (6, slots, 8, 9216)
    assert v_pool["delta"].shape == (6, slots, 8192, 128)
    held = (v_pool["conv"].size + v_pool["delta"].size) * 4
    # no leaf is padded on the device: the plan is the arrays' bytes
    assert plan.state_bytes == held == slots * cut.state_bytes_per_slot
    assert plan.fits
    # and the configuration's file is that cut, to the byte
    path = os.path.join(ROOT, "benchmarks", "configs",
                        "solar-open2-250b.json")
    filed = config_from_hf_json(path)
    assert planner.weight_bytes_per_device(
        filed.replace(name=cut.name)) == planner.weight_bytes_per_device(cut)
    with open(path) as f:
        spec = json.load(f)
    for key, value in PUBLISHED.items():
        if key not in spec["reduced"]:
            assert spec[key] == value, key
    assert set(spec["scopes"]) == {"kda_proj", "kda_conv", "kda_gate",
                                   "kda_delta", "attn_gate", "moe_shared"}


# ---------------------------------------------------------------------------
# (g) the scopes reach the compiled program; (h) the benchmark's entries
# ---------------------------------------------------------------------------

def test_kda_scopes_reach_the_hlo(model):
    cfg, params = model
    k, v = make_kv_pool_arrays(cfg, 9, 16, state_slots=3)
    text = jax.jit(drv.decode_step, static_argnums=(1,),
                   static_argnames=("page_size",)).lower(
        params, cfg, k, v, jnp.ones((1, 4), jnp.int32), jnp.asarray([5]),
        jnp.asarray([3]), jnp.asarray([True]),
        page_size=16).compile().as_text()
    for scope in ("kda_proj", "kda_conv", "kda_gate", "kda_delta",
                  "attn_gate", "moe_shared", "moe_experts"):
        assert f"/{scope}/" in text, scope


def test_new_per_layer_entries_list_the_new_cell_alone():
    cell = "solar-open2-250b.chat-decode"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {"dev_kda_share", "delta_step_roofline", "delta_chunk_roofline",
           "delta_state_restore_share", "gated_gqa_attn_roofline",
           "ep16_experts_read_share"}
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in new}
    assert set(listed) == new
    for m in listed.values():
        assert m["workloads"] == [cell], m["name"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
    # and no older metric's list gained the cell (a LATER PR's metric may
    # list it: `decode_run_step_share`, PR 53)
    first = min(i for i, m in enumerate(bench["per_layer"])
                if m["name"] in new)
    for m in bench["per_layer"][:first]:
        assert cell not in m.get("workloads", ()), m["name"]
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "solar-open2-250b", "chat-decode", 1)


def test_delta_roofline_counts_from_the_calls_own_shapes():
    roof = _load("", "delta_roofline")
    lanes, heads, d = 32, 64, 128
    rows = [(lanes, 1, heads * d)] * 5
    dims = [(1,), (lanes,)] + rows + [(6, 129, heads * d, d)]
    flops, nbytes = roof.step_call(dims)
    assert nbytes == 4 * lanes * (2 * heads * d * d + 6 * heads * d)
    dims = [(1,)] + [(4,)] * 4 + [(4, 64, heads * d)] * 5 + [
        (6, 129, heads * d, d)]
    flops, nbytes = roof.chunk_call(dims)
    assert nbytes == 4 * 4 * (64 * 6 * heads * d + 3 * heads * d * d)
    assert flops == 4 * heads * 2 * (4 * 64 * 64 * d + 3 * 64 * d * d)
    assert roof.step_call([(3, 4)]) is None
