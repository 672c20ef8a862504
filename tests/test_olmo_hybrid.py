"""Olmo-Hybrid-7B on the served path (ISSUE 66; `olmo_hybrid`): Gated DeltaNet
layers whose state is a d_k x d_v matrix a head under ONE decay a head, laid
[d_k, heads x d_v] in a state slot and updated in place by a chunked prefill
kernel and a decode-step kernel, beside post-normed multi-head attention that
does not rotate, under a QK-norm over the whole projection, every layer
dense, on the lead-and-routed tree.

CPU, float32, tiny widths that keep the SHAPE of the problem (hidden 96, 3
heads of d_k 24 / d_v 48: unequal, neither a power of two, no lane multiple
anywhere; 3 attention heads x 32; two periods L L L F; vocabulary 512),
seeded weights, against the plain reference
`benchmarks/references/olmohybrid.py` (the recurrence token by token, imports
nothing of kafka_tpu).  The kernels run interpreted.

TOLERANCES.  `forward` and the reference do the same float32 arithmetic in
another order: they agree to ~1e-5 relative RMS of the logits.  REF_TOL =
1e-4 leaves 10x room and is far under what any missing mechanism costs at
these sizes (`test_reference_variants_exceed_tol`; the smallest is a decay
rounded to bfloat16, 0.002).  The kernels against the row-by-row scan:
KERNEL_TOL = 2e-5 absolute on outputs of order 0.5 and states of order 1.
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
    DELTA, GLOBAL, UnsupportedConfigError, config_from_hf_json,
)
from kafka_tpu.models.cache import (
    HybridPathError, StatePlan, _read_state, _write_state,
)
from kafka_tpu.models.mixers.state import state_launch_forms
from kafka_tpu.ops.pallas import gdn
from kafka_tpu.ops.pallas.state_slot import chunk_slots
from kafka_tpu.runtime import EngineConfig, InferenceEngine
from kafka_tpu.runtime.engine import RecurrentStateUnsupported
from kafka_tpu.runtime.kv_cache import default_state_slots, make_kv_pool_arrays
from kafka_tpu.runtime.step_programs import StepPrograms

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_TOL = 1e-4
KERNEL_TOL = 2e-5
PERIOD = (DELTA, DELTA, DELTA, GLOBAL)

# the catalog row's `config` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": list(PERIOD) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
CUT = dict(num_hidden_layers=16)


def _load(folder, name):
    path = os.path.join(ROOT, "benchmarks", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references", "olmohybrid")
drv = _load("drivers", "olmohybrid_pool")


def tiny_cfg(layers=8, backend="xla", **kw):
    base = dict(
        name="tiny-olmohybrid", vocab_size=512, hidden_size=96,
        intermediate_size=160, num_layers=layers, num_heads=3, num_kv_heads=3,
        head_dim=32, layer_types=(PERIOD * layers)[:layers],
        delta_heads=3, delta_head_dim=24, delta_value_dim=48,
        delta_conv_kernel=4, delta_neg_eigval=True, delta_gate="head",
        norm_position="post", qk_norm=True, qk_norm_whole=True,
        unrotated_kinds=(GLOBAL,), rms_norm_eps=1e-6, dtype="float32",
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
    return [int(t) for t in np.random.RandomState(seed).randint(0, 512, n)]


# ---------------------------------------------------------------------------
# (d) the configuration
# ---------------------------------------------------------------------------

def _cfg_of(tmp_path, **over):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(dict(PUBLISHED, **over)))
    return config_from_hf_json(str(path))


def test_config_from_hf_json_honours_every_key(tmp_path):
    cfg = _cfg_of(tmp_path)
    assert cfg.layer_types == PERIOD * 8
    assert (cfg.hidden_size, cfg.num_layers, cfg.vocab_size) == (
        3840, 32, 100352)
    # `head_dim` is absent as published: 3840 / 30
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (30, 30, 128)
    assert (cfg.delta_heads, cfg.delta_head_dim, cfg.delta_v_dim,
            cfg.delta_conv_kernel) == (30, 96, 192, 4)
    assert cfg.delta_gate == "head" and cfg.delta_neg_eigval
    assert cfg.delta_conv_dim == 11520
    assert cfg.norm_position == "post"
    assert cfg.qk_norm and cfg.qk_norm_whole
    # a theta of null: no layer rotates, and it is never read as a number
    assert cfg.unrotated_kinds == (GLOBAL,)
    assert cfg.intermediate_size == 11008 and not cfg.is_moe
    assert cfg.rms_norm_eps == 1e-6 and cfg.max_context == 65536
    assert not cfg.tie_word_embeddings
    assert cfg.lead_tree and cfg.kind_leaves
    assert not cfg.hybrid_decoder and not cfg.by_kind
    assert cfg.has_state and cfg.state_layers == 24 and cfg.kv_layers == 8
    # S itself a head, the heads side by side along the lanes: whole tiles
    assert cfg.state_shapes() == (("conv", (8, 4320)),
                                  ("delta", (96, 5760)))
    assert cfg.state_bytes_per_slot == 24 * (3 * 11520 + 96 * 5760) * 4
    assert cfg.kv_values_per_token == 8 * 2 * 3840
    assert cfg.pattern == (0, PERIOD)
    cut = _cfg_of(tmp_path, **CUT)
    assert cut.pattern == (0, PERIOD)
    assert cut.state_layers == 12 and cut.kv_layers == 4
    assert cut.state_bytes_per_slot == 28200960
    # honoured both ways: a theta that is a number rotates, beta in (0, 1)
    alt = _cfg_of(tmp_path, rope_parameters={"rope_theta": 500000.0},
                  linear_allow_neg_eigval=False)
    assert alt.unrotated_kinds == () and alt.rope_theta == 500000.0
    assert not alt.delta_neg_eigval


def test_a_null_theta_is_refused_by_name_elsewhere(tmp_path):
    """`rope_parameters: {"rope_theta": null}` reached `_rope_params` as a
    number before: under any other model_type it is refused by its key, and
    a theta that IS a number is read as before."""
    llama = {"model_type": "llama", "vocab_size": 300, "hidden_size": 64,
             "intermediate_size": 96, "num_hidden_layers": 2,
             "num_attention_heads": 4, "num_key_value_heads": 2}
    path = tmp_path / "config.json"
    path.write_text(json.dumps(dict(
        llama, rope_parameters={"rope_theta": None})))
    with pytest.raises(UnsupportedConfigError, match="rope_theta = null"):
        config_from_hf_json(str(path))
    path.write_text(json.dumps(dict(
        llama, rope_parameters={"rope_theta": 250000.0})))
    assert config_from_hf_json(str(path)).rope_theta == 250000.0


@pytest.mark.parametrize("over,key", [
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(rope_scaling={"factor": 2.0}), "rope_scaling"),
    (dict(sliding_window=4096), "sliding_window"),
    (dict(linear_num_value_heads=60), "linear_num_value_heads"),
    (dict(linear_conv_kernel_dim=1), "delta_conv_kernel"),
    (dict(linear_key_head_dim=0), "delta_head_dim"),
    (dict(layer_types=["linear_attention"] * 32), "full_attention"),
    (dict(layer_types=["sliding_attention", "full_attention"] * 16),
     "layer_types"),
], ids=["gelu", "attn_bias", "rope_scaling", "window", "grouped_values",
        "one_tap", "no_head", "no_attention", "unknown_kind"])
def test_config_refuses_by_key(tmp_path, over, key):
    with pytest.raises(UnsupportedConfigError, match=key):
        _cfg_of(tmp_path, **over)


def test_the_new_fields_know_where_they_are_built():
    with pytest.raises(UnsupportedConfigError, match="delta_gate"):
        tiny_cfg(delta_gate="row")
    with pytest.raises(UnsupportedConfigError, match="delta_value_dim"):
        tiny_cfg(delta_gate="channel")  # unequal heads need one decay a head
    with pytest.raises(UnsupportedConfigError, match="norm_position"):
        ModelConfig(norm_position="both")
    with pytest.raises(UnsupportedConfigError, match="norm_position"):
        ModelConfig(norm_position="post", residual_multiplier=0.5)
    with pytest.raises(UnsupportedConfigError, match="qk_norm_whole"):
        ModelConfig(qk_norm_whole=True)
    assert ModelConfig().norm_position == "pre"


# ---------------------------------------------------------------------------
# (k) the kernels against the row-by-row scan, at ONE real head geometry
# ---------------------------------------------------------------------------

H, DK, DV = 2, 96, 192


def _rows(B, T, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, T, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, DK)))
    k = k.at[:, 5:9].set(k[:, 4:5])  # a repeated key, under a beta past 1
    v = jax.random.normal(ks[2], (B, T, H, DV))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H), minval=-6., maxval=1.5))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)) + 1.0)
    return q, k, v, g, beta


def _gdn(leaf, plan, rows, kernel, layer=1):
    return jax.jit(lambda lf: gdn.gdn(
        lf, layer, plan, *rows, kernel=kernel, read_state=_read_state,
        write_state=_write_state))(leaf)


@pytest.fixture(scope="module")
def leaf0():
    return 0.1 * jax.random.normal(jax.random.PRNGKey(5),
                                   (2, 5, DK, H * DV), jnp.float32)


def test_chunk_kernel_equals_the_scan_over_a_padded_chunk_and_a_snapshot(
        leaf0):
    """128 rows: lane 0 ends inside its second chunk (the state after its
    last REAL row is what must be written) and goes to a slot AND a snapshot
    slot; lane 1 starts from zeros; beta passes 1 on a repeated key.  Each
    head alone a grid step (what the interpreter picks) and the PAIR the chip
    takes (384 lanes: a head's lanes picked by mask)."""
    rows = _rows(2, 128)
    lens = jnp.asarray([100, 128], jnp.int32)
    plan = StatePlan(lens=lens, src=jnp.asarray([0, 1]),
                     dst=jnp.asarray([2, 3]), snap=jnp.asarray([4, 3]),
                     fresh=jnp.asarray([False, True]))
    o_x, leaf_x = _gdn(leaf0, plan, rows, False)
    o_k, leaf_k = _gdn(leaf0, plan, rows, True)
    real = (np.arange(128)[None, :] < np.asarray(lens)[:, None])
    assert float(jnp.max(jnp.abs(leaf_x))) > 0.5
    np.testing.assert_allclose(np.asarray(o_k)[real], np.asarray(o_x)[real],
                               atol=KERNEL_TOL)
    np.testing.assert_allclose(leaf_k, leaf_x, atol=KERNEL_TOL)
    # layer 0 and the source slots are as they were; the snapshot holds what
    # the lane's slot does
    assert np.array_equal(leaf_k[0], leaf0[0])
    assert np.array_equal(leaf_k[1, :2], leaf0[1, :2])
    assert np.array_equal(leaf_k[1, 2], leaf_k[1, 4])
    q, k, v, g, beta = rows
    g, beta = (jnp.where(real[..., None], a, 0.0) for a in (g, beta))
    pad = ((0, 0),) * 3 + ((0, 128 - DK),)
    o_p, leaf_p = gdn.gdn_chunk(
        leaf0, jnp.int32(1), *chunk_slots(plan, 2),
        jnp.pad(q, pad).reshape(2, 128, -1),
        jnp.pad(k, pad).reshape(2, 128, -1),
        (beta[..., None] * v).reshape(2, 128, -1), g, beta, dv=DV, chunk=64,
        heads_a_step=2, interpret=True)
    assert np.array_equal(o_p.reshape(o_k.shape)[real], np.asarray(o_k)[real])
    assert np.array_equal(leaf_p, leaf_k)


@pytest.mark.parametrize("heads", [1, 2])
def test_step_kernel_equals_the_scan_and_spares_idle_lanes(leaf0, heads):
    rows = tuple(a[:, :1] for a in _rows(2, 8, seed=2))
    plan = StatePlan(lens=jnp.asarray([1, 0], jnp.int32))
    o_x, leaf_x = _gdn(leaf0, plan, rows, False, layer=0)
    q, k, v, g, beta = (a[:, 0] for a in rows)
    on = (plan.lens > 0)[:, None]
    g, beta = jnp.where(on, g, 0.0), jnp.where(on, beta, 0.0)
    pad = ((0, 0),) * 2 + ((0, 128 - DK),)
    o_k, leaf_k = gdn.gdn_step(
        leaf0, jnp.int32(0), jnp.arange(2, dtype=jnp.int32),
        jnp.pad(q, pad).reshape(2, -1), jnp.pad(k, pad).reshape(2, -1),
        (beta[..., None] * v).reshape(2, -1),
        jnp.repeat(jnp.exp(g), DV, axis=-1), jnp.repeat(beta, DV, axis=-1),
        dv=DV, heads_a_step=heads, interpret=True)
    np.testing.assert_allclose(o_k.reshape(2, 1, H, DV)[0], o_x[0],
                               atol=KERNEL_TOL)
    np.testing.assert_allclose(leaf_k, leaf_x, atol=KERNEL_TOL)
    assert np.array_equal(leaf_k[0, 1], leaf0[0, 1])  # the idle lane's slot
    assert np.array_equal(leaf_k[1], leaf0[1])


def test_the_geometry_rules():
    # Olmo-Hybrid's 30 x 96 x 192: pairs are the fewest heads in whole tiles
    assert gdn.tiles(30, 96, 192)
    assert gdn.head_groups(30, 192) == [2, 6, 10, 30]
    assert gdn.step_heads(30, 96, 192) == 30  # one contiguous 2.2 MB block
    assert not gdn.tiles(3, 24, 48)           # the tiny twin, on the chip
    assert gdn.head_groups(3, 48, aligned=False) == [1, 3]
    assert gdn.form(True, True, 64, True, 3, 24, 48) == "kernel"  # off chip
    assert gdn.form(True, True, 1, True, 3, 24, 48) == "xla"
    assert gdn.form(True, True, 48, True, 3, 24, 48) == "xla"
    assert gdn.form(False, True, 64, True, 30, 96, 192) == "xla"
    assert gdn.form(True, False, 64, True, 30, 96, 192) == "xla"


# ---------------------------------------------------------------------------
# (a) forward against the reference; every must-fail variant fails
# ---------------------------------------------------------------------------

def test_full_forward_logits(model):
    cfg, params = model
    ids = tokens(24, seed=3)
    want = ref.reference_logits(params, ref.hyper(cfg), ids, list(range(24)))
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, jnp.asarray([ids], jnp.int32),
                         jnp.arange(24, dtype=jnp.int32)[None])
    assert rel_rms(got[0], want["logits"]).max() < REF_TOL
    assert np.isinf(want["router_gap"]).all()


MUST_FAIL = {
    "beta_in_0_1", "q_scale_dropped", "q_unnormalised", "k_unnormalised",
    "sigmoid_output_gate", "decay_per_channel", "a_log_dropped",
    "norm_position_pre", "qk_norm_per_head", "rotation_on",
    "conv_tail_zeroed_at_snapshot", "conv_tail_zeroed_at_decode",
    "state_lost_at_snapshot", "state_lost_at_decode",
    "state_transposed_at_snapshot"}


def test_reference_variants_exceed_tol(model):
    """Each variant is one mechanism of ISSUE 66's list taken out or got
    wrong: were the served program to make that mistake the logits at the
    compared positions move by far more than REF_TOL (an unnormalised k
    diverges: NaN fails too)."""
    cfg, params = model
    ids = tokens(107, seed=0)
    positions = list(range(95, 107))
    hp = ref.hyper(cfg)
    variants = ref.variants(hp)
    assert MUST_FAIL <= set(variants)
    want = ref.reference_logits(params, hp, ids, positions)["logits"]
    for name, wrong in variants.items():
        got = ref.reference_logits(params, wrong, ids, positions)["logits"]
        err = rel_rms(got, want).max()
        assert not err < 10 * REF_TOL, (name, err)


def test_the_reference_imports_nothing_of_the_program():
    for mod in (ref,):
        with open(mod.__file__) as f:
            text = f.read()
        assert "import kafka_tpu" not in text
        assert "from kafka_tpu" not in text
    assert ref.LAST == drv.LAST == 16


# ---------------------------------------------------------------------------
# (b) launches through pages and state slots + decode = the full pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_prefill_then_decode_through_pages_and_state(model, backend):
    """The driver's launches (80 rows padded into a bucket of 128, leaving a
    snapshot and not the lane's slot; 16 rows in a bucket of 64 resumed from
    it), then decode in the lane's slot.  Pallas: `gdn_chunk` and `gdn_step`,
    flash prefill and paged decode at 3 / 3 heads, interpreted."""
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    assert drv.launches(96, 16) == [
        (0, 80, drv.TRASH, drv.TRASH, drv.SNAPSHOT),
        (80, 16, drv.SNAPSHOT, drv.LANE, drv.TRASH)]
    assert [r[:2] for r in drv.launches(1536, 16)] == [
        (0, 512), (512, 512), (1024, 496), (1520, 16)]
    ids = tokens(107, seed=1)
    want = ref.reference_logits(params, ref.hyper(cfg), ids,
                                list(range(95, 107)))
    with jax.default_matmul_precision("highest"):
        got = drv.served_logits(params, cfg, ids, 96, page_size=16,
                                pages_per_seq=40)
    assert rel_rms(got, want["logits"]).max() < REF_TOL


def test_the_check_fails_by_name_where_the_state_is_not_float32(
        model, monkeypatch):
    from kafka_tpu.runtime import kv_cache

    cfg, params = model
    real = kv_cache.make_kv_pool_arrays

    def rounded(*a, **kw):
        k, v = real(*a, **kw)
        return k, dict(v, delta=v["delta"].astype(jnp.bfloat16))

    monkeypatch.setattr(kv_cache, "make_kv_pool_arrays", rounded)
    with pytest.raises(drv.GdnStateError, match="float32"):
        with jax.default_matmul_precision("highest"):
            drv.served_logits(params, cfg, tokens(100, seed=4), 96,
                              page_size=16, pages_per_seq=40)


def _prefill(params, cfg, ids, sizes, stale_at=None):
    """Prefill `ids` in launches of `sizes` rows (bucket 64), lane slot 0,
    each leaving a snapshot in slot 1 that the NEXT launch resumes from (the
    slot's layout round-trips through a snapshot and a restore at every
    boundary); `stale_at`: the launch that starts there reads slot 2, never
    written.  Slot 0 starts out holding garbage: a launch at 0 is `fresh`."""
    k_pool, v_pool = make_kv_pool_arrays(cfg, 41, 16, state_slots=3)
    v_pool = dict(v_pool, conv=v_pool["conv"].at[:, 0].set(7.0),
                  delta=v_pool["delta"].at[:, 0].set(7.0))
    page_row = jnp.arange(1, 41, dtype=jnp.int32)
    pre = jax.jit(drv.prefill_chunk, static_argnums=(1,),
                  static_argnames=("page_size",))
    start = 0
    for n in sizes:
        chunk = np.zeros(64, np.int32)
        chunk[:n] = ids[start:start + n]
        src = 2 if start == stale_at else 1 if start else 0
        logits, k_pool, v_pool = pre(
            params, cfg, k_pool, v_pool, page_row, jnp.asarray(chunk),
            jnp.int32(start), jnp.int32(n), jnp.int32(src), jnp.int32(0),
            jnp.int32(1), page_size=16)
        start += n
    return np.asarray(logits), k_pool, v_pool


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_launches_through_snapshots_equal_one_launch(model, backend):
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    ids = tokens(64, seed=2)
    want = ref.reference_logits(params, ref.hyper(cfg), ids, [63])["logits"][0]
    with jax.default_matmul_precision("highest"):
        one, _, v1 = _prefill(params, cfg, ids, [64])
        got, _, v = _prefill(params, cfg, ids, [16, 32, 16])
        bad, _, _ = _prefill(params, cfg, ids, [48, 16], stale_at=48)
    assert rel_rms(one, want) < REF_TOL and rel_rms(got, want) < REF_TOL
    assert rel_rms(bad, want) > 100 * REF_TOL  # a state not restored
    assert v["delta"].shape[2:] == (24, 144) and v["conv"].shape[2:] == (8, 108)
    for leaf in ("conv", "delta"):
        # (float32 sums in another order: a chunk of 64 against 16 + 32 + 16
        # through three inverses, on states of order 0.3)
        np.testing.assert_allclose(v[leaf][:, 0], v1[leaf][:, 0],
                                   rtol=1e-3, atol=1e-4)
        # the snapshot slot holds what the lane's does
        assert np.array_equal(v[leaf][:, 0], v[leaf][:, 1])


# ---------------------------------------------------------------------------
# (c) the engine: a shared prefix, a restore, the new counter
# ---------------------------------------------------------------------------

ENGINE = dict(max_batch=4, page_size=16, num_pages=96, max_pages_per_seq=16,
              prefill_buckets=(16, 64), multi_step=4, attention_backend="xla")
FORMS = ("recurrence_kernel", "recurrence_xla", "tail_kernel", "tail_xla")


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
def test_engine_two_threads_over_a_shared_prefix_and_the_new_counter(
        model, backend):
    eng = make_engine(model, attention_backend=backend)
    cfg = model[0]
    assert eng.state_pool.n_slots == default_state_slots(4) == 17
    assert eng.kv_bytes_per_token == 2 * 2 * 96 * 4  # the 2 MHA layers' rows
    shared = tokens(100, seed=7)
    a = run(eng, model, shared + tokens(5, seed=8), "a")
    assert a.cached_tokens == 0 and eng.state_restores == 0
    b = run(eng, model, shared + tokens(9, seed=9), "b")
    assert b.cached_tokens == 64 and eng.state_restores == 1
    assert b.state_restored is not None and a.state_restored is None
    cold = make_engine(model, attention_backend=backend)
    fresh = run(cold, model, shared + tokens(9, seed=9), "b2")
    assert fresh.cached_tokens == 0 and fresh.output_ids == b.output_ids
    sec = eng.state_section()
    assert sec["state_bytes_per_slot"] == 6 * (3 * 288 + 24 * 144) * 4
    # the new counter family: passes x state layers by the form each op took
    snap = eng.metrics.snapshot(engine=eng)["engine"]
    got = {f: snap[f"state_launches_{f}"] for f in FORMS}
    assert got == eng.state_launches
    kernel = backend == "pallas"
    # (off the chip the interpreter takes the tiny recurrence; its tail of
    # 288 channels laid (8, 108) tiles for nobody)
    assert (got["recurrence_kernel"] > 0) == kernel
    assert (got["recurrence_xla"] > 0) == (not kernel)
    assert got["tail_kernel"] == 0 and got["tail_xla"] > 0
    assert got["recurrence_kernel"] + got["recurrence_xla"] \
        == got["tail_xla"] and got["tail_xla"] % cfg.state_layers == 0
    assert state_launch_forms(eng.cfg, 1, False) == {
        "recurrence": "kernel" if kernel else "xla", "tail": "xla"}
    assert state_launch_forms(ModelConfig(), 1, False) == {}


def test_the_counter_is_exported_under_its_family(model):
    from kafka_tpu.runtime.metrics import METRICS

    rows = {m.key: m for m in METRICS if m.section == "engine"
            and m.key.startswith("state_launches_")}
    assert set(rows) == {f"state_launches_{f}" for f in FORMS}
    for f in FORMS:
        op, form = f.split("_")
        m = rows[f"state_launches_{f}"]
        assert m.family == "kafka_tpu_engine_state_launches_total"
        assert dict(m.labels) == {"op": op, "form": form}


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
    assert cfg.has_state and cfg.lead_tree and not cfg.is_latent
    with pytest.raises(RecurrentStateUnsupported, match=path) as err:
        InferenceEngine(cfg, params, EngineConfig(**dict(ENGINE, **kw)),
                        mesh=None if mesh is None else _mesh(**mesh))
    assert path in err.value.path and why in str(err.value)


def test_loader_quantiser_and_forward_backstops(model):
    from kafka_tpu.models.loader import convert_hf_state_dict
    from kafka_tpu.models.quant import quantize_params

    cfg, params = model
    with pytest.raises(NotImplementedError, match="olmo_hybrid"):
        convert_hf_state_dict({}, cfg)
    with pytest.raises(NotImplementedError, match="olmo_hybrid"):
        quantize_params(params, cfg)
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
    # 12 x 215.57M + 4 x 185.81M + 770.70M parameters at 2 B
    assert round(planner.weight_bytes_per_device(cut) / 1e9, 2) == 8.20
    slots = default_state_slots(16)
    assert slots == 65
    plan = planner.plan_memory(
        cut, num_pages=2560, page_size=16, max_pages_per_seq=1024,
        max_batch=16, prefill_bucket=512, state_slots=slots,
        grammar_table_bytes=0)
    k_pool, v_pool = jax.eval_shape(lambda: make_kv_pool_arrays(
        cut, 2560, 16, state_slots=slots))
    rows = k_pool.size * 2 + v_pool["v"].size * 2
    # 4 row-holding layers x 2 x 3,840 values x 2 B x 40,960 slots
    assert plan.kv_pool_bytes == rows == 4 * 2 * 3840 * 2 * 2560 * 16
    assert v_pool["conv"].shape == (12, slots, 8, 4320)
    assert v_pool["delta"].shape == (12, slots, 96, 5760)
    held = (v_pool["conv"].size + v_pool["delta"].size) * 4
    # no leaf is padded on the device (96 = 12 sublane tiles, 5,760 = 45 lane
    # tiles): the plan is the arrays' bytes
    assert plan.state_bytes == held == slots * cut.state_bytes_per_slot
    # weights, pool and slots: the 12.55 GB the configuration's file states.
    # (`plan.fits` is judged with the PORTABLE path's decode temporaries, the
    # XLA window gather of 16 lanes x 16,384 tokens x 7,680 values x 2 B =
    # 4.03 GB that the Pallas backend the file pins never makes.)
    held = plan.weight_bytes + plan.kv_pool_bytes + plan.state_bytes
    assert round(held / 1e9, 2) == 12.55
    assert plan.activation_bytes == 16 * 100352 * 12 + 16 * 16384 * 7680 * 2
    # and the configuration's file is that cut, to the byte
    path = os.path.join(ROOT, "benchmarks", "configs", "olmo-hybrid-7b.json")
    filed = config_from_hf_json(path)
    assert filed.replace(name=cut.name) == cut
    with open(path) as f:
        spec = json.load(f)
    assert list(spec["reduced"]) == ["num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key not in spec["reduced"]:
            assert spec[key] == value, key
    assert "head_dim" not in spec  # absent as published
    assert set(spec["scopes"]) == {"kda_proj", "kda_conv", "kda_gate",
                                   "kda_delta", "qk_norm"}
    assert {f"A{i}" for i in range(1, 10)} <= {
        v[1:3] for v in spec["assumed"].values() if v.startswith("(A")}
    srv = spec["serving"]
    assert (srv["max_batch"], srv["num_pages"], srv["page_size"]) == (
        16, 2560, 16)


# ---------------------------------------------------------------------------
# (g) the scopes reach the compiled program; (h) the benchmark's entries
# ---------------------------------------------------------------------------

def test_scopes_reach_the_hlo_and_the_post_norms_sit_with_their_adds(model):
    cfg, params = model
    k, v = make_kv_pool_arrays(cfg, 9, 16, state_slots=3)
    text = jax.jit(drv.decode_step, static_argnums=(1,),
                   static_argnames=("page_size",)).lower(
        params, cfg, k, v, jnp.ones((1, 4), jnp.int32), jnp.asarray([5]),
        jnp.asarray([3]), jnp.asarray([True]),
        page_size=16).compile().as_text()
    for scope in ("kda_proj", "kda_conv", "kda_gate", "kda_delta", "qk_norm",
                  "attn_out", "mlp"):
        assert f"/{scope}/" in text, scope
    # no input norm is traced: a sublayer reads the stream as it is
    assert "/attn_norm/" not in text and "/mlp_norm/" not in text
    # each output norm inside the scope of the add it precedes
    for scope in ("kda_proj", "attn_out", "mlp"):
        assert f"/{scope}/rsqrt" in text, scope


NEW_METRICS = {"dev_gdn_share", "dev_gdn_conv_share", "gdn_step_roofline",
               "gdn_chunk_roofline", "mha_attn_roofline",
               "gdn_state_restore_share", "state_kernel_launch_share"}


def test_new_per_layer_entries_list_the_new_cell_alone():
    cell = "olmo-hybrid-7b.chat-decode"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]
              if m["name"] in NEW_METRICS}
    assert set(listed) == NEW_METRICS
    for m in listed.values():
        assert m["workloads"] == [cell], m["name"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
    first = min(i for i, m in enumerate(bench["per_layer"])
                if m["name"] in NEW_METRICS)
    for m in bench["per_layer"][:first]:
        assert cell not in m.get("workloads", ()), m["name"]
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "olmo-hybrid-7b", "chat-decode", 1)
    config = next(c for c in bench["configs"] if c["name"] == "olmo-hybrid-7b")
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == ("https://huggingface.co/allenai/"
                                "Olmo-Hybrid-7B/blob/main/config.json")


def test_gdn_roofline_counts_the_published_state_from_the_calls_shapes():
    roof = _load("", "gdn_roofline")
    lanes, heads, dk, dv = 16, 30, 96, 192
    rows_k, rows_v = (lanes, 1, heads * 128), (lanes, 1, heads * dv)
    leaf = (12, 65, dk, heads * dv)
    dims = [(1,), (lanes,), rows_k, rows_k, rows_v, rows_v, rows_v, leaf]
    flops, nbytes = roof.step_call(dims)
    # the published state in and out and the rows at their own widths: the
    # operands' 128-lane key tiles are not counted
    assert nbytes == 4 * lanes * (2 * heads * dk * dv
                                  + heads * (2 * dk + 2 * dv + 2))
    dims = ([(1,)] + [(4,)] * 4 + [(4, 64, heads * 128)] * 2
            + [(4, 64, heads * dv), (4, 64, heads), (4, 1, heads, 64),
               (4, 64, heads), leaf])
    flops, nbytes = roof.chunk_call(dims)
    assert nbytes == 4 * 4 * (64 * heads * (2 * dk + 2 * dv + 2)
                              + 3 * heads * dk * dv)
    assert flops == 4 * heads * 2 * (2 * 64 * 64 * dk + 3 * 64 * dk * dv
                                     + 2 * 64 * 64 * dv)
    assert roof.step_call([(3, 4)]) is None
    # Solar-Open2's calls are another kernel's: not these counts
    assert roof.step_call([(1,), (32,)] + [(32, 1, 8192)] * 5
                          + [(6, 129, 8192, 128)]) is None
