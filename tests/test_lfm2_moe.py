"""LFM2-8B-A1B on the served path (ISSUE 47; `lfm2_moe`): gated short
convolutions whose two-row tail lives in a state slot of a second shape,
beside full attention with QK-norm, two dense lead layers and sigmoid-routed
experts chosen with a bias, on the lead-and-routed tree.

CPU, float32, tiny widths, seeded weights, against the plain reference
`benchmarks/references/lfm2moe.py` (written from the equations, imports
nothing of kafka_tpu).  The uncached comparison runs the PUBLISHED 24-entry
`layer_types` (one unrolled period of 22 bodies after the two lead layers);
the paged and engine tests run the cut's 14 layers (three scanned periods of
four).

TOLERANCES.  `forward` and the reference do the same float32 arithmetic in
another order (stacked einsums against per-layer loops): they agree to ~5e-6
relative RMS of the logits.  REF_TOL = 1e-4 leaves 20x room and is far under
what any missing mechanism costs at these sizes, which
`test_reference_variants_exceed_tol` holds (the smallest: experts weighed by
the biased scores, 0.1).  Engine tests compare TOKENS, greedy, against the
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
    CONV, GLOBAL, UnsupportedConfigError, config_from_hf_json,
)
from kafka_tpu.models.cache import HybridPathError, KVCache
from kafka_tpu.models.llama import init_kv_cache
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime.engine import RecurrentStateUnsupported
from kafka_tpu.runtime.kv_cache import default_state_slots, make_kv_pool_arrays
from kafka_tpu.runtime.metrics import STATE_METRIC_KEYS
from kafka_tpu.runtime.step_programs import StepPrograms, decode_plan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_TOL = 1e-4

# the catalog row's `config` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention", "conv",
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}


def _load(folder, name):
    path = os.path.join(ROOT, "benchmarks", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references", "lfm2moe")
drv = _load("drivers", "lfm2_pool")


def tiny_cfg(layers=14, backend="xla", **kw):
    base = dict(
        name="tiny-lfm2moe", vocab_size=300, hidden_size=64,
        intermediate_size=24, num_layers=layers, num_heads=8, num_kv_heads=2,
        head_dim=8, layer_types=tuple(PUBLISHED["layer_types"][:layers]),
        conv_L_cache=3, qk_norm=True, rope_theta=1e4, first_k_dense=2,
        dense_intermediate_size=96, num_experts=8, num_experts_per_tok=3,
        moe_scoring="sigmoid", dtype="float32",
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
# (d) the configuration
# ---------------------------------------------------------------------------

def _cfg_of(tmp_path, **over):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(dict(PUBLISHED, **over)))
    return config_from_hf_json(str(path))


def test_config_from_hf_json_honours_every_key(tmp_path):
    cfg = _cfg_of(tmp_path)
    assert cfg.layer_types == tuple(PUBLISHED["layer_types"])
    assert (cfg.conv_L_cache, cfg.hidden_size, cfg.num_layers) == (3, 2048, 24)
    assert (cfg.first_k_dense, cfg.dense_intermediate_size) == (2, 7168)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (32, 4)
    assert cfg.intermediate_size == 1792  # the experts' width
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert cfg.moe_scoring == "sigmoid" and cfg.routed_scaling_factor == 1.0
    assert cfg.rms_norm_eps == 1e-5
    assert cfg.rope_theta == 1e6 and cfg.max_context == 128000
    assert cfg.vocab_size == 65536 and cfg.tie_word_embeddings
    assert cfg.qk_norm and cfg.lead_tree and cfg.kind_leaves
    assert not cfg.hybrid_decoder and not cfg.by_kind
    # the state is asked of the KIND of layer the model has
    assert cfg.has_state and cfg.state_layers == 18
    assert cfg.state_shapes() == (("conv", (2, 2048)),)
    assert cfg.state_bytes_per_slot == 18 * 2 * 2048 * 4
    assert cfg.kv_layers == 6 and cfg.kv_values_per_token == 6 * 2 * 512
    # the uncut depth: one unrolled period of 22 bodies after the lead
    assert cfg.pattern == (0, tuple(PUBLISHED["layer_types"][2:]))
    # the cut: three whole periods of four
    cut = _cfg_of(tmp_path, num_hidden_layers=14)
    assert cut.pattern == (0, (GLOBAL, CONV, CONV, CONV))
    assert cut.state_layers == 11 and cut.kv_layers == 3
    assert cut.state_bytes_per_slot == 180224


@pytest.mark.parametrize("over,key", [
    (dict(conv_bias=True), "conv_bias"),
    (dict(conv_L_cache=1), "conv_L_cache"),
    (dict(use_expert_bias=False), "use_expert_bias"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(rope_scaling={"factor": 2.0}), "rope_scaling"),
    (dict(layer_types=["conv"] * 24), "full_attention"),
    (dict(layer_types=["conv", "sliding_attention"] * 12, sliding_window=8),
     "layer_types"),
    (dict(layer_types=["conv", "mamba"] * 12), "unknown kinds"),
], ids=["conv_bias", "one_tap", "no_expert_bias", "unnormalised", "gelu",
        "rope_scaling", "no_attention", "sliding", "mamba"])
def test_config_refuses_by_key(tmp_path, over, key):
    with pytest.raises(UnsupportedConfigError, match=key):
        _cfg_of(tmp_path, **over)


def test_conv_kind_needs_its_key():
    """`layer_types` naming conv without conv_L_cache is an unknown kind, as
    it was; a Mamba decoder's state is what it was."""
    with pytest.raises(UnsupportedConfigError, match="unknown kinds"):
        tiny_cfg(conv_L_cache=0)
    assert ModelConfig().state_shapes() == () and not ModelConfig().has_state


# ---------------------------------------------------------------------------
# (a) forward against the reference, the published layer list
# ---------------------------------------------------------------------------

def test_full_forward_logits_on_the_published_layer_list():
    cfg = tiny_cfg(layers=24)
    assert len(cfg.pattern[1]) == 22
    params = init_params(cfg, jax.random.PRNGKey(1))
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
    ids = tokens(40, seed=3)
    hp = ref.hyper(cfg)
    base = ref.reference_logits(params, hp, ids, list(range(40)))["logits"]
    for name, variant in ref.variants(hp).items():
        got = ref.reference_logits(params, variant, ids,
                                   list(range(20, 40)))["logits"]
        assert np.median(rel_rms(got, base[20:])) > 100 * REF_TOL, name


# ---------------------------------------------------------------------------
# (b) chunks through pages and state slots + decode = the full pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_prefill_then_decode_through_pages_and_state(model, backend):
    """The driver's launches (112 rows in a bucket of 128, then 17 launches
    of one row in a bucket of 64 at starts that no page boundary holds, the
    first and the last resumed from a SNAPSHOT slot), then decode in the
    lane's slot: the tail crosses a launch boundary, a restore and the
    prefill-to-decode boundary inside the compared positions.  Pallas: the
    decode and flash-prefill kernels at 8 / 2 heads, interpreted."""
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    ids = tokens(154, seed=1)
    want = ref.reference_logits(params, ref.hyper(cfg), ids,
                                list(range(128, 154)))
    with jax.default_matmul_precision("highest"):
        got = drv.served_logits(params, cfg, ids, 129, page_size=16,
                                pages_per_seq=12)
    assert rel_rms(got, want["logits"]).max() < REF_TOL


def test_forced_picks_are_the_programs_own_in_float32(model, monkeypatch):
    """The driver hands every one-row launch the reference's experts through
    the selection bias.  In float32 the program takes those experts by
    itself, so forcing changes nothing; and the bias does force: handed
    OTHER experts, every compared row moves."""
    cfg, params = model
    ids = tokens(140, seed=4)

    def served(**kw):
        with jax.default_matmul_precision("highest"):
            return drv.served_logits(params, cfg, ids, 129, page_size=16,
                                     pages_per_seq=12, **kw)

    free, held = served(force=False), served()
    assert rel_rms(held, free).max() < REF_TOL
    real = drv._reference.reference_logits
    picks = real(params, ref.hyper(cfg), ids, [128])["picks"]
    assert picks.shape == (12, 140, cfg.num_experts_per_tok)
    monkeypatch.setattr(
        drv._reference, "reference_logits",
        lambda *a, **kw: {"picks": (picks + 1) % cfg.num_experts})
    assert rel_rms(served(), free).min() > 100 * REF_TOL


def _prefill(params, cfg, ids, sizes, zero_at=None):
    """Prefill `ids` in launches of `sizes` rows (bucket 64), lane slot 0;
    `zero_at`: the launch that starts there reads a ZERO tail (slot 2 is
    never written).  Slot 0 starts out holding garbage: a launch at position
    0 is `fresh` and must not read it."""
    k_pool, v_pool = make_kv_pool_arrays(cfg, 13, 16, state_slots=3)
    v_pool = dict(v_pool, conv=v_pool["conv"].at[:, 0].set(7.0))
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


@pytest.mark.parametrize("sizes", [[40, 24], [7, 33, 24], [63, 1], [1] * 3
                                   + [61]],
                         ids=["40+24", "7+33+24", "63+1", "1+1+1+61"])
def test_chunks_equal_one_chunk(model, sizes):
    """A prompt prefilled 64 at once equals the same rows in several padded
    launches (a chunk boundary inside a tail's reach; launches shorter than
    the tail): same last-row logits, same tail in the lane's slot, and a
    `fresh` lane never reads what its slot held."""
    cfg, params = model
    ids = tokens(64, seed=2)
    want = ref.reference_logits(params, ref.hyper(cfg), ids, [63])["logits"][0]
    with jax.default_matmul_precision("highest"):
        one, _, v1 = _prefill(params, cfg, ids, [64])
        got, _, v = _prefill(params, cfg, ids, sizes)
    assert rel_rms(one, want) < REF_TOL and rel_rms(got, want) < REF_TOL
    np.testing.assert_allclose(v["conv"][:, 0], v1["conv"][:, 0],
                               rtol=1e-4, atol=1e-5)
    # the snapshot slot holds what the lane's does
    assert np.array_equal(v["conv"][:, 0], v["conv"][:, 1])


def test_zeroed_tail_at_a_chunk_boundary_fails(model):
    """The mutation: the second launch reads a zero tail instead of what the
    first left.  The comparison that passes above must FAIL."""
    cfg, params = model
    ids = tokens(64, seed=2)
    want = ref.reference_logits(params, ref.hyper(cfg), ids, [63])["logits"][0]
    with jax.default_matmul_precision("highest"):
        bad, _, _ = _prefill(params, cfg, ids, [62, 2], zero_at=62)
    assert rel_rms(bad, want) > 100 * REF_TOL


# ---------------------------------------------------------------------------
# (c) inactive lanes, snapshots, the engine
# ---------------------------------------------------------------------------

def test_inactive_lanes_leave_state_untouched(model):
    """Decode with lane 1 inactive, and a batched prefill with lane 1
    inactive: every bit of lane 1's slot stays."""
    cfg, params = model
    k_pool, v_pool = make_kv_pool_arrays(cfg, 9, 16, state_slots=4)
    assert set(v_pool) == {"v", "conv"}
    v_pool = dict(v_pool, conv=jax.random.normal(
        jax.random.PRNGKey(3), v_pool["conv"].shape))
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    _, _, v_new = jax.jit(drv.decode_step, static_argnums=(1,),
                          static_argnames=("page_size",))(
        params, cfg, k_pool, v_pool, table, jnp.asarray([5, 6]),
        jnp.asarray([3, 9]), jnp.asarray([True, False]), page_size=16)
    old, new = v_pool["conv"], v_new["conv"]
    assert np.array_equal(new[:, 1:], old[:, 1:])
    # decode's closed-form step: the tail shifts by one row
    assert np.array_equal(new[:, 0, 0], old[:, 0, 1])
    assert not np.array_equal(new[:, 0, 1], old[:, 0, 1])
    fn = StepPrograms(cfg, None, 16, 2, 4).batched_prefill(16, 2)
    z2 = jnp.zeros(2, jnp.int32)
    _, v_new, _ = fn(
        params, jnp.copy(k_pool), jax.tree.map(jnp.copy, v_pool), table,
        jnp.ones((2, 16), jnp.int32), z2, jnp.asarray([9, 7]),
        jnp.zeros(2), z2, jnp.ones(2), jnp.zeros(2, jnp.uint32),
        jnp.asarray([True, False]), jnp.asarray([0, 1]), jnp.asarray([3, 2]))
    new = v_new["conv"]
    # (the engine gives an inactive lane the trash slot for both; here slot
    # 2 takes lane 1's "snapshot": a copy of what it read)
    assert np.array_equal(new[:, 1], old[:, 1])
    assert np.array_equal(new[:, 2], old[:, 1])
    # lane 0's tail went to its slot AND to its snapshot slot
    assert np.array_equal(new[:, 0], new[:, 3])
    assert not np.array_equal(new[:, 0], old[:, 0])


ENGINE = dict(max_batch=4, page_size=16, num_pages=64, max_pages_per_seq=16,
              prefill_buckets=(16, 64), multi_step=4, attention_backend="xla")


def make_engine(model, **kw):
    cfg, params = model
    return InferenceEngine(cfg, params, EngineConfig(**dict(ENGINE, **kw)))


def run(eng, model, prompt, key, n=6):
    req = eng.generate(prompt, max_new_tokens=n, temperature=0.0,
                       prefix_key=key)
    assert_greedy_consistent(*model, prompt, req.output_ids)
    assert eng.self_check() == []
    return req


def test_engine_snapshot_hit_gives_the_cold_run(model):
    """A prefix hit restores a conv snapshot; what it serves is what the
    cold run serves (both equal the uncached forward, token for token)."""
    eng = make_engine(model)
    assert eng.state_pool.n_slots == default_state_slots(4) == 17
    assert eng.kv_bytes_per_token == 3 * 2 * 16 * 4  # the 3 attention layers
    shared = tokens(100, seed=7)
    a = run(eng, model, shared + tokens(5, seed=8), "a")
    assert a.cached_tokens == 0 and eng.state_restores == 0
    # pages match 96, the deepest snapshot stands at 64: the hit is
    # shortened to it and the cut chunk leaves a snapshot at 96
    b = run(eng, model, shared + tokens(9, seed=9), "b")
    assert b.cached_tokens == 64 and eng.state_restores == 1
    c = run(eng, model, shared + tokens(3, seed=10), "c")
    assert c.cached_tokens == 96 and eng.state_restores == 2
    # the same request again, warm: the tokens of its cold run
    cold = make_engine(model)
    again = run(eng, model, shared + tokens(9, seed=9), "b2")
    fresh = run(cold, model, shared + tokens(9, seed=9), "b2")
    assert again.cached_tokens == 96 and fresh.cached_tokens == 0
    assert again.output_ids == fresh.output_ids
    sec = eng.state_section()
    assert set(sec) == set(STATE_METRIC_KEYS)
    assert sec["state_bytes_per_slot"] == 11 * 2 * 64 * 4
    assert sec["state_tokens_skipped"] == 64 + 96 + 96
    assert eng.metrics.snapshot(engine=eng)["state"] == sec


def test_engine_batched_prefill_fused_decode_and_preempt(model):
    """Three threads at once: same-bucket chunks fuse into the batched
    prefill program (per-lane slots and snapshots), decode fuses 4 steps; a
    preempted lane's tail goes with its lane and it readmits exactly."""
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
    # (64 tokens each: however far the fused steps ran ahead, one is live)
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
# (f) what cannot carry the state of a routed lead tree is refused by name,
# for the reason that is true of this model
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
    ("pp / tp / ep mesh", {}, dict(pp=2), "state slots live on one device"),
    ("KV tier", dict(kv_host_tier_mb=8), None, "snapshot"),
    ("KV tier", dict(kv_object_dir="/tmp/nowhere"), None, "snapshot"),
], ids=["speculative", "int8", "ring", "tp", "ep", "pp", "host_tier",
        "object"])
def test_engine_refuses_by_name(model, path, kw, mesh, why):
    """A model with a state AND a routed lead tree: every refused option
    raises the error whose reason is true of it.  The state's refusal comes
    first on a mesh (the lead tree's RoutedTreeUnsupported is as true, and
    never reached), and none of the reasons names a kernel this model does
    not run."""
    cfg, params = model
    assert cfg.has_state and cfg.lead_tree and not cfg.is_latent
    with pytest.raises(RecurrentStateUnsupported, match=path) as err:
        InferenceEngine(cfg, params, EngineConfig(**dict(ENGINE, **kw)),
                        mesh=None if mesh is None else _mesh(**mesh))
    assert path in err.value.path and why in str(err.value)
    assert "scan" not in str(err.value)
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
    assert not eng.waiting
    ids, pos = jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None]
    k, v = make_kv_pool_arrays(cfg, 3, 16, state_slots=2)
    _, paged = decode_plan(jnp.ones((1, 2), jnp.int32),
                           jnp.zeros(1, jnp.int32), jnp.ones(1, bool), 16)
    with pytest.raises(HybridPathError, match="StatePlan"):
        forward(params, cfg, ids[:, :1], pos[:, :1], kv_cache=KVCache(k, v),
                paged=paged)
    with pytest.raises(HybridPathError, match="one device"):
        forward(params, cfg, ids, pos, mesh=_mesh(tp=2))
    with pytest.raises(HybridPathError, match="contiguous"):
        init_kv_cache(cfg, 1, 16)
    with pytest.raises(NotImplementedError, match="roll"):
        StepPrograms(cfg, None, 16, 2, 4).verify(2)


# ---------------------------------------------------------------------------
# (e) the memory plan, at the cut's sizes by shape only
# ---------------------------------------------------------------------------

def test_memory_plan_counts_the_tree_the_pool_and_the_slots(tmp_path, model):
    from kafka_tpu.runtime import planner

    cut = _cfg_of(tmp_path, num_hidden_layers=14)
    for cfg in (model[0], cut, _cfg_of(tmp_path)):
        shapes = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0)))
        held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(shapes))
        assert planner.weight_bytes_per_device(cfg) == held
    assert round(planner.weight_bytes_per_device(cut) / 1e9, 2) == 9.33
    slots = default_state_slots(32)
    plan = planner.plan_memory(
        cut, num_pages=8192, page_size=16, max_pages_per_seq=1024,
        max_batch=32, prefill_bucket=2048, state_slots=slots,
        grammar_table_bytes=0)
    pools = jax.eval_shape(lambda: make_kv_pool_arrays(
        cut, 8192, 16, state_slots=slots))
    k_pool, v_pool = pools
    rows = k_pool.size * 2 + v_pool["v"].size * 2
    # 3 row-holding layers x 2 x 512 values x 2 B x 131,072 slots
    assert plan.kv_pool_bytes == rows == 3 * 2 * 512 * 2 * 8192 * 16
    assert v_pool["conv"].shape == (11, slots, 2, 2048)
    assert v_pool["conv"].size * 4 == slots * cut.state_bytes_per_slot
    # as the device lays a slot out: the tail's 2 rows take 8
    assert plan.state_bytes == slots * 11 * 4 * 8 * 2048
    assert plan.fits


# ---------------------------------------------------------------------------
# (g) both scopes reach the compiled program
# ---------------------------------------------------------------------------

def test_conv_scopes_reach_the_hlo(model):
    cfg, params = model
    k, v = make_kv_pool_arrays(cfg, 9, 16, state_slots=3)
    text = jax.jit(drv.decode_step, static_argnums=(1,),
                   static_argnames=("page_size",)).lower(
        params, cfg, k, v, jnp.ones((1, 4), jnp.int32), jnp.asarray([5]),
        jnp.asarray([3]), jnp.asarray([True]),
        page_size=16).compile().as_text()
    for scope in ("conv_proj", "conv_mix", "qk_norm", "moe_experts"):
        assert f"/{scope}/" in text, scope
