"""runtime/program_store.py (ISSUE 67): a second build of the same code on
the same device LOADS its step programs instead of tracing them.

What must hold: the loaded program is the compiled one (same tokens, the
donated pools consumed); the key holds every ingredient a trace could read
(each alone turns a hit into a miss); the store never stops a boot (a torn
file, an unwritable directory, a call it was not compiled for); and where
the persistent compile cache is off, `_jit_step` hands out what it always
did."""

import dataclasses
import os
import pathlib
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import monitoring

import kafka_tpu
from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.runtime import (EngineConfig, GenRequest, InferenceEngine,
                               compile_log, program_store, step_programs)
from kafka_tpu.runtime.metrics import (BOOT_METRIC_KEYS, COMPILE_METRIC_KEYS,
                                       METRICS)

REPO = pathlib.Path(kafka_tpu.__file__).resolve().parent.parent
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


@pytest.fixture
def store(tmp_path):
    """The store on under a temporary cache directory, as
    enable_compile_cache() leaves it; off again afterwards."""
    program_store.reset_for_tests()
    program_store.enable(str(tmp_path))
    yield tmp_path / program_store.DIR_NAME
    program_store.enable(None)
    program_store.reset_for_tests()
    step_programs.clear()


@pytest.fixture
def traced():
    """Names of the functions jax traces while the test runs."""
    names = []

    def listen(event, duration, **kw):
        if event == TRACE_EVENT:
            names.append(kw.get("fun_name"))

    monitoring.register_event_duration_secs_listener(listen)
    yield names
    monitoring.unregister_event_duration_listener(listen)


def toy(scale=2.0):
    def fn(params, k_pool, v_pool, x, mask=None):
        y = x * scale + params["w"].sum()
        if mask is not None:
            y = jnp.where(mask, y, 0.0)
        return k_pool + 1.0, {"v": v_pool["v"] * 2.0}, y

    return fn


def toy_args(dtype=jnp.float32, n=4, device=None):
    put = lambda a: jax.device_put(a, device or jax.devices()[0])
    return (
        {"w": put(jnp.ones((3,), dtype))},
        put(jnp.zeros((n, 2), dtype)),
        {"v": put(jnp.ones((n,), dtype))},
        put(jnp.arange(n, dtype=dtype)),
    )


def build(key=("toy", 1), label="toy", scale=2.0):
    return step_programs._jit_step(label, toy(scale), key, None)


def counts():
    c = program_store.counters()
    return c["store_hits"], c["store_misses"], c["store_fallbacks"]


# ----------------------------------------------------------------------
# the round trip
# ----------------------------------------------------------------------


def test_second_build_loads_what_the_first_compiled(store, traced):
    out1 = build()(*toy_args())
    assert counts() == (0, 1, 0)
    assert "fn_toy" in traced
    del traced[:]
    out2 = build()(*toy_args())
    assert counts() == (1, 1, 0)
    assert traced == []  # no trace at all: the executable was loaded
    for a, b in zip(jax.tree.leaves(out1), jax.tree.leaves(out2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert program_store.load_seconds() > 0.0
    (entry,) = program_store.entries(str(store))
    meta = program_store.read_meta(entry[0])
    assert meta["label"] == "toy" and meta["key"]["donated"] == [1, 2]


def test_a_loaded_call_consumes_the_donated_pools(store):
    build()(*toy_args())
    args = toy_args()
    k2, v2, _ = build()(*args)
    assert counts()[0] == 1
    assert args[1].is_deleted() and args[2]["v"].is_deleted()
    assert not args[0]["w"].is_deleted() and not args[3].is_deleted()
    # ... and the results feed the next call, as the engine chains them
    k3, _, _ = build()(args[0], k2, v2, args[3])
    np.testing.assert_array_equal(np.asarray(k3), np.full((4, 2), 2.0))


def test_another_tree_of_arguments_is_another_entry(store):
    """One jit serves decode with and without a mask; so does the store."""
    fn = build()
    mask = jnp.array([True, False, True, False])
    _, _, plain = fn(*toy_args())
    _, _, masked = fn(*toy_args(), mask)
    assert counts() == (0, 2, 0)
    fn = build()
    _, _, masked2 = fn(*toy_args(), mask)
    _, _, plain2 = fn(*toy_args())
    assert counts() == (2, 2, 0)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(plain2))
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(masked2))
    assert float(masked[1]) == 0.0 and float(plain[1]) != 0.0


CFG = ModelConfig(name="store-test", vocab_size=262, hidden_size=64,
                  intermediate_size=128, num_layers=2, num_heads=4,
                  num_kv_heads=2, head_dim=16, dtype="float32")


def _drive(params):
    eng = InferenceEngine(
        CFG, params, EngineConfig(max_batch=4, page_size=8, num_pages=96,
                                  max_pages_per_seq=16,
                                  prefill_buckets=(8, 16, 32), multi_step=4),
        kv_dtype=jnp.float32)
    outs = []
    for n in (5, 12, 30):
        r = eng.generate(list(range(5, 5 + n)), max_new_tokens=12,
                         temperature=0.0)
        outs.append(list(r.output_ids))
    r = GenRequest(request_id="masked", prompt_ids=[3] * 4, max_new_tokens=3,
                   logits_mask_fn=lambda out: (
                       [3] if len(out) == 0 else
                       [3, 4] if len(out) == 1 else None))
    eng.submit(r)
    eng.run_to_completion()
    outs.append(list(r.output_ids))
    return outs, set(eng._programs.built)


def test_an_engine_rebuilt_after_clear_loads_every_step_program(store, traced):
    params = init_params(CFG, jax.random.PRNGKey(11))
    step_programs.clear()
    first, built = _drive(params)
    hits, misses, fallbacks = counts()
    assert (hits, fallbacks) == (0, 0) and misses >= len(built) >= 4
    step_programs.clear()
    del traced[:]
    second, built2 = _drive(params)
    assert second == first and built2 == built
    assert counts() == (misses, misses, 0)
    names = {step_programs.program_name(label) for label, _ in built}
    assert not names & set(traced)  # zero trace events for the step programs


# ----------------------------------------------------------------------
# the key: each ingredient alone turns a hit into a miss
# ----------------------------------------------------------------------


def _touch_source(monkeypatch, tmp_path):
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "a.py").write_bytes(b"x = 1\n")
    before = program_store.source_digest(str(root))
    (root / "a.py").write_bytes(b"x = 2\n")
    program_store.source_digest.cache_clear()
    after = program_store.source_digest(str(root))
    assert before != after
    monkeypatch.setattr(program_store, "source_digest", lambda: after)
    return {}


INGREDIENTS = {
    "a touched source byte": _touch_source,
    "a KAFKA_TPU_ value": lambda mp, tmp: mp.setenv(
        "KAFKA_TPU_ATTENTION_BACKEND", "xla") or {},
    "XLA_FLAGS": lambda mp, tmp: mp.setenv(
        "XLA_FLAGS", os.environ.get("XLA_FLAGS", "") + " ") or {},
    "a changed cfg field": lambda mp, tmp: {
        "key": ("toy", dataclasses.replace(CFG, rms_norm_eps=1e-6))},
    "another label": lambda mp, tmp: {"label": "toy2"},
    "another dtype": lambda mp, tmp: {"args": toy_args(jnp.bfloat16)},
    "another shape": lambda mp, tmp: {"args": toy_args(n=8)},
    "another device": lambda mp, tmp: {
        "args": toy_args(device=jax.devices()[1])},
    "jax's trace context": lambda mp, tmp: {},  # (the test's `with`)
    "the PJRT version": lambda mp, tmp: mp.setattr(
        program_store, "_runtime_version", lambda dev: "another") or {},
}


@pytest.mark.parametrize("name", sorted(INGREDIENTS))
def test_each_key_ingredient_alone_is_a_miss(store, monkeypatch, tmp_path,
                                             name):
    key = ("toy", CFG)
    build(key)(*toy_args())
    build(key)(*toy_args())
    assert counts() == (1, 1, 0)  # the control: nothing changed, a hit
    changed = INGREDIENTS[name](monkeypatch, tmp_path)
    if name == "jax's trace context":
        with jax.numpy_rank_promotion("warn"):
            build(key)(*toy_args())
    else:
        build(changed.get("key", key), changed.get("label", "toy"))(
            *changed.get("args", toy_args()))
    assert counts() == (1, 2, 0), name
    assert len(program_store.entries(str(store))) == 2


def test_the_profiler_switch_is_not_in_the_key_and_no_traced_code_reads_it(
        store, monkeypatch):
    """A traced benchmark run sets KAFKA_TPU_PROFILING: it boots warm from
    what the untraced runs stored, because nothing under a trace reads it."""
    build()(*toy_args())
    monkeypatch.setenv("KAFKA_TPU_PROFILING", "1")
    build()(*toy_args())
    assert counts() == (1, 1, 0)
    pkg = REPO / "kafka_tpu"
    traced_code = [*pkg.glob("models/**/*.py"), *pkg.glob("ops/**/*.py"),
                   *pkg.glob("parallel/**/*.py"),
                   pkg / "runtime" / "step_programs.py"]
    assert len(traced_code) > 20
    for path in traced_code:
        text = path.read_text()
        for name in program_store.HOST_ONLY_ENV:
            assert name not in text, (path, name)
        assert "profiler_annotations_enabled" not in text, path


# ----------------------------------------------------------------------
# the store never stops a boot
# ----------------------------------------------------------------------


@pytest.mark.parametrize("damage", ["torn", "foreign", "another_layout"])
def test_a_file_it_cannot_trust_is_a_miss_and_is_rewritten(store, damage):
    _, _, want = build()(*toy_args())
    (entry,) = program_store.entries(str(store))
    data = pathlib.Path(entry[0]).read_bytes()
    pathlib.Path(entry[0]).write_bytes({
        "torn": data[: len(data) // 2],
        "foreign": b"not a program\n" * 10,
        "another_layout": data.replace(program_store.MAGIC,
                                       b"kafka_tpu program store 0\n"),
    }[damage])
    _, _, got = build()(*toy_args())
    assert counts() == (0, 2, 0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    build()(*toy_args())
    assert counts() == (1, 2, 0)  # written over: the next build loads it


def test_an_unwritable_directory_boots(store, caplog):
    store.parent.joinpath(program_store.DIR_NAME).write_text("a file")
    with caplog.at_level("WARNING", logger="kafka_tpu.program_store"):
        _, _, a = build()(*toy_args())
        _, _, b = build(label="toy2")(*toy_args())
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert counts() == (0, 2, 0)
    logged = [r for r in caplog.records if "cannot write" in r.getMessage()]
    assert len(logged) == 1  # once, not a line a program


def test_a_call_the_program_was_not_compiled_for_falls_to_the_jit(store):
    fn = build()
    fn(*toy_args())
    args = toy_args(n=8)  # same tree, other shapes
    _, _, y = fn(*args)
    assert counts() == (0, 1, 1)
    np.testing.assert_array_equal(np.asarray(y), 2.0 * np.arange(8) + 3.0)
    assert args[1].is_deleted()  # the jit donated them, once
    # the variant stays with the jit: both shapes run, nothing more counted
    fn(*toy_args())
    fn(*toy_args(n=8))
    assert counts() == (0, 1, 1)


def test_without_the_compile_cache_a_step_program_is_the_plain_jit():
    assert program_store.directory() is None  # the suite's setting
    fn = step_programs._jit_step("toy", toy(), ("toy", 1), None)
    assert type(fn) is type(jax.jit(lambda x: x))
    assert compile_log.get() is None  # (else instrument() would wrap it)


def test_enable_compile_cache_is_the_switch(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append(k))
    try:
        assert compile_log.enable_compile_cache() == str(tmp_path)
        assert program_store.directory() == str(
            tmp_path / program_store.DIR_NAME)
        compile_log.configure_cache(None)  # server/app.py, compile_cache off
        assert program_store.directory() is None
    finally:
        program_store.enable(None)


def test_a_program_over_a_mesh_is_left_to_the_jit(store):
    """By `mesh` (StepPrograms hands `_jit_step` no key) and by the
    arguments: a leaf over several devices is not stored."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    programs = step_programs.StepPrograms(CFG, mesh, 8, 4, 16)
    fn = programs.prefill(8)
    assert not isinstance(fn, program_store.StoredProgram)
    step_programs.clear()
    sharded = jax.device_put(
        jnp.arange(4, dtype=jnp.float32),
        NamedSharding(mesh, PartitionSpec("tp")))
    # (uncommitted, so that the jit may place them beside the sharded leaf)
    params, k_pool, v_pool = (
        {"w": jnp.ones((3,))}, jnp.zeros((4, 2)), {"v": jnp.ones((4,))})
    _, _, y = build()(params, k_pool, v_pool, sharded)
    np.testing.assert_array_equal(np.asarray(y), 2.0 * np.arange(4) + 3.0)
    assert counts() == (0, 0, 0) and not program_store.entries(str(store))


def test_a_program_on_another_device_is_stored_under_that_device(store):
    dev = jax.devices()[3]
    build()(*toy_args(device=dev))
    _, _, y = build()(*toy_args(device=dev))
    assert counts() == (1, 1, 0)
    assert y.devices() == {dev}
    (entry,) = program_store.entries(str(store))
    assert program_store.read_meta(entry[0])["key"]["device"] == [0, dev.id]


def test_the_least_recently_used_entry_goes_first(store):
    for i, label in enumerate(("a", "b", "c")):
        build(label=label)(*toy_args())
        path = program_store.entries(str(store))[-1][0]
        os.utime(path, (1000.0 + i, 1000.0 + i))
    build(label="a")(*toy_args())  # a load touches its file
    size = {program_store.read_meta(p)["label"]: s
            for p, s, _ in program_store.entries(str(store))}
    assert program_store.evict(str(store), size["a"] + size["c"]) == 1
    left = [program_store.read_meta(p)["label"]
            for p, _, _ in program_store.entries(str(store))]
    assert left == ["c", "a"]


# ----------------------------------------------------------------------
# what it reports
# ----------------------------------------------------------------------


def test_a_load_is_a_ring_record_of_disposition_store(store):
    compile_log.reset_for_tests()
    obs = compile_log.init(16)
    try:
        build()(*toy_args())
        build()(*toy_args())
        recs = [r for r in obs.records() if r["label"] == "toy"]
        assert [r["cache"] for r in recs] == ["off", "store"]
        sec = obs.metrics_section()
        assert sec["by_cache"]["store"] == 1
        assert (sec["store_hits"], sec["store_misses"],
                sec["store_fallbacks"]) == (1, 1, 0)
        assert set(sec) == set(COMPILE_METRIC_KEYS) | {
            m.key for m in METRICS if m.section == "compiles"}
    finally:
        compile_log.reset_for_tests()


def test_the_new_metrics_keys_are_declared():
    assert {"store_hits", "store_misses",
            "store_fallbacks"} <= set(COMPILE_METRIC_KEYS)
    assert "store_load_s" in BOOT_METRIC_KEYS
    families = {m.key: (m.family, dict(m.labels)) for m in METRICS
                if m.section == "compiles"}
    assert families["store_hits"] == (
        "kafka_tpu_program_store_total", {"event": "hit"})


# ----------------------------------------------------------------------
# scripts/program_store.py
# ----------------------------------------------------------------------


def test_the_script_lists_verifies_and_clears(store, capsys, monkeypatch):
    # `verify` lowers each entry in a process of its own, which must come
    # up under the jax configuration conftest.py gave this one in code
    monkeypatch.setenv("JAX_DEFAULT_MATMUL_PRECISION", "highest")
    sys.path.insert(0, str(REPO / "scripts"))
    try:
        import program_store as script
    finally:
        sys.path.pop(0)
    programs = step_programs.StepPrograms(CFG, None, 8, 4, 16)
    params = init_params(CFG, jax.random.PRNGKey(0))
    from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

    k_pool, v_pool = make_kv_pool_arrays(CFG, 24, 8, dtype=jnp.float32)
    programs.prefill(8)(
        params, k_pool, v_pool, np.arange(16, dtype=np.int32),
        np.full((8,), 3, np.int32), np.int32(0), np.int32(5),
        np.float32(0.0), np.int32(0), np.float32(1.0),
        np.asarray([7], np.uint32), np.ones((1, CFG.vocab_size), bool))
    assert counts() == (0, 1, 0)
    assert script.main(["list", str(store)]) == 0
    assert "prefill[8]" in capsys.readouterr().out
    assert script.main(["verify", str(store)]) == 0
    assert "equal" in capsys.readouterr().out
    # an ingredient the key does NOT hold would show here: the same entry
    # under a recipe that traces to another text
    (entry,) = program_store.entries(str(store))
    meta, body = program_store.read_entry(entry[0])
    make, cfg, ps, extra = body["recipe"]
    body["recipe"] = (make, dataclasses.replace(cfg, rms_norm_eps=1e-3), ps,
                      extra)
    program_store.write_entry(str(store), "0" * 64, meta, body)
    assert script.main(["verify", str(store)]) == 1
    assert "differs" in capsys.readouterr().out
    assert script.main(["clear", str(store)]) == 0
    assert not program_store.entries(str(store))


# ----------------------------------------------------------------------
# benchmarks/layer_metrics/boot_store_hit_share.py
# ----------------------------------------------------------------------


@pytest.mark.parametrize("compiles,want", [
    ({"compiles_total": 70, "by_cache": {"hit": 20}}, None),  # the parent
    ({"store_hits": 8, "store_misses": 0, "store_fallbacks": 0}, 100.0),
    ({"store_hits": 6, "store_misses": 1, "store_fallbacks": 1}, 75.0),
    ({"store_hits": 0, "store_misses": 8, "store_fallbacks": 0}, 0.0),
    ({"store_hits": 0, "store_misses": 0, "store_fallbacks": 0}, None),
    (None, None),
])
def test_the_benchmark_reader_of_the_hit_share(compiles, want):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "boot_store_hit_share", REPO / "benchmarks" / "layer_metrics"
        / "boot_store_hit_share.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    ctx = {"after": {} if compiles is None else {"compiles": compiles}}
    assert reader.read(ctx) == want
    assert reader.read({}) is None
