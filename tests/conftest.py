"""Test configuration.

Forces JAX onto CPU with 8 virtual devices so sharding/mesh tests exercise
real 8-way SPMD partitioning without TPU hardware (the standard JAX recipe:
--xla_force_host_platform_device_count).

JAX_PLATFORMS=cpu in the environment is enough to keep the suite off an
accelerator (nothing imports jax before this file does); the
jax.config.update below repeats it for a run started without the variable.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# The suite is XLA:CPU COMPILE-bound (hundreds of jitted programs over
# tiny models), and tests don't need optimized code — skipping LLVM's
# expensive passes measured ~40% faster module runs with identical
# numerics (greedy token streams, chi-square distribution checks, and
# the llama forward-parity tests all pass under it).  Tests only: the
# serving path never sets this.
if "xla_llvm_disable_expensive_passes" not in flags:
    flags = (flags + " --xla_llvm_disable_expensive_passes=true").strip()
os.environ["XLA_FLAGS"] = flags
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Hermetic suite: never dial the default remote MCP server from tests
# (individual tests override this to exercise the config parser).
os.environ.setdefault("KAFKA_TPU_MCP_SERVERS", "[]")
# NO persistent compile cache in tests: server boots would enable it
# (ServingConfig.compile_cache), but serializing/deserializing CPU
# SPMD executables segfaults/aborts INSIDE XLA in this environment —
# observed three times at suite scale, in both put_executable_and_time
# (write) and get_executable_and_time (read, machine-feature mismatch
# from a migrated host).  An in-process crash is uncatchable and kills
# the whole run, so tests switch the cache off (runtime/compile_log.py
# compile_cache_enabled) AND drop JAX's own variable, which JAX would
# otherwise honour by itself; the TPU serving path keeps the cache.
os.environ["KAFKA_TPU_COMPILE_CACHE"] = "0"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

# NO compile observatory by default in tests: the observatory is a
# process-wide singleton, and build_tpu_provider boots it and leaves the
# phase at "first_traffic" on exit.  After any test touches that path,
# the suite's hundreds of tiny-model recompiles all read as live-traffic
# compiles, the storm detector latches, and every later engine's flight
# recorder reports a compile_storm anomaly — observed polluting
# test_metrics, test_autoscaler, and test_flight_recorder at suite
# scale.  Ring size 0 makes init() a no-op ("" would mean "use the
# default"); device-truth tests opt back in with an explicit init(size)
# or a monkeypatched env.
os.environ["KAFKA_TPU_COMPILE_RING"] = "0"

# The root cause of full-suite crashes (segfault/abort inside XLA:CPU
# compile, detonating at a shifting late-suite test): every JIT-compiled
# executable holds process memory mappings, the suite compiles thousands,
# and the count crosses vm.max_map_count (65530 default) near the end —
# mmap starts failing and LLVM/XLA dies uncatchably.  Measured: ~42k maps
# six minutes into the run, growing ~5k/min.  Two defenses: raise the
# sysctl when permitted AND opted in (the sysctl is HOST-GLOBAL kernel
# config, so mutating it is gated behind KAFKA_TPU_TEST_RAISE_MAP_COUNT=1
# and undone at session finish — see pytest_sessionfinish below), and drop
# compiled executables between test modules (fixture below), which is the
# always-on defense.
_PRIOR_MAP_COUNT = None
if os.environ.get("KAFKA_TPU_TEST_RAISE_MAP_COUNT") == "1":
    try:
        with open("/proc/sys/vm/max_map_count") as _f:
            _cur = int(_f.read())
        if _cur < 262144:
            with open("/proc/sys/vm/max_map_count", "w") as _f:
                _f.write("262144")
            _PRIOR_MAP_COUNT = _cur
    except (OSError, ValueError):
        pass  # not privileged / not Linux: the per-module purge still applies


def pytest_configure(config):
    """Marker registration (no pytest.ini in this repo).

    * ``slow`` — excluded from the tier-1 run (`-m 'not slow'`); its
      semantics are unchanged vs the seed, just registered now.
    * ``chaos`` — multi-PROCESS kill tests (subprocess spawn + kill +
      backoff waits).  Chaos tests that are also slow carry BOTH markers
      so tier-1 keeps its fast single-process subset; run the full matrix
      with ``pytest -m chaos``.
    """
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from tier-1 (-m 'not slow')"
    )
    config.addinivalue_line(
        "markers",
        "chaos: cross-process fault-injection (kill subprocesses/workers)",
    )


# Compile-heavy integration modules, light -> heavy.  Everything NOT
# listed (the cheap unit modules: wire formats, tries, metrics, sandbox
# protocol, tracing, ...) runs first in its usual order; the listed
# modules are appended in THIS order, heaviest per-test at the very end.
# Time-to-signal ordering: failures in the cheap majority surface in the
# first minutes, and a CI/driver wall-clock budget that truncates the run
# cuts into the most expensive tail instead of a random alphabetical
# suffix.  Modules are already isolated (module-scoped fixtures, the
# _drop_xla_executables purge, monkeypatch-reverted env), so inter-module
# order is not load-bearing; intra-module order is unchanged.
_HEAVY_TAIL = (
    "test_flash_prefill.py",
    "test_kv_quant.py",
    "test_quant.py",
    "test_compaction.py",
    "test_llm_provider.py",
    "test_prefix_cache.py",
    "test_pallas_kernels.py",
    "test_constrained.py",
    "test_server.py",
    # boots the real server in a subprocess (chip_smoke.py --rehearse)
    "test_chip_smoke.py",
    # autoscaler chaos e2e builds dp routers over the tiny model and
    # smoke-runs the bench traffic-ramp phase (compile-heavy rebuilds)
    "test_autoscaler.py",
    "test_dp_router.py",
    # disaggregated prefill/decode shares test_dp_router's dp=2 tiny
    # model and adds cross-replica ship compiles on top
    "test_disagg.py",
    "test_engine.py",
    # after test_engine: the tier tests share its tiny-model shapes, and
    # running them first would pre-warm the XLA cache under test_engine's
    # wall-clock-sensitive deadline tests (timeout would race length)
    "test_kv_tier.py",
    # object-store tier builds several engines over the same tiny-model
    # shapes (sleep on A / wake on B) — keep it with the tier tests on
    # the warm-cache side of test_engine
    "test_object_tier.py",
    # zero-copy movement (ISSUE 19) reuses the shipper pool shapes and
    # the object-tier fixtures — keep it with its neighbors on the
    # warm-cache side (its jax work is gather/scatter compiles only)
    "test_zero_copy.py",
    # store-guard fsck/outage acceptance builds the same engine shapes
    # (drain on A, scrub, wake on B) plus the bench store_outage smoke
    "test_store_guard.py",
    # flight-recorder integration shares the tiny-model shapes too and
    # arms wall-clock-sensitive delay failpoints — keep it off the cold
    # compile path like test_kv_tier
    "test_flight_recorder.py",
    # device-truth telemetry (ISSUE 18) drives real engines with the
    # kernel sampler tracing every step — jax.profiler windows on the
    # warm-cache side, same reasoning as test_flight_recorder
    "test_device_truth.py",
    "test_grammar_fsm.py",
    "test_speculative.py",
    "test_server_parallel.py",
    "test_parallel.py",
    "test_moe.py",
    "test_pp_ep.py",
    "test_vision.py",
    "test_checkpoint_serving.py",
    "test_llama_numerics.py",
    "test_long_context.py",
    "test_multihost.py",
)


def pytest_collection_modifyitems(config, items):
    """Time-to-signal ordering (see _HEAVY_TAIL): stable sort by
    (tail rank, original position) — unlisted modules keep their relative
    order up front, listed modules run last in list order."""
    rank = {name: i + 1 for i, name in enumerate(_HEAVY_TAIL)}
    pos = {id(item): i for i, item in enumerate(items)}
    items.sort(key=lambda item: (
        rank.get(item.path.name if hasattr(item, "path")
                 else item.fspath.basename, 0),
        pos[id(item)],
    ))


def pytest_sessionfinish(session, exitstatus):
    """Restore the host sysctl we raised (never leave kernel config
    mutated as a test side effect)."""
    if _PRIOR_MAP_COUNT is None:
        return
    try:
        with open("/proc/sys/vm/max_map_count", "w") as _f:
            _f.write(str(_PRIOR_MAP_COUNT))
    except OSError:
        pass

import gc  # noqa: E402

import pytest  # noqa: E402

import jax  # noqa: E402

# belt and braces: the suite is CPU-only even when JAX_PLATFORMS was not
# exported (no backend has been initialized yet, so this still takes)
jax.config.update("jax_platforms", "cpu")


@pytest.fixture(autouse=True, scope="module")
def _drop_xla_executables():
    """Per-module XLA executable purge (see max_map_count note above).

    Engines and jitted helpers from a finished module are garbage;
    clearing jax's caches and collecting frees their code mappings.  Live
    objects from module-scoped fixtures simply recompile on next use."""
    yield
    jax.clear_caches()
    gc.collect()
# DEFAULT matmul precision runs f32 einsums through a reduced-precision fast
# path (bf16 passes on TPU MXU, oneDNN on CPU) whose rounding is
# shape-dependent — decode-vs-full-forward token comparisons then flip on
# near-tied logits. Tests pin full f32 precision; production keeps DEFAULT.
jax.config.update("jax_default_matmul_precision", "highest")
