"""Inputs of the /metrics goldens under tests/recorded/ (ISSUE 46).

`scripts/record_metric_goldens.py` ran these AT THE PARENT COMMIT (646876c,
the hand-written snapshot / dp merge / renderer) and wrote what they gave;
tests/test_prometheus.py runs them on the tree as it is and compares.  The
module names no metric key of its own: optional sections are filled from
the registries, so the same code builds the same snapshot on both trees.
"""

import json
import os
import types

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded")

# integer leaves of the live engine's snapshot that the host's clock
# decides, not the script of requests (golden b compares every other one)
CLOCKED = {
    ("engine", "fetch_pops"),          # which rule released each entry
    ("engine", "fetch_depth_steps_sum"),
    ("engine", "fetch_depth_samples"),
    ("engine", "decode_holds"),
    ("flight", "flight_records"),      # one a scheduler iteration
    ("utilization",),                  # gaps over 2 s are dropped
    ("histograms",),
    ("slo",),                          # met / missed against 200 ms
    ("anomalies",),
    ("sched",),                        # gaps and deliveries the clock saw
}


def load(name):
    path = os.path.join(RECORDED, name)
    with open(path) as f:
        return f.read() if name.endswith(".prom") else json.load(f)


def tiny_engine():
    import jax
    import jax.numpy as jnp

    from kafka_tpu.models import ModelConfig, init_params
    from kafka_tpu.runtime import EngineConfig, InferenceEngine

    cfg = ModelConfig(name="golden-tiny", vocab_size=300, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(3))
    return InferenceEngine(
        cfg, params,
        EngineConfig(max_batch=2, page_size=8, num_pages=64,
                     max_pages_per_seq=8, prefill_buckets=(8, 16, 32)),
        kv_dtype=jnp.float32,
    )


def run_script(eng):
    """The fixed script: three greedy requests one after the other, the
    second and third over the first one's prompt."""
    shared = list(range(5, 29))
    eng.generate(shared + [40, 41], max_new_tokens=6, prefix_key="t1")
    eng.generate(shared + [50, 51, 52], max_new_tokens=5, prefix_key="t2")
    eng.generate(shared[:16] + [60], max_new_tokens=4, prefix_key="t3")


def live_snapshot():
    eng = tiny_engine()
    run_script(eng)
    return eng.metrics.snapshot(eng)


def shape(node):
    """Nested key set with the type of every leaf."""
    if isinstance(node, dict):
        return {k: shape(v) for k, v in node.items()}
    if isinstance(node, list):
        return [shape(v) for v in node]
    return type(node).__name__


def int_leaves(node, path=()):
    """{path: value} for every integer leaf the clock does not decide."""
    out = {}
    if any(path[:len(c)] == c for c in CLOCKED):
        return out
    if isinstance(node, dict):
        for k, v in node.items():
            out.update(int_leaves(v, path + (k,)))
    elif isinstance(node, int) and not isinstance(node, bool):
        out["/".join(path)] = node
    return out


def _distinct(node, start, skip=("histograms", "ship_ms")):
    """Every numeric leaf outside the histograms gets a value of its own
    (ints stay ints), so a merge or a family that reads the wrong key
    shows."""
    counter = [start]

    def walk(n):
        if isinstance(n, dict):
            return {k: (v if k in skip else walk(v)) for k, v in n.items()}
        if isinstance(n, list):
            return [walk(v) for v in n]
        if isinstance(n, bool) or not isinstance(n, (int, float)):
            return n
        counter[0] += 1
        return counter[0] if isinstance(n, int) else counter[0] + 0.25

    return walk(node)


def replica_snapshot(base, start):
    """`base` (a live engine's snapshot) with every optional per-replica
    section present and distinct values from `start` up."""
    from kafka_tpu.runtime import metrics as M

    snap = json.loads(json.dumps(base))
    for section, keys in (("kv_tier", M.KV_TIER_METRIC_KEYS),
                          ("object_tier", M.OBJECT_TIER_METRIC_KEYS),
                          ("flight", M.FLIGHT_METRIC_KEYS),
                          ("agent", M.AGENT_METRIC_KEYS),
                          ("state", M.STATE_METRIC_KEYS)):
        snap[section] = {k: 0 for k in keys}
    snap["memory"] = {
        "source": "device",
        **{k: 0 for k in M.MEMORY_METRIC_KEYS},
        "hbm_plan_skew": 0.5,
        "hbm_component_bytes": {"weights": 0, "kv_pool": 0,
                                "unattributed": 0},
        "devices": [{"device": str(start), "bytes_in_use": 0,
                     "bytes_peak": 0, "bytes_limit": 0}],
    }
    snap["anomalies"]["active"] = [
        {"kind": "queue_stall", "since_s": 1.5}]
    for name in M.HISTOGRAM_NAMES:
        h = M.StreamingHistogram.from_snapshot(snap["histograms"][name])
        h.record(float(start % 97 + 1))
        snap["histograms"][name] = h.snapshot()
    snap = _distinct(snap, start)
    snap["utilization"]["peak_tflops"] = 197.0
    snap["utilization"]["peak_hbm_gbps"] = 819.0
    return snap


class _FakeEngine:
    def __init__(self, snap, busy, waiting, active, parked):
        self.metrics = types.SimpleNamespace(
            snapshot=lambda engine=None, reset_peak=True:
                json.loads(json.dumps(snap)),
            decode_busy_slots=busy,
        )
        self.waiting = [None] * waiting
        self.num_active = active
        self.parked = [None] * parked


def fake_router(replica_snaps):
    """What `_AggregateMetrics` reads of a router, over recorded replica
    snapshots: replica 0 the prefill pool, the rest the decode pool."""
    from kafka_tpu.runtime.metrics import (
        DisaggMetrics,
        ReplicaSupervisorMetrics,
    )

    n = len(replica_snaps)
    disagg = DisaggMetrics()
    disagg.record_ship(3, 4096, 0.002, transport="host")
    disagg.record_ship(5, 8192, 0.004, transport="device")
    disagg.ship_failures, disagg.ship_skips = 7, 11
    disagg.handoffs, disagg.prefill_in_place = 13, 17
    health = [
        types.SimpleNamespace(
            gauge=lambda i=i: (1.0, 0.5)[i % 2],
            state=("healthy", "probation")[i % 2],
            consecutive_failures=i, total_failures=10 + i)
        for i in range(n)
    ]
    return types.SimpleNamespace(
        engines=[_FakeEngine(s, busy=1000 + 37 * i, waiting=2 + i,
                             active=1 + i, parked=i)
                 for i, s in enumerate(replica_snaps)],
        _prefill_pool=[0],
        _decode_pool=list(range(1, n)),
        disagg=disagg,
        health=health,
        supervisor=ReplicaSupervisorMetrics(
            quarantines=3, readmits=2, waiting_migrated=5,
            affinity_resteered=4, rebuilds=1, replica_rebuilds=6),
    )


def aggregate(replica_snaps):
    from kafka_tpu.runtime.dp_router import _AggregateMetrics

    return _AggregateMetrics(fake_router(replica_snaps)).snapshot()


def served_snapshot(agg, replica_snaps):
    """The aggregate as GET /metrics serves it: the sections server/app.py
    lays over it, and a recurrent model's `state` (a replica's, which the
    one-engine snapshot carries at the top)."""
    from kafka_tpu.runtime import metrics as M

    snap = json.loads(json.dumps(agg))
    snap["state"] = replica_snaps[0]["state"]
    snap["requests"]["slow"] = 9001
    snap["sandbox"] = {"crashes": 9002, "restarts": 9003,
                       "crash_loops": 9004, "reaped": 9005}
    snap["tracing"] = {"traces": 9006, "stitched_spans": 9007,
                       "slow": 9001}
    snap["autoscaler"] = {
        k: 9100 + i for i, k in enumerate(M.AUTOSCALER_METRIC_KEYS)}
    snap["compiles"] = {
        **{k: 9200 + i for i, k in enumerate(M.COMPILE_METRIC_KEYS)},
        "compile_seconds_total": 92.125,
        "by_cache": {"hit": 9210, "miss": 9211},
        "by_phase": {"boot": 9212, "warmup": 9213, "first_traffic": 9214},
        "trace_seconds_by_phase": {"boot": 92.5, "warmup": 93.5},
        "lower_seconds_by_phase": {"boot": 94.5, "warmup": 95.5},
    }
    snap["boot"] = {
        k: 9300.5 + i for i, k in enumerate(M.BOOT_METRIC_KEYS)}
    snap["metrics"] = {"snapshot_s": 94.125, "snapshots": 9401}
    return snap
