#!/usr/bin/env python
"""Record the /metrics goldens of tests/recorded/ (ISSUE 46).

    JAX_PLATFORMS=cpu python scripts/record_metric_goldens.py

Run at the commit whose telemetry is the reference (the files in the tree
were written at 646876c, the last commit with the hand-written snapshot,
dp merge and renderer); tests/test_prometheus.py::TestGoldens compares the
tree as it is against them.  Inputs come from tests/_metric_goldens.py:

    metrics_live.json          a live tiny engine after a fixed script of
                               requests: nested key set, leaf types, and the
                               integer counters the clock does not decide
    metrics_replicas.json      two full replica snapshots, distinct values
    metrics_aggregate.json     _AggregateMetrics.snapshot() over those two
    metrics_served.prom        render_prometheus() of that aggregate as GET
                               /metrics serves it (G.served_snapshot)
    metrics_replica.prom       render_prometheus() of replica 0 alone
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]


def main():
    import _metric_goldens as G

    from kafka_tpu.server.prometheus import render_prometheus

    def write(name, value):
        with open(os.path.join(G.RECORDED, name), "w") as f:
            if name.endswith(".prom"):
                f.write(value)
            else:
                json.dump(value, f, indent=1, sort_keys=True)
                f.write("\n")

    os.makedirs(G.RECORDED, exist_ok=True)
    live = G.live_snapshot()
    write("metrics_live.json",
          {"shape": G.shape(live), "ints": G.int_leaves(live)})
    replicas = [G.replica_snapshot(live, 1000), G.replica_snapshot(live, 5000)]
    write("metrics_replicas.json", replicas)
    agg = G.aggregate(replicas)
    write("metrics_aggregate.json", agg)
    served = G.served_snapshot(agg, replicas)
    write("metrics_served.prom", render_prometheus(served))
    write("metrics_replica.prom", render_prometheus(replicas[0]))


if __name__ == "__main__":
    main()
