"""Boot: seconds JAX spent tracing programs to jaxprs and lowering them to
modules during the boot (`/metrics` `compiles.trace_seconds_by_phase` +
`compiles.lower_seconds_by_phase`, phases `boot` and `warmup`: jax's
`jaxpr_trace_duration` and `jaxpr_to_mlir_module_duration` events): what the
persistent compile cache does not save, paid at EVERY boot (a Pallas call
lowers its kernel each time).  The two stages by phase are printed beside it.
None on a program without the sums."""
import json
import sys

PHASES = ("boot", "warmup")


def read(ctx):
    try:
        compiles = ctx["after"]["compiles"]
        stages = {s: compiles[s + "_seconds_by_phase"]
                  for s in ("trace", "lower")}
        value = sum(float(by.get(p, 0.0))
                    for by in stages.values() for p in PHASES)
    except (KeyError, TypeError, ValueError, AttributeError):
        return None
    print("sched_account: trace and lower " + json.dumps(stages),
          file=sys.stderr, flush=True)
    return value
