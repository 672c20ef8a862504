"""Boot: seconds of the `weights` stage of the program's boot (`/metrics`
`boot.weights_s`, `kafka_tpu.tracing.BOOT_STAGES`): `init_params` or
`load_checkpoint`, and `quantize_params`.  Random weights are made by a
jitted program that is dispatched and not waited for: its compile is in the
stage, its run on the device ends under `engine_build`.  All six stages are
printed beside it.  None on a program without the section."""
import json
import sys


def read(ctx):
    try:
        boot = ctx["after"]["boot"]
        value = float(boot["weights_s"])
    except (KeyError, TypeError, ValueError):
        return None
    print("sched_account: boot " + json.dumps(boot),
          file=sys.stderr, flush=True)
    return value
