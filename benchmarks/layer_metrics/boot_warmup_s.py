"""Boot: seconds of the `warmup` stage of the program's boot (`/metrics`
`boot.warmup_s`, `kafka_tpu.tracing.BOOT_STAGES`): `_warm_engine` over every
prefill bucket and decode program and the `warmup_*` calls, i.e. every
compile (or load from the persistent cache) and one run of each program.
None on a program without the section."""


def read(ctx):
    try:
        return float(ctx["after"]["boot"]["warmup_s"])
    except (KeyError, TypeError, ValueError):
        return None
