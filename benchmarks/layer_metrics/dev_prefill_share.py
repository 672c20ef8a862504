"""Step programs: % of the capture's device busy time in prefill programs
(`jit_fn_prefill_<bucket>`, `jit_fn_bprefill_<bucket>x<width>`), all their
ops: what prefill chunks take from the decoding lanes."""
import scope_reduce


def read(ctx):
    return scope_reduce.share(scope_reduce.of_ctx(ctx),
                              programs=scope_reduce.PREFILL_PROGRAM)
