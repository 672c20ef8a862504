"""State layers, by the FORM their ops took: of the window's state launches
(passes x state layers, the recurrence and the convolution's tail counted
apart), the % that ran as a Pallas kernel and not as XLA ops (`/metrics`
`engine.state_launches_<op>_<form>`, window deltas; Prometheus
`kafka_tpu_engine_state_launches_total{op, form}`).  Which form a launch
takes is decided by shapes when its program is traced, and was visible in a
device capture only: Granite's (8, 3168) tail runs the XLA chain and nothing
said so; this model's 96 x 192 state would have run a row-by-row scan in
silence.  Olmo-Hybrid on the Pallas backend: every recurrence a kernel
(`gdn_step`, `gdn_chunk`), every tail XLA (pieces of 11.25 lane tiles): 50.
A program that exports no such counter (the parent) or ran no state launch in
the window has nothing to read: None."""
import readers

OPS, FORMS = ("recurrence", "tail"), ("kernel", "xla")


def read(ctx):
    counts = {(op, form): readers.counter_delta(
        ctx, "engine", f"state_launches_{op}_{form}")
        for op in OPS for form in FORMS}
    if None in counts.values() or not sum(counts.values()):
        return None
    return 100.0 * sum(counts[op, "kernel"] for op in OPS) / sum(
        counts.values())
