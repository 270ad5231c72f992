"""Milliseconds per timed kernel point in the program's
``pallas.warmup`` and ``pallas.reps`` spans: the warm-up launch and
the timed launches, the only part of a point in which the device
works."""


def read(run):
    if run.tracer is None:
        return None
    reps = run.tracer.spans("pallas.reps")
    if not reps:
        return None
    spans = run.tracer.spans("pallas.warmup") + reps
    return 1e3 * sum(s.end - s.start for s in spans) / len(reps)
