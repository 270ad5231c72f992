"""Seconds of jaxpr tracing (JAX's ``jaxpr_trace_duration``, outermost
only) per kernel point whose lowering went through: the ``trace_s`` of
the program's ``pallas.lower`` spans without ``refused``."""


def read(run):
    if run.tracer is None:
        return None
    spans = [s for s in run.tracer.spans("pallas.lower")
             if "refused" not in s.attrs]
    if not spans:
        return None
    return sum(s.attrs["trace_s"] for s in spans) / len(spans)
