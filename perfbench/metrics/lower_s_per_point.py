"""Seconds the measured oracle spent lowering a kernel point, over the
points whose lowering went through: the program's ``pallas.lower``
spans without ``refused``, over every query of the run."""


def read(run):
    if run.tracer is None:
        return None
    spans = [s for s in run.tracer.spans("pallas.lower")
             if "refused" not in s.attrs]
    if not spans:
        return None
    return sum(s.end - s.start for s in spans) / len(spans)
