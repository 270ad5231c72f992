"""Share (%) of the kernel points whose lowering the point cache made
unnecessary: the program's ``pallas.lower`` spans without ``refused``
whose ``point_cache`` reads ``hit``, over every query of the run.  A
program that sets no ``point_cache`` gives nothing to read."""


def read(run):
    if run.tracer is None:
        return None
    spans = [s for s in run.tracer.spans("pallas.lower")
             if "refused" not in s.attrs and "point_cache" in s.attrs]
    if not spans:
        return None
    return 100.0 * sum(s.attrs["point_cache"] == "hit" for s in spans) \
        / len(spans)
