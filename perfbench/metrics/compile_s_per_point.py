"""Seconds the measured oracle spent lowering and compiling, per kernel
point it timed, over every query of the run (``PallasOracle.stats``)."""


def read(run):
    timed = sum(q.oracle.stats["timed"] for q in run.queries)
    if not timed:
        return None
    return sum(q.oracle.stats["compile_s"] for q in run.queries) / timed
