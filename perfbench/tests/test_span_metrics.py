"""The readers of the oracle's ``pallas.*`` spans, on a run made by
hand: what they read, and nothing where the program opens no such
span."""

import os

import pytest

import harness
from repro.core.obs import Tracer

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = ("lower_s_per_point", "jaxpr_trace_s_per_point",
         "measure_ms_per_point")


def _span(tracer, name, start, end, **attrs):
    tracer.span(name, start=start, **attrs).finish(end=end)


def _run(tracer):
    run = harness.Run(cell=None, seconds=1.0)
    run.tracer = tracer
    return run


def _read(run):
    return {n: harness.read_metric(BENCH, n, run) for n in NAMES}


def test_readers_of_the_oracle_spans():
    tr = Tracer()
    for t, (lower, trace_s) in enumerate([(0.040, 0.010), (0.060, 0.020)]):
        t0 = 10.0 * t
        _span(tr, "pallas.lower", t0, t0 + lower, component="warp",
              trace_s=trace_s, mlir_s=0.005)
        _span(tr, "pallas.compile", t0 + lower, t0 + 0.2, cache="hit")
        _span(tr, "pallas.warmup", t0 + 0.2, t0 + 0.201)
        _span(tr, "pallas.reps", t0 + 0.201, t0 + 0.204, best_s=0.001)
    # a point refused at lowering counts in none of the three
    _span(tr, "pallas.lower", 30.0, 31.0, trace_s=0.5, mlir_s=0.0,
          refused="lowering: ValueError: x")
    got = _read(_run(tr))
    assert got["lower_s_per_point"] == pytest.approx(0.050)
    assert got["jaxpr_trace_s_per_point"] == pytest.approx(0.015)
    assert got["measure_ms_per_point"] == pytest.approx(4.0)


def test_readers_find_nothing_without_the_spans():
    assert _read(_run(None)) == dict.fromkeys(NAMES)
    tr = Tracer()
    _span(tr, "tool.point", 0.0, 1.0, component="warp")
    assert _read(_run(tr)) == dict.fromkeys(NAMES)
