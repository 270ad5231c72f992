"""The reader of ``point_cache`` on the oracle's ``pallas.lower`` spans,
on a run made by hand: hits over the points not refused, and nothing
where the program sets no ``point_cache``."""

import os

import pytest

import harness
from repro.core.obs import Tracer

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _span(tracer, name, start, end, **attrs):
    tracer.span(name, start=start, **attrs).finish(end=end)


def _read(tracer):
    run = harness.Run(cell=None, seconds=1.0)
    run.tracer = tracer
    return harness.read_metric(BENCH, "point_cache_hit_share", run)


def test_point_cache_hit_share_reads_the_lower_spans():
    tr = Tracer()
    for t, point in enumerate(["hit", "hit", "miss", "hit"]):
        _span(tr, "pallas.lower", float(t), t + 0.01, component="warp",
              point_cache=point)
    # refused points count in neither the hits nor the whole
    _span(tr, "pallas.lower", 9.0, 9.5, point_cache="miss",
          refused="lowering: ValueError: x")
    _span(tr, "pallas.lower", 10.0, 10.5, point_cache="hit",
          refused="lowering: ValueError: x")
    assert _read(tr) == pytest.approx(75.0)
    cold = Tracer()
    _span(cold, "pallas.lower", 0.0, 0.1, point_cache="miss")
    assert _read(cold) == 0.0


def test_point_cache_hit_share_finds_nothing_without_point_cache():
    # untraced, or a program whose spans carry no point_cache
    assert _read(None) is None
    bare = Tracer()
    _span(bare, "pallas.lower", 0.0, 0.1, component="warp")
    assert _read(bare) is None
