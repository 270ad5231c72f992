"""The trace reduction on hand-made intervals and on a small trace
recorded on a TPU v5e (``data/``)."""

import glob
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_gaps_inside_a_window():
    busy = tr.union(tr.clip([(1, 2), (4, 5), (9, 12)], 0, 10))
    assert tr.gaps(busy, 0, 10) == [(0, 1), (2, 4), (5, 9)]


def test_idle_time_goes_to_the_innermost_open_span():
    spans = [(0.0, 10.0, "query"), (1.0, 3.0, "compile a"),
             (5.0, 6.0, "launch a"), (5.5, 5.6, "inner")]
    idle = tr.attribute([(0.5, 2.0), (5.2, 5.8), (10.0, 11.0)], spans)
    assert idle == pytest.approx({"query": 0.5 + 0.0, "compile a": 1.0,
                                  "launch a": 0.5, "inner": 0.1,
                                  "(no annotation)": 1.0})


def test_reduce_of_a_synthetic_profile():
    class E:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur

    class L:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class P:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    s = 1_000_000_000
    host = P("/host:CPU", [L("python", [
        E("pb:mark start", 0, 10), E("pb:compile k", 1 * s, 2 * s),
        E("pb:mark stop", 10 * s, 10), E("other", 0, 10 * s)])])
    dev = P("/device:TPU:0", [
        L("XLA Ops", [E("k", 4 * s, 1 * s), E("k", 6 * s, 1 * s),
                      E("copy", int(6.5 * s), 1 * s)]),
        L("XLA Modules", [E("jit_k", 0, 10 * s)])])

    class Profile:
        planes = [host, dev]
    got = tr.reduce(Profile)
    assert got["window_s"] == pytest.approx(10.0)
    assert got["busy_s"] == pytest.approx(2.5)
    assert dict(map(tuple, got["device_ops"])) == pytest.approx(
        {"k": 2.0, "copy": 1.0})
    assert dict(map(tuple, got["idle_gaps"])) == pytest.approx(
        {"compile k": 2.0, "(no annotation)": 5.5})


def test_recorded_tpu_trace():
    files = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    assert files, "the recorded trace is missing"
    import jax
    got = tr.reduce(jax.profiler.ProfileData.from_file(files[0]))
    assert got["devices"] == 1
    assert 0 < got["busy_s"] < got["window_s"]
    names = [n for n, _ in got["device_ops"]]
    assert names and all(isinstance(n, str) for n in names)
    idle = sum(s for _, s in got["idle_gaps"])
    assert idle <= got["window_s"] - got["busy_s"] + 1e-9
