"""Observability layer: tracer/metrics units, determinism, reconciliation.

The contract under test (docs/observability.md):

  * two identical runs under a :class:`LogicalClock` export
    byte-identical JSONL and Chrome ``trace_event`` artifacts — the CI
    determinism gate;
  * span nesting mirrors the session's phase structure;
  * every evaluated point carries exactly one outcome tag from
    ``fresh | cache_hit | inflight_join | replay``, and the traced
    tags reconcile with the ledger's Fig. 11 invocation totals;
  * the metrics registry is lock-consistent and create-on-first-use,
    with type conflicts rejected loudly.
"""

import json
import threading

import pytest

from repro.core import (DSEQuery, ExplorationSession, HLSTool, KnobSpace,
                        LogicalClock, MetricsRegistry, NULL_TRACER,
                        OracleLedger, PersistentOracleCache, SharedOracle,
                        Tracer, pipeline_tmg)
from repro.core.hlsim import ComponentSpec, LoopNest
from repro.core.obs import OUTCOMES, validate_chrome, validate_jsonl
from repro.core.oracle import InvocationRequest
from repro.core.registry import _APPS, App, register_app
from repro.serve import DSEService


def _system():
    specs = {
        "a": ComponentSpec("a", LoopNest(256, 2, 1, 8, 3, 6), 1024, 1024),
        "b": ComponentSpec("b", LoopNest(128, 1, 1, 4, 2, 4), 512, 512),
    }
    tmg = pipeline_tmg(list(specs), buffers=2)
    spaces = {n: KnobSpace(clock_ns=1.0, max_ports=4, max_unrolls=8)
              for n in specs}
    return specs, tmg, spaces


def _traced_run(tracer=None):
    specs, tmg, spaces = _system()
    tracer = tracer or Tracer(clock=LogicalClock())
    s = ExplorationSession(tmg, HLSTool(dict(specs)), spaces, delta=0.3,
                           tracer=tracer)
    s.run()
    return s, tracer


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("n")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("depth")
    g.set(3)
    g.add(-1)
    assert g.value == 2
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["n"] == 5
    assert snap["depth"] == 2
    assert snap["lat"]["count"] == 3
    assert snap["lat"]["buckets"] == {"le_0.1": 1, "le_1": 1, "le_inf": 1}
    assert snap["lat"]["sum"] == pytest.approx(5.55)


def test_registry_create_on_first_use_and_conflicts():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")       # same instance
    with pytest.raises(TypeError):
        reg.gauge("x")                                # wrong type
    with pytest.raises(ValueError):
        reg.histogram("h", buckets=(1.0, 2.0)) and \
            reg.histogram("h", buckets=(1.0, 3.0))    # bucket mismatch


def test_counter_thread_consistency():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    threads = [threading.Thread(target=lambda: [c.inc() for _ in range(500)])
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 4000


# ----------------------------------------------------------------------
# tracer units
# ----------------------------------------------------------------------
def test_span_nesting_follows_with_stack():
    tr = Tracer(clock=LogicalClock())
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            assert tr.current() is inner
        assert tr.current() is outer
    assert tr.current() is None
    [i] = tr.spans("inner")
    assert i.parent_id == outer.span_id


def test_span_error_status_recorded_and_not_swallowed():
    tr = Tracer(clock=LogicalClock())
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("seeded")
    [sp] = tr.spans("boom")
    assert sp.status == "error"
    assert "seeded" in sp.error


def test_null_tracer_is_inert():
    with NULL_TRACER.span("anything", k=1) as sp:
        sp.set("more", 2)
    NULL_TRACER.instant("evt")


def test_span_takes_times_read_by_its_caller():
    tr = Tracer(clock=LogicalClock())
    with tr.span("stage", start=0.25) as sp:
        sp.finish(end=0.75)
    [s] = tr.spans("stage")
    assert (s.start, s.end, s.status) == (0.25, 0.75, "ok")
    assert tr.current() is None


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""

    def __init__(self):
        self.log = []
        outer = self

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                outer.log.append(("enter", self.name))

            def __exit__(self, *exc):
                outer.log.append(("exit", self.name))
        self.cls = Annotation


def test_annotating_tracer_mirrors_each_context_managed_span(monkeypatch):
    import jax.profiler
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann.cls)
    tr = Tracer(annotate="x:")
    with tr.span("session.plan", delta=0.25):
        with tr.span("pallas.lower", component="debayer", ports=2):
            pass
        tr.begin("service.query", component="c").finish()
        tr.instant("session.progress", component="c")
    with pytest.raises(RuntimeError):
        with tr.span("tool.point", component="warp"):
            raise RuntimeError("seeded")
    assert ann.log == [("enter", "x:session.plan"),
                       ("enter", "x:pallas.lower debayer"),
                       ("exit", "x:pallas.lower debayer"),
                       ("exit", "x:session.plan"),
                       ("enter", "x:tool.point warp"),
                       ("exit", "x:tool.point warp")]
    assert [s.name for s in tr.spans()] == [
        "session.plan", "pallas.lower", "service.query", "session.progress",
        "tool.point"]


def test_default_tracer_annotates_nothing(monkeypatch):
    import jax.profiler
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann.cls)
    _, tr = _traced_run(Tracer())
    assert tr.spans() and ann.log == []
    with NULL_TRACER.span("pallas.lower", component="debayer"):
        pass
    assert ann.log == []


def test_exports_are_valid_and_schema_checked():
    _, tr = _traced_run()
    assert validate_jsonl(tr.export_jsonl()) == []
    doc = tr.export_chrome()
    assert doc["displayTimeUnit"] == "ms"
    assert validate_chrome(doc) == []
    # round-trips through JSON
    assert validate_chrome(json.loads(json.dumps(doc))) == []


def test_schema_rejects_bad_documents():
    assert validate_chrome({"traceEvents": "nope"})
    # a complete event missing dur
    bad = {"displayTimeUnit": "ms",
           "traceEvents": [{"name": "x", "cat": "x", "ph": "X", "pid": 1,
                            "tid": 0, "ts": 1.0, "args": {}}]}
    assert validate_chrome(bad)
    # an oracle.point event without an outcome tag
    bad = {"displayTimeUnit": "ms",
           "traceEvents": [{"name": "oracle.point", "cat": "oracle",
                            "ph": "X", "pid": 1, "tid": 0, "ts": 1.0,
                            "dur": 1.0, "args": {}}]}
    assert validate_chrome(bad)
    assert validate_jsonl("not json\n")


# ----------------------------------------------------------------------
# determinism: the CI byte-equality gate in miniature
# ----------------------------------------------------------------------
def test_two_logical_clock_runs_export_identical_bytes():
    _, tr1 = _traced_run()
    _, tr2 = _traced_run()
    assert tr1.export_jsonl() == tr2.export_jsonl()
    assert (json.dumps(tr1.export_chrome(), sort_keys=True)
            == json.dumps(tr2.export_chrome(), sort_keys=True))


# ----------------------------------------------------------------------
# session phases <-> spans
# ----------------------------------------------------------------------
def test_session_spans_mirror_phases():
    s, tr = _traced_run()
    names = {sp.name for sp in tr.spans()}
    assert {"session.characterize", "session.component", "session.plan",
            "session.map", "session.map_point",
            "oracle.point", "tool.point"} <= names
    [char] = tr.spans("session.characterize")
    comps = tr.spans("session.component")
    assert {c.attrs["component"] for c in comps} == {"a", "b"}
    assert all(c.parent_id == char.span_id for c in comps)
    [mapped] = tr.spans("session.map")
    points = tr.spans("session.map_point")
    assert len(points) == len(s.planned)
    assert all(p.parent_id == mapped.span_id for p in points)


def test_progress_instants_match_events():
    specs, tmg, spaces = _system()
    events = []
    tr = Tracer(clock=LogicalClock())
    s = ExplorationSession(tmg, HLSTool(dict(specs)), spaces, delta=0.3,
                           on_event=events.append, tracer=tr)
    s.run()
    instants = tr.spans("session.progress")
    assert len(instants) == len(events)
    assert ([(i.attrs["phase"], i.attrs["label"]) for i in instants]
            == [(e.phase, e.label) for e in events])


# ----------------------------------------------------------------------
# outcome partition <-> ledger reconciliation (Fig. 11)
# ----------------------------------------------------------------------
def test_ledger_outcomes_reconcile_with_totals():
    s, tr = _traced_run()
    counts = s.ledger.outcome_counts()
    assert set(counts) == set(OUTCOMES)
    assert counts["fresh"] + counts["replay"] == s.ledger.total()
    assert counts["cache_hit"] > 0                 # repeats within phases
    traced = tr.outcome_counts("oracle.point")
    assert {o: n for o, n in counts.items() if n} == traced
    assert sum(counts.values()) == len(tr.spans("oracle.point"))


def test_replay_outcome_from_persistent_restore(tmp_path):
    specs, tmg, spaces = _system()

    def run_once(tracer):
        cache = PersistentOracleCache(str(tmp_path / "c"), flush_every=1)
        ledger = OracleLedger(HLSTool(dict(specs)), cache=cache,
                              tracer=tracer)
        s = ExplorationSession(tmg, HLSTool(dict(specs)), spaces, delta=0.3,
                               ledger=ledger)
        s.run()
        return ledger

    cold = run_once(Tracer(clock=LogicalClock()))
    assert cold.outcome_counts()["replay"] == 0

    tr = Tracer(clock=LogicalClock())
    warm = run_once(tr)
    counts = warm.outcome_counts()
    assert counts["fresh"] == 0                    # everything restored
    assert counts["replay"] > 0
    assert counts["replay"] == warm.total()
    assert tr.outcome_counts("oracle.point") == \
        {o: n for o, n in counts.items() if n}


def test_shared_oracle_outcomes_and_inflight_join():
    specs, _, _ = _system()
    tr = Tracer(clock=LogicalClock())
    gate = threading.Event()

    class SlowTool(HLSTool):
        def synthesize(self, component, **kw):
            gate.wait(timeout=30)
            return super().synthesize(component, **kw)

    shared = SharedOracle(SlowTool(dict(specs)),
                          cache=PersistentOracleCache(None), tracer=tr)
    req = InvocationRequest("a", 2, 2)
    results = []
    threads = [threading.Thread(
        target=lambda: results.append(shared.evaluate(req)))
        for _ in range(3)]
    for t in threads:
        t.start()
    while shared.outcome_counts().get("inflight_join", 0) < 2:
        if not any(t.is_alive() for t in threads):
            break
        gate.wait(0.01)
    gate.set()
    for t in threads:
        t.join()
    counts = shared.outcome_counts()
    assert counts["fresh"] == 1
    assert counts["inflight_join"] == 2
    assert shared.evaluate(req) is not None
    assert shared.outcome_counts()["cache_hit"] == 1
    assert tr.outcome_counts("shared.point") == \
        {o: n for o, n in shared.outcome_counts().items() if n}
    assert len({id(r) for r in results}) >= 1 and len(results) == 3


# ----------------------------------------------------------------------
# service-level reconciliation
# ----------------------------------------------------------------------
@pytest.fixture
def _toy_app():
    specs, _, _ = _system()
    app = App(
        name="obs-toy",
        description="runnable toy for the observability battery",
        tmg=lambda: pipeline_tmg(["a", "b"], buffers=2),
        knob_spaces=lambda **_: {n: KnobSpace(clock_ns=1.0, max_ports=4,
                                              max_unrolls=8)
                                 for n in ("a", "b")},
        analytical=lambda: HLSTool(dict(specs)),
    )
    register_app(app)
    try:
        yield app
    finally:
        _APPS.pop("obs-toy", None)


def test_service_stats_embed_metrics_and_partition(_toy_app):
    tr = Tracer(clock=LogicalClock())
    with DSEService(max_pending=4, workers=1, tracer=tr) as svc:
        h1 = svc.submit(DSEQuery(app="obs-toy", backend="analytical",
                                 tenant="t0"))
        h1.result(timeout=120)
        h2 = svc.submit(DSEQuery(app="obs-toy", backend="analytical",
                                 tenant="t1"))
        h2.result(timeout=120)
        stats = svc.stats()

    m = stats["metrics"]
    assert m["service.submitted"] == 2
    assert m["service.done"] == 2
    assert m["service.queue_wait_s"]["count"] == 2
    assert m["service.latency_s"]["count"] == 2

    # every tenant-fresh point reaches the shared oracle exactly once,
    # and the shared fresh count is the real tool-invocation total
    tenant_fresh = sum(h.outcome_counts()["fresh"] for h in (h1, h2))
    pool_outcomes = {}
    for p in stats["pools"].values():
        for o, n in p["outcomes"].items():
            pool_outcomes[o] = pool_outcomes.get(o, 0) + n
    assert sum(pool_outcomes.values()) == tenant_fresh
    assert pool_outcomes["fresh"] == stats["shared_invocations"]
    assert pool_outcomes["cache_hit"] > 0          # t1 reuses t0's work
    # the trace saw the same partition at both levels
    assert tr.outcome_counts("shared.point") == \
        {o: n for o, n in pool_outcomes.items() if n}
    svc_q = tr.spans("service.query")
    assert len(svc_q) == 2
    assert all(sp.attrs.get("status") != "failed" for sp in svc_q)
