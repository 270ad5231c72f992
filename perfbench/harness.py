"""One run of one benchmark cell: set-up, the measured window of DSE
queries, the per-layer readings, and the comparison that decides
``correct``.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  It names a
configuration (``configs/<name>.json`` plus the plain reference module
the file names) and a traffic mix (``traffic/<name>.json``).  Metrics
are read by ``metrics/<metric name>.py``.  Nothing in this module names
a cell, a configuration, a traffic mix or a metric.

The entry the window drives is one measured DSE query,
``build_session(app, "pallas", tool=<record-mode PallasOracle>).run()``
(characterize, plan, map), back to back in one client.  Each query gets
a new oracle and a new, empty recording.  The harness wraps each kernel
point's program on its way from the app's kernel spec to the oracle: it
hands the kernel the seeded inputs, times lowering and compiling,
counts what JAX's compiler reports while compiling, and keeps the
output of the point's last launch for the comparison after the window.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import dse_ref
import numerics as nx
import system_ref


class CellError(Exception):
    """The cell cannot run as stated (a bad name, a configuration that
    no longer matches the program)."""


# ----------------------------------------------------------------------
# the cell, as data
# ----------------------------------------------------------------------
@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    reference: Any                      # the configuration's reference module
    bench_dir: str                      # where traffic/ and metrics/ live


def _module(path: str):
    """The Python file ``path`` as a module of its own."""
    name = "perfbench_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> Cell:
    """Resolve workload ``name`` from ``<root>/BENCHMARK.json``."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"unknown workload {name!r}; known: {sorted(cells)}")
    wl = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[wl["config"]]
    config = _load_json(os.path.join(root, entry["file"]))
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      wl["traffic"] + ".json"))

    def applies(m: Dict[str, Any], e2e_names) -> bool:
        if "workloads" in m:
            return name in m["workloads"]
        return e2e_names is None or m["moves"] in e2e_names

    e2e = [m for m in bench["end_to_end"] if applies(m, None)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if applies(m, names)]
    reference = _module(os.path.join(os.path.dirname(
        os.path.join(root, entry["file"])), config["reference"] + ".py"))
    return Cell(name, int(wl["chips"]), config, traffic, e2e, layer,
                reference, bench_dir)


# ----------------------------------------------------------------------
# what the harness records per kernel point
# ----------------------------------------------------------------------
@dataclass
class Point:
    query: int
    name: str
    ports: int
    unrolls: int
    lower_s: float = 0.0
    compile_s: float = 0.0
    backend_compiles: int = 0           # XLA compile requests while compiling
    cache_hits: int = 0                 # of those, served by the persistent cache
    refused: bool = False
    done_at: float = 0.0                # host clock when the point finished
    output: Any = None                  # the last launch's output


class CompileEvents:
    """Counts JAX's compile and persistent-cache events per thread.  One
    per process: JAX keeps its listeners for the life of the process."""

    def __init__(self):
        import jax
        self._local = threading.local()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _counts(self) -> Dict[str, int]:
        c = getattr(self._local, "c", None)
        if c is None:
            c = self._local.c = {"compiles": 0, "hits": 0}
        return c

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._counts()["hits"] += 1

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._counts()["compiles"] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self._counts())


class _Annotation:
    """A ``jax.profiler.TraceAnnotation`` when tracing, else nothing."""

    def __init__(self, on: bool, name: str):
        self._ann = None
        if on:
            import jax
            self._ann = jax.profiler.TraceAnnotation("pb:" + name)

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class _Compiled:
    def __init__(self, compiled, point: Point, annotate: bool):
        self._compiled, self._point, self._annotate = compiled, point, annotate

    def __call__(self, *args):
        with _Annotation(self._annotate, f"launch {self._point.name}"):
            out = self._compiled(*args)
        self._point.output = out
        self._point.done_at = time.monotonic()
        return out


class _Lowered:
    def __init__(self, lowered, point: Point, rec: "Recorder"):
        self._lowered, self._point, self._rec = lowered, point, rec

    def compile(self, *a, **kw):
        pt, rec = self._point, self._rec
        before = rec.events.snapshot()
        t0 = time.monotonic()
        try:
            with _Annotation(rec.annotate, f"compile {pt.name}"):
                compiled = self._lowered.compile(*a, **kw)
        except BaseException:
            pt.refused = True
            pt.done_at = time.monotonic()
            raise
        finally:
            pt.compile_s = time.monotonic() - t0
            after = rec.events.snapshot()
            pt.backend_compiles = after["compiles"] - before["compiles"]
            pt.cache_hits = after["hits"] - before["hits"]
        return _Compiled(compiled, pt, rec.annotate)


class _Program:
    def __init__(self, program, point: Point, rec: "Recorder"):
        self._program, self._point, self._rec = program, point, rec

    def lower(self, *args, **kw):
        pt = self._point
        t0 = time.monotonic()
        try:
            with _Annotation(self._rec.annotate, f"lower {pt.name}"):
                lowered = self._program.lower(*args, **kw)
        except BaseException:
            pt.refused = True
            pt.done_at = time.monotonic()
            raise
        finally:
            pt.lower_s = time.monotonic() - t0
        return _Lowered(lowered, pt, self._rec)


def _seeded_like(full, like):
    """The seeded input ``full``, which has to have the shape and type
    of the kernel's own input ``like``."""
    if tuple(like.shape) != tuple(full.shape) or like.dtype != full.dtype:
        raise CellError(f"kernel input {like.dtype}{tuple(like.shape)} "
                        f"differs from the seeded "
                        f"{full.dtype}{tuple(full.shape)}")
    return full


class Recorder:
    """Wraps the app's kernel specs so every kernel point the oracle
    measures runs on the seeded inputs and leaves a :class:`Point`."""

    def __init__(self, inputs: Dict[str, tuple], events: CompileEvents,
                 annotate: bool, on_build: Optional[Callable[[], None]] = None):
        self.inputs = inputs
        self.events = events
        self.annotate = annotate
        self.on_build = on_build
        self.points: List[Point] = []

    def wrap(self, specs: Dict[str, Any], query: int) -> Dict[str, Any]:
        import dataclasses
        out = {}
        for name, spec in specs.items():
            if name not in self.inputs:
                raise CellError(f"kernel {name!r} has no seeded inputs in "
                                f"the configuration's reference")
            out[name] = dataclasses.replace(
                spec, build=self._build(spec.build, name, query))
        return out

    def _build(self, build, name: str, query: int):
        def wrapped(ports: int, unrolls: int, interpret: bool):
            if self.on_build is not None:
                self.on_build()
            program, args = build(ports, unrolls, interpret)
            args = tuple(_seeded_like(full, a)
                         for full, a in zip(self.inputs[name], args))
            pt = Point(query, name, ports, unrolls)
            self.points.append(pt)
            return _Program(program, pt, self), args
        return wrapped


class AnnotatingTracer:
    """The program's WallClock tracer, with each context-managed span
    also written into the profiler's trace as ``pb:<span> <component>``,
    so idle gaps on the device can be named by the session phase or
    oracle point the host was in."""

    def __init__(self):
        from repro.core.obs import Tracer, WallClock
        self._tracer = Tracer(WallClock())

    def span(self, name, **kw):
        label = name + (f" {kw['component']}" if "component" in kw else "")
        return _AnnotatedSpan(self._tracer.span(name, **kw), label)

    def __getattr__(self, attr):
        return getattr(self._tracer, attr)


class _AnnotatedSpan:
    def __init__(self, span, label: str):
        self._span, self._ann = span, _Annotation(True, label)

    def __enter__(self):
        self._ann.__enter__()
        return self._span.__enter__()

    def __exit__(self, *exc):
        try:
            return self._span.__exit__(*exc)
        finally:
            self._ann.__exit__(*exc)

    def __getattr__(self, attr):
        return getattr(self._span, attr)


# ----------------------------------------------------------------------
# the run
# ----------------------------------------------------------------------
@dataclass
class Query:
    index: int
    oracle: Any
    session: Any
    started: float
    ended: float = 0.0
    result: Any = None


@dataclass
class Run:
    """Everything a metric reader may read."""

    cell: Cell
    seconds: float
    setup_s: float = 0.0
    t_start: float = 0.0
    t_stop: float = 0.0
    points: List[Point] = field(default_factory=list)
    queries: List[Query] = field(default_factory=list)
    tracer: Any = None
    trace: Optional[Dict[str, Any]] = None

    def in_window(self) -> List[Point]:
        return [p for p in self.points
                if p.done_at and self.t_start <= p.done_at <= self.t_stop]


class CompileCache:
    """Where JAX keeps compiled programs during a run.  ``checkout``
    keeps the persistent cache at the checkout's fixed path for the
    whole run; ``fresh_per_query`` hands every query (and the warm-up) a
    new, empty directory, so no query reuses another's compiles."""

    def __init__(self, mode: str, checkout_dir: str, scratch: str):
        if mode not in ("checkout", "fresh_per_query"):
            raise CellError(f"unknown compile_cache {mode!r}")
        self.mode, self.checkout_dir, self.scratch = mode, checkout_dir, scratch
        self._n = 0

    def _use(self, path: str) -> None:
        import jax
        from jax.experimental.compilation_cache import compilation_cache
        jax.config.update("jax_compilation_cache_dir", path)
        compilation_cache.reset_cache()

    def harness(self) -> None:
        """The fixed directory, for the harness's own programs."""
        self._use(self.checkout_dir)

    def query(self) -> None:
        if self.mode == "fresh_per_query":
            self._n += 1
            self._use(tempfile.mkdtemp(prefix=f"cache{self._n}-",
                                       dir=self.scratch))


def _oracle(cell: Cell, rec: Recorder, query: int, scratch: str,
            interpret: bool):
    from repro.core.pallas_oracle import PallasOracle, open_recording
    from repro.core.registry import get_app
    cfg = cell.config
    app = get_app(cfg["app"])
    kind = "interpret" if interpret else None
    if kind is None:
        from repro.core.pallas_oracle import live_device_kind
        kind = live_device_kind()
    measurements = open_recording(
        os.path.join(scratch, f"query{query}.json"), mode="record",
        tile=app.native_tile, device_kind=kind)
    specs = rec.wrap(app.kernel_specs(app.native_tile), query)
    return PallasOracle(specs, mode="record", measurements=measurements,
                        fallback=app.analytical(), interpret=interpret,
                        device_kind=kind, native_tile=app.native_tile,
                        reps=int(cfg["reps"]))


def check_config(cell: Cell) -> None:
    """Fail when the program no longer runs the configuration as the
    file states it: knob bounds, fixed latencies, the timed marked graph,
    the kernels and their shapes, the tile."""
    from repro.core.registry import get_app
    cfg = cell.config
    app = get_app(cfg["app"])
    problems = []
    if int(cfg["tile"]) != app.native_tile:
        problems.append(f"tile {cfg['tile']} != app tile {app.native_tile}")
    spaces = {n: {"clock_ns": s.clock_ns, "min_ports": s.min_ports,
                  "max_ports": s.max_ports, "max_unrolls": s.max_unrolls}
              for n, s in app.knob_spaces().items()}
    if spaces != cfg["knobs"]:
        problems.append(f"knob spaces {spaces} != {cfg['knobs']}")
    if dict(app.fixed) != cfg["fixed"]:
        problems.append(f"fixed latencies {app.fixed} != {cfg['fixed']}")
    tmg = app.tmg()
    if ([t.name for t in tmg.transitions] != cfg["tmg"]["transitions"]
            or [[p.src, p.dst, p.tokens] for p in tmg.places]
            != cfg["tmg"]["places"]):
        problems.append("timed marked graph differs from the configuration")
    specs = app.kernel_specs(app.native_tile)
    shapes = {n: list(s.shape) for n, s in specs.items()}
    want = {n: k["shape"] for n, k in cfg["kernels"].items()}
    if shapes != want:
        problems.append(f"kernel shapes {shapes} != {want}")
    if problems:
        raise CellError("configuration does not match the program: "
                        + "; ".join(problems))


def run_cell(cell: Cell, *, root: str, seed: int, seconds: float,
             trace: bool, t0: float, interpret: bool = False,
             log=print) -> Dict[str, Any]:
    """Set up, measure ``seconds`` of back-to-back queries, finish the
    query in flight, read the metrics, compare with the reference, and
    return the result line as a dict."""
    import jax
    from repro.core.registry import build_session

    cfg, traffic = cell.config, cell.traffic
    scratch = tempfile.mkdtemp(prefix="perfbench-")
    try:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()                # the floor for caching: 0 s
        cache = CompileCache(traffic["compile_cache"],
                             os.path.join(root, ".jax_cache"), scratch)
        cache.harness()
        check_config(cell)
        events = CompileEvents()
        inputs = jax.block_until_ready(cell.reference.make_inputs(cfg, seed))
        run = Run(cell, float(seconds))
        closed = threading.Event()
        profiler_dir = os.path.join(scratch, "profile")

        def on_build():
            # the window closes at the first kernel point begun after
            # its end; with tracing on, the profiler stops there
            if run.t_start and not closed.is_set() \
                    and time.monotonic() >= run.t_start + run.seconds:
                close()

        def close():
            closed.set()
            if trace:
                with _Annotation(True, "mark stop"):
                    pass
                run.t_stop = time.monotonic()
                jax.profiler.stop_trace()

        rec = Recorder(inputs, events, annotate=trace, on_build=on_build)

        def one_query(index: int, tracer=None) -> Query:
            cache.query()
            oracle = _oracle(cell, rec, index, scratch, interpret)
            session = build_session(cfg["app"], "pallas", tool=oracle,
                                    delta=float(cfg["delta"]), tracer=tracer)
            q = Query(index, oracle, session, time.monotonic())
            q.result = session.run()
            q.ended = time.monotonic()
            return q

        # ---- set-up: the traffic's set-up queries, or, where it has
        # none, one warm-up point of each kernel; a replay query
        from repro.core.registry import get_app
        app = get_app(cfg["app"])
        setup_queries = int(traffic["setup_queries"])
        if not setup_queries:
            specs = rec.wrap(app.kernel_specs(app.native_tile), -1)
            for name, spec in specs.items():
                cache.query()
                program, args = spec.build(*cfg["kernels"][name]["warmup"],
                                           interpret)
                compiled = program.lower(*args).compile()
                jax.block_until_ready(compiled(*args))
        # the host's DSE path (session code, the LP solver's import and
        # first solve) warmed by one replay of the app's committed
        # recording: no compile, no device work
        build_session(cfg["app"], "pallas", delta=float(cfg["delta"])).run()
        for i in range(setup_queries):
            one_query(-1 - i)
        rec.points.clear()
        cache.harness()

        # ---- the window
        tracer = AnnotatingTracer() if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(profiler_dir, profiler_options=opts)
            with _Annotation(True, "mark start"):
                pass
        run.setup_s = time.monotonic() - t0
        run.t_start = time.monotonic()
        index = 0
        while not closed.is_set():
            run.queries.append(one_query(index, tracer))
            index += 1
            if (not closed.is_set()
                    and time.monotonic() >= run.t_start + run.seconds):
                close()
        if not trace:
            run.t_stop = run.t_start + run.seconds
        run.points = list(rec.points)
        run.tracer = tracer
        cache.harness()

        # ---- device memory, then the trace
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
        if trace:
            import trace_reduce
            run.trace = trace_reduce.reduce_dir(profiler_dir)
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]

        metrics = {}
        for m in (cell.per_layer if trace else cell.end_to_end):
            value = read_metric(cell.bench_dir, m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        inw = run.in_window()
        checks = compare(cell, run, inputs)
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        out = {"correct": correct, "attempted": len(inw),
               "failed": sum(p.refused for p in inw), "metrics": metrics,
               "device": device}
        if trace:
            out["breakdown"] = {"device_ops": run.trace["device_ops"],
                                "idle_gaps": run.trace["idle_gaps"]}
        out["checks"] = checks
        log(f"queries={len(run.queries)} points_in_window={len(inw)} "
            f"points_total={len(run.points)} setup_s={run.setup_s!r}")
        slow = sorted(run.points, key=lambda p: -(p.lower_s + p.compile_s))[:3]
        log("slowest points: " + ", ".join(
            f"{p.name} p{p.ports} u{p.unrolls} q{p.query} lower "
            f"{p.lower_s:.3f} s compile {p.compile_s:.3f} s" for p in slow))
        for q in run.queries:
            secs = sorted(p.lower_s + p.compile_s for p in run.points
                          if p.query == q.index and not p.refused)
            if secs:
                log(f"query {q.index}: {q.ended - q.started!r} s, "
                    f"{len(secs)} points timed, lower+compile s per point "
                    f"min {secs[0]!r} median {secs[len(secs) // 2]!r} "
                    f"max {secs[-1]!r}")
        return out
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


def read_metric(bench_dir: str, name: str, run: Run) -> Optional[float]:
    """Metric ``name`` from ``metrics/<name>.py``; None when the run has
    nothing for it to read."""
    return _module(os.path.join(bench_dir, "metrics", name + ".py")).read(run)


# ----------------------------------------------------------------------
# correct
# ----------------------------------------------------------------------
def reference_outputs(cell: Cell, inputs, prec: str) -> Dict[str, tuple]:
    import numpy as np
    host = {n: tuple(np.asarray(a) for a in args) for n, args in inputs.items()}
    return {n: tuple(cell.reference.reference(n, prec, *host[n]))
            for n in cell.config["kernels"]}


def kernel_errors(cell: Cell, outputs_by_kernel, refs) -> Dict[str, float]:
    """Per kernel, the worst error (max-norm, relative) of any of its
    outputs over every point given."""
    import numpy as np
    errs: Dict[str, float] = {}
    for name in cell.config["kernels"]:
        for out in outputs_by_kernel.get(name, []):
            out = out if isinstance(out, (tuple, list)) else (out,)
            worst = max(nx.rel_err(np.asarray(o), r)
                        for o, r in zip(out, refs[name]))
            if len(out) != len(refs[name]):
                worst = float("inf")
            errs[name] = max(errs.get(name, 0.0), worst)
    return errs


def compare(cell: Cell, run: Run, inputs) -> Dict[str, Dict[str, float]]:
    """Each number compared, with its limit.  Every point measured in
    the run (the queries of the window, and the one in flight at its
    close, run to its end) is compared; so is each query's front and
    ledger."""
    limits = dict(cell.config["limits"])
    limits.update(cell.traffic.get("limits", {}))
    refs = reference_outputs(cell, inputs, "exact")
    by_kernel: Dict[str, list] = {}
    missing = 0
    for p in run.points:
        if p.refused:
            continue
        if p.output is None:
            missing += 1
            continue
        by_kernel.setdefault(p.name, []).append(p.output)
    errs = kernel_errors(cell, by_kernel, refs)
    checks = {f"err.{n}": {"value": errs.get(n, float("inf")),
                           "limit": limits[f"err.{n}"]}
              for n in cell.config["kernels"]}
    gap, plan_gap, bad = 0.0, 0.0, missing
    for q in run.queries:
        walls = {}
        for store in q.oracle.measurements.stores():
            walls.update(store.entries)
        g, b = system_ref.check_query(cell.config, cell.reference.area_bytes,
                                      q.result, q.session.ledger, walls)
        pg, pb = dse_ref.check_query(cell.config, q.result, q.session.ledger)
        gap, plan_gap, bad = max(gap, g), max(plan_gap, pg), bad + b + pb
    if not run.queries:
        bad += 1
    checks["system_gap"] = {"value": gap, "limit": limits["system_gap"]}
    checks["plan_gap"] = {"value": plan_gap, "limit": limits["plan_gap"]}
    checks["mismatches"] = {"value": bad, "limit": limits["mismatches"]}
    if "reused_compiles" in limits:
        reused = sum(1 for p in run.points if not p.refused
                     and (p.backend_compiles == 0
                          or p.cache_hits >= p.backend_compiles))
        checks["reused_compiles"] = {"value": reused,
                                     "limit": limits["reused_compiles"]}
    return checks
