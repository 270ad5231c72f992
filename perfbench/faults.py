#!/usr/bin/env python3
"""Faults planted in the timed path, to show that ``correct`` catches them.

    python3 perfbench/faults.py --workload <name> --seeds 1 2 3 [--seconds 1]

On a TPU, runs the cell (a short window: the query in flight runs to its
end) once per fault and seed, with the fault planted underneath, and
prints one JSON line per run: the numbers compared, beside their limits.
The benchmark's own runs never plant a fault.  ``tests/test_correct.py``
plants the same faults on a CPU.

Faults, each a context manager that patches the program for its
duration:

  * ``kernel_output`` -- one element of a kernel's output altered where
    the kernel produces it;
  * ``half_output``   -- the second half of a kernel's first output
    left out (zeros);
  * ``oracle_answer`` -- the record-mode oracle's latency of every
    measured point off by 0.1%;
  * ``session_answer``-- the throughput of the first mapped point of a
    record-mode session's result off by 0.1%;
  * ``ledger_answer`` -- one invocation too many on a record-mode
    session's ledger count;
  * ``reused_compiles`` -- every query compiles into one fixed cache, so
    later queries load earlier ones' programs (cold traffic only);
  * ``walk_step``     -- Algorithm 1's walk skips the top ports rung of
    the first component;
  * ``plan_step``     -- the LP sweep leaves out its second throughput
    target;
  * ``map_choice``    -- the mapping of the first component moves up
    one ports rung from the region it picked, to that rung's slowest
    point.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@contextlib.contextmanager
def _kernel_outputs(how):
    """Pass every kernel program's output through ``how`` where the
    compiled program returns it, underneath the harness's recorder."""
    import harness
    orig = harness.Recorder.wrap

    def wrap(self, specs, query):
        def broken(build):
            def b(ports, unrolls, interpret):
                program, args = build(ports, unrolls, interpret)
                return _Altered(program, how), args
            return b
        return orig(self, {n: dataclasses.replace(s, build=broken(s.build))
                           for n, s in specs.items()}, query)
    harness.Recorder.wrap = wrap
    try:
        yield
    finally:
        harness.Recorder.wrap = orig


class _Altered:
    """A kernel program whose compiled output passes through ``how``."""

    def __init__(self, program, how):
        self._program, self._how = program, how

    def lower(self, *a, **kw):
        return _AlteredLowered(self._program.lower(*a, **kw), self._how)


class _AlteredLowered:
    def __init__(self, lowered, how):
        self._lowered, self._how = lowered, how

    def compile(self, *a, **kw):
        compiled, how = self._lowered.compile(*a, **kw), self._how
        return lambda *args: how(compiled(*args))


def _first(outputs, fn):
    if isinstance(outputs, (tuple, list)):
        return type(outputs)([fn(outputs[0]), *outputs[1:]])
    return fn(outputs)


def _alter_one(x):
    idx = tuple(min(1, n - 1) for n in x.shape)
    if x.dtype == bool:
        return x.at[idx].set(~x[idx])
    return x.at[idx].add(0.01 * (1 + abs(x[idx])))


def _drop_half(x):
    return x.at[x.shape[0] // 2:].set(0) if x.ndim == 2 or x.shape[0] > 1 \
        else x.at[:, x.shape[1] // 2:].set(0)


@contextlib.contextmanager
def kernel_output():
    with _kernel_outputs(lambda o: _first(o, _alter_one)):
        yield


@contextlib.contextmanager
def half_output():
    with _kernel_outputs(lambda o: _first(o, _drop_half)):
        yield


@contextlib.contextmanager
def oracle_answer():
    from repro.core.pallas_oracle import PallasOracle
    orig = PallasOracle.synthesize

    def off(self, *a, **kw):
        s = orig(self, *a, **kw)
        if self.mode == "record" and s.feasible \
                and "wall_s" in (s.detail or {}):
            s = dataclasses.replace(s, lam=s.lam * 1.001)
        return s
    PallasOracle.synthesize = off
    try:
        yield
    finally:
        PallasOracle.synthesize = orig


@contextlib.contextmanager
def session_answer():
    from repro.core.session import ExplorationSession
    orig = ExplorationSession.result

    def off(self):
        res = orig(self)
        if getattr(self.ledger.tool, "mode", "") == "record" and res.mapped:
            res.mapped[0] = dataclasses.replace(
                res.mapped[0], theta_actual=res.mapped[0].theta_actual * 1.001)
        return res
    ExplorationSession.result = off
    try:
        yield
    finally:
        ExplorationSession.result = orig


@contextlib.contextmanager
def ledger_answer():
    from repro.core.session import ExplorationSession
    orig = ExplorationSession.result

    def off(self):
        if getattr(self.ledger.tool, "mode", "") == "record" \
                and self.ledger.invocations:
            name = next(iter(self.ledger.invocations))
            self.ledger.invocations[name] += 1
        return orig(self)
    ExplorationSession.result = off
    try:
        yield
    finally:
        ExplorationSession.result = orig


@contextlib.contextmanager
def reused_compiles():
    import harness
    orig = harness.CompileCache.query
    harness.CompileCache.query = lambda self: None
    try:
        yield
    finally:
        harness.CompileCache.query = orig


@contextlib.contextmanager
def _patched(module, name, make):
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def _first_name(ledger):
    """The first component the record-mode oracle measures."""
    return next(iter(ledger.tool.components))


@contextlib.contextmanager
def walk_step():
    from repro.core import session

    def make(orig):
        def characterize(tool, component, space, **kw):
            if component == _first_name(tool):
                space = dataclasses.replace(space,
                                            max_ports=space.max_ports // 2)
            return orig(tool, component, space, **kw)
        return characterize
    with _patched(session, "characterize_component", make):
        yield


@contextlib.contextmanager
def plan_step():
    from repro.core import session

    def make(orig):
        def sweep(*a, **kw):
            out = orig(*a, **kw)
            return out[:1] + out[2:]
        return sweep
    with _patched(session, "sweep", make):
        yield


@contextlib.contextmanager
def map_choice():
    from repro.core import session

    def make(orig):
        def map_target(tool, component, regions, lam_target, **kw):
            out = orig(tool, component, regions, lam_target, **kw)
            up = [r for r in regions if out.region is not None
                  and r.ports > out.region.ports]
            if component != _first_name(tool) or not up:
                return out
            r = min(up, key=lambda r: r.ports)
            s = tool.synthesize(component, unrolls=r.mu_min, ports=r.ports,
                                tile=r.tile)
            return dataclasses.replace(out, synthesis=s, region=r)
        return map_target
    with _patched(session, "map_target", make):
        yield


FAULTS = {"kernel_output": kernel_output, "half_output": half_output,
          "oracle_answer": oracle_answer, "session_answer": session_answer,
          "ledger_answer": ledger_answer, "reused_compiles": reused_compiles,
          "walk_step": walk_step, "plan_step": plan_step,
          "map_choice": map_choice}


def run_with(fault: str, cell, *, root: str, seed: int, seconds: float,
             interpret: bool = False):
    """One run of ``cell`` with ``fault`` planted; the result dict."""
    import harness
    with FAULTS[fault]():
        return harness.run_cell(cell, root=root, seed=seed, seconds=seconds,
                                trace=False, t0=time.monotonic(),
                                interpret=interpret, log=lambda m: None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--faults", nargs="+", default=sorted(FAULTS))
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    cell = harness.load_cell(ROOT, args.workload)
    for fault in args.faults:
        if fault == "reused_compiles" and \
                cell.traffic["compile_cache"] != "fresh_per_query":
            continue
        for seed in args.seeds:
            if fault == "reused_compiles":
                # a first run fills the one cache the second run reads
                run_with(fault, cell, root=ROOT, seed=seed,
                         seconds=args.seconds)
            out = run_with(fault, cell, root=ROOT, seed=seed,
                           seconds=args.seconds)
            print(json.dumps({"workload": cell.name, "fault": fault,
                              "seed": seed, "correct": out["correct"],
                              "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
