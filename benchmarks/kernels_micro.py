"""Kernel micro-benchmarks — one cell per registered app x backend.

Both cells drive the app's registered ``parity_cases`` (the registry
is the work list: a new app's kernels join by registering):

  * ``analytical`` — the same cases timed down their XLA reference
    path (``use_pallas=False``).  CPU microseconds, reported only to
    catch regressions in the jnp fallback kernels.
  * ``pallas`` — every kernel runs through its Pallas path and is
    checked against its jnp oracle: compiled on a TPU, in interpret
    mode on a CPU (interpret-mode walls are structural, not TPU
    performance).  Every row names the platform it ran on.  ``--smoke``
    shrinks the tile and exits non-zero on any parity failure — the CI
    gate that the measured backend's kernels still compute the right
    thing.

Standalone (all apps at once):

    PYTHONPATH=src python benchmarks/kernels_micro.py --smoke --backend pallas
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

# every registered app joins both cells through its parity cases: the
# pallas cell checks + times the kernels (compiled on a TPU, interpreted
# on a CPU), the analytical cell times the same cases down their XLA
# reference path
SCENARIOS = {"apps": "*", "backends": ("analytical", "pallas")}


def cell_skip_reason(app, backend, variant):
    """Bench-specific capability: both kernels cells drive the app's
    registered parity cases (interpret mode needs no recordings, so the
    registry's recording-based pallas check would be too strict)."""
    if app.parity_cases is None:
        return (f"app {app.name!r} registers no parity cases "
                f"(nothing for the kernels bench to drive)")
    return None


def _time(fn, *args, reps=5, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _max_err(a, b):
    fa = jnp.asarray(a, jnp.float32)
    fb = jnp.asarray(b, jnp.float32)
    denom = float(jnp.abs(fb).max()) or 1.0
    return float(jnp.abs(fa - fb).max()) / max(1.0, denom)


def _registry_parity_cases(tile: int, app: str | None = None):
    """(name, knobbed_fn, oracle_fn, args) from registered apps that
    expose parity cases (all of them, or just ``app``) — the registry
    is the work list, so a new app's kernels join the CI gate by
    registering, not by editing this file."""
    from repro.core.registry import list_apps
    cases = []
    for a in list_apps():
        if app is not None and a.name != app:
            continue
        if a.parity_cases is not None:
            cases += list(a.parity_cases(tile))
    return cases


def run_pallas(report, *, app: str | None = None, tile: int = 128,
               ports: int = 4, unrolls: int = 8,
               reps: int = 3, tol: float = 1e-4) -> int:
    """Drive the registered Pallas kernels (every app's, or one app's
    cell) vs their jnp oracles — compiled on a TPU, interpreted on a
    CPU.  Returns the number of parity failures.  The oracles' matmuls
    run at full f32 precision, as the kernels' do, so one tolerance
    holds on both platforms."""
    from repro.core.pallas_oracle import platform_interpret
    interpret = platform_interpret()
    dev = jax.devices()[0]
    platform = "interpret" if interpret else dev.platform
    lines = [f"# Pallas kernels ({app or 'all registered apps'}), "
             f"{'interpret mode' if interpret else 'compiled'} on "
             f"{dev.platform} ({dev.device_kind}), "
             f"tile={tile}, ports={ports}, unrolls={unrolls}",
             "kernel,platform,us_per_call,max_rel_err"]
    failures = 0
    for name, fn, oracle, args in _registry_parity_cases(tile, app):
        got = fn(*args, ports=ports, unrolls=unrolls, use_pallas=True,
                 interpret=interpret)
        want = oracle(*args)
        errs = [_max_err(g, w) for g, w in
                zip(got if isinstance(got, tuple) else (got,),
                    want if isinstance(want, tuple) else (want,))]
        err = max(errs)
        if err > tol:
            failures += 1
        us = _time(fn, *args, reps=reps, ports=ports, unrolls=unrolls,
                   use_pallas=True, interpret=interpret)
        lines.append(f"{name},{platform},{us:.0f},{err:.2e}")
        report.csv(f"{name}_pallas", us,
                   f"{platform}_parity={'OK' if err <= tol else 'FAIL'}"
                   f"_{err:.1e}")
    report.write("kernels_micro_pallas", lines)
    return failures


def run_reference(report, *, app: str, tile: int = 128, ports: int = 2,
                  unrolls: int = 4, reps: int = 5) -> None:
    """The analytical cell: every parity case the app registers, timed
    down its XLA reference path (``use_pallas=False``) — the regression
    canary for the jnp fallback kernels, registry-driven like the
    interpret-mode cell."""
    lines = [f"# {app} kernels, XLA reference path (use_pallas=False), "
             f"tile={tile}",
             "kernel,us_per_call_ref"]
    for name, fn, oracle, args in _registry_parity_cases(tile, app):
        us = _time(fn, *args, reps=reps, ports=ports, unrolls=unrolls,
                   use_pallas=False, interpret=False)
        lines.append(f"{name},{us:.0f}")
        report.csv(f"{name}_ref", us, "xla_reference")
    report.write(f"kernels_micro_{app}", lines)


def run(report, cell) -> None:
    if cell.backend == "pallas":
        failures = run_pallas(report, app=cell.app)
        if failures:
            raise RuntimeError(f"{failures} {cell.app} Pallas kernel(s) "
                               f"diverged from their jnp oracle")
        return
    run_reference(report, app=cell.app)


if __name__ == "__main__":
    import argparse
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=["analytical", "pallas"],
                    default="analytical")
    ap.add_argument("--smoke", action="store_true",
                    help="small tile, 1 rep, non-zero exit on any parity "
                         "failure (CI gate)")
    args = ap.parse_args()

    class _Report:
        def write(self, name, lines):
            print("\n".join(lines))

        def csv(self, name, us, derived):
            print(f"{name},{us:.1f},{derived}")

    if args.backend == "pallas":
        tile, reps = (32, 1) if args.smoke else (128, 3)
        failures = run_pallas(_Report(), tile=tile, ports=2, unrolls=4,
                              reps=reps)
        if args.smoke and failures:
            print(f"kernels-micro-smoke: FAIL — {failures} kernel(s) "
                  f"diverged from the jnp oracle", file=sys.stderr)
            raise SystemExit(1)
        raise SystemExit(0)
    from repro.core.registry import list_apps
    for app in list_apps():
        if app.parity_cases is not None:
            run_reference(_Report(), app=app.name)
