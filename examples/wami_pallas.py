"""COSMOS over the *measured* backend: the WAMI DSE driven by a
PallasOracle that prices each (component, knob) point by compiling and
timing the stage's knob-parameterized Pallas kernel (interpret mode on
CPU, the compiled kernel on TPU).

Default run replays the recording checked in under
``artifacts/measurements/`` — fully deterministic, no TPU needed — then
fits the analytical HLSTool's latency constants to the measured points
and reports both backends' Pareto views side by side.

    PYTHONPATH=src python examples/wami_pallas.py            # replay
    PYTHONPATH=src python examples/wami_pallas.py --record   # re-measure

``--record`` times the interpreter on a CPU host and the compiled
kernels on a TPU host; the recording's ``device_kind`` says which, and
each device kind writes its own file under ``artifacts/measurements/``.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--record", action="store_true",
                    help="re-measure every point this drive touches and "
                         "rewrite this platform's measurement recording")
    ap.add_argument("--tile", type=int, default=None,
                    help="PLM tile edge (default: the WAMI 128)")
    ap.add_argument("--delta", type=float, default=0.25)
    args = ap.parse_args()

    from repro.apps.wami import wami_hls_tool
    from repro.apps.wami.components import TILE
    from repro.apps.wami.pallas import (wami_pallas_oracle,
                                        wami_pallas_session)
    from repro.core import ExplorationSession, calibrate_to_records
    from repro.core.calibrate import CalibratedTool
    from repro.core.pallas_oracle import platform_interpret
    from repro.launch.compile_cache import enable_compile_cache

    tile = args.tile or TILE
    mode = "record" if args.record else "replay"
    if args.record:
        enable_compile_cache()
        oracle = wami_pallas_oracle(mode, tile=tile,
                                    interpret=platform_interpret())
    else:
        oracle = wami_pallas_oracle(mode, tile=tile)
    t0 = time.time()
    session = wami_pallas_session(args.delta, oracle=oracle,
                                  workers=1 if args.record else 8)
    res = session.run()
    saved = oracle.flush()
    wall = time.time() - t0

    print(f"[pallas] {mode} drive: {res.total_invocations} oracle "
          f"invocations, {len(res.mapped)} mapped points, {wall:.1f}s")
    if saved:
        print(f"[pallas] recording saved: {saved} "
              f"({len(oracle.store)} measured points, device_kind="
              f"{oracle.device_kind!r})")
    by_phase = session.ledger.records_by_phase()
    print("[pallas] invocations by phase: "
          + ", ".join(f"{k}={v}" for k, v in sorted(by_phase.items())))
    print(f"[pallas] Pareto front (theta in [{res.theta_min:.2f}, "
          f"{res.theta_max:.2f}] frames/s; cost = VMEM bytes + fallback "
          f"mm^2):")
    for pt in res.pareto():
        print(f"   theta {pt.perf:8.2f} fps   cost {pt.cost:12.1f}")

    # ---- calibrate the analytical backend to the measured points -------
    measured_comps = set(oracle.components)
    hls = wami_hls_tool()
    fit = calibrate_to_records(
        hls, [r for r in session.ledger.records
              if r.component in measured_comps])
    print("[calibrate] per-component latency scale (measured / analytical):")
    for name in sorted(fit.scales):
        print(f"   {name:14s} x{fit.scales[name]:10.3g}   "
              f"({fit.points[name]} pts, residual spread "
              f"x{fit.lam_spread[name]:.2f})")

    cal_session = ExplorationSession(
        session.tmg, CalibratedTool(hls, fit), session.spaces,
        delta=args.delta, fixed=session.fixed, workers=8)
    cal = cal_session.run()
    print(f"[calibrate] theta range, calibrated analytical: "
          f"[{cal.theta_min:.2f}, {cal.theta_max:.2f}] fps "
          f"vs measured: [{res.theta_min:.2f}, {res.theta_max:.2f}] fps")


if __name__ == "__main__":
    main()
