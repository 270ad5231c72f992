#!/usr/bin/env python3
"""Smoke run of the measured DSE path on one TPU chip.

    python3 chip_smoke.py

One process owns the chip and runs four phases, each printing its own
lines:

  (a) require a TPU: print the platform, device kind and device count;
      anything but a TPU exits non-zero;
  (b) kernel parity: every registered app's parity cases compiled for
      the chip (no interpret mode) at the measured geometry — WAMI at
      its native 128 tile, fleet at FLASH_S=128 / SSD_S=256 — against
      the jnp oracles, at one (ports=1, unrolls=8) point and one
      (ports=4, unrolls=2) point;
  (c) one WAMI DSE query end to end (characterize, plan, map) through
      ``build_session("wami", "pallas")`` on a record-mode
      ``PallasOracle`` that compiles and times every kernel point on the
      chip, writing the recording under ``artifacts/chip_smoke/``;
  (d) replay that recording through a fresh session: the front, the
      mapped points and the ledger counts must equal (c)'s exactly.

A failed phase exits non-zero.  Only when all pass does the last line
of standard output read
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "artifacts", "chip_smoke")
TILE = 128
DELTA = 0.25
PARITY_KNOBS = ((1, 8), (4, 2))          # (ports, unrolls)


class SmokeFailure(Exception):
    pass


class _Rows:
    """Report sink for the kernels bench: keeps its rows."""

    def __init__(self):
        self.lines = []

    def write(self, name, lines):
        self.lines = list(lines)

    def csv(self, name, us, derived):
        pass


def require_tpu():
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"[a] platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX found platform {dev.platform!r} "
                           f"({dev.device_kind!r})")
    return dev, len(devices)


def kernel_parity():
    from benchmarks import kernels_micro
    from repro.core.registry import list_apps
    failures = 0
    for app in list_apps():
        if app.parity_cases is None:
            continue
        tile = app.native_tile or TILE
        for ports, unrolls in PARITY_KNOBS:
            rows = _Rows()
            # compiled for the chip: the bench runs interpret mode only
            # on a CPU, and phase (a) made sure this is a TPU
            failures += kernels_micro.run_pallas(
                rows, app=app.name, tile=tile, ports=ports, unrolls=unrolls,
                reps=1)
            for line in rows.lines[2:]:
                print(f"[b] {app.name} p={ports} u={unrolls}: {line}",
                      flush=True)
    if failures:
        raise SmokeFailure(f"{failures} kernel parity check(s) failed")


def _fingerprint(res, session) -> str:
    return json.dumps({
        "front": [[p.perf, p.cost] for p in res.pareto()],
        "mapped": repr(res.mapped),
        "invocations": session.ledger.invocations,
        "failed": session.ledger.failed,
    }, sort_keys=True)


def dse_query(device_kind: str):
    from repro.apps.wami.pallas import (default_measurement_path,
                                        wami_pallas_oracle)
    from repro.core.registry import build_session
    shutil.rmtree(OUT, ignore_errors=True)
    path = os.path.join(OUT, os.path.basename(
        default_measurement_path(TILE, device_kind)))
    oracle = wami_pallas_oracle("record", tile=TILE, store_path=path)
    if oracle.device_kind != device_kind:
        raise SmokeFailure(f"oracle records as {oracle.device_kind!r}, "
                           f"not the chip's {device_kind!r}")
    session = build_session("wami", "pallas", tool=oracle, delta=DELTA)
    t0 = time.perf_counter()
    res = session.run()
    wall = time.perf_counter() - t0
    oracle.flush()
    st = oracle.stats
    print(f"[c] invocations={res.total_invocations} "
          f"kernel_points_timed={int(st['timed'])} "
          f"compiler_refused={int(st['refused'])} "
          f"kernel_points_fallback_priced={int(st['fallback'])}",
          flush=True)
    front = res.pareto()
    print(f"[c] front_points={len(front)} mapped={len(res.mapped)} "
          f"theta_min={res.theta_min!r} theta_max={res.theta_max!r}",
          flush=True)
    print(f"[c] session_wall_s={wall!r} compile_s={st['compile_s']!r} "
          f"timed_reps_s={st['timed_s']!r} recording={path}", flush=True)
    if st["fallback"]:
        raise SmokeFailure(f"{int(st['fallback'])} kernel-component "
                           f"point(s) priced by the analytical fallback")
    if not st["timed"]:
        raise SmokeFailure("no kernel point was timed on the chip")
    if not front or not res.theta_max > res.theta_min > 0:
        raise SmokeFailure("empty or degenerate front")
    return path, _fingerprint(res, session)


def replay_check(path: str, recorded: str):
    from repro.apps.wami.pallas import wami_pallas_oracle
    from repro.core.registry import build_session
    oracle = wami_pallas_oracle("replay", tile=TILE, store_path=path)
    session = build_session("wami", "pallas", tool=oracle, delta=DELTA)
    res = session.run()
    same = _fingerprint(res, session) == recorded
    print(f"[d] replay of {os.path.basename(path)} "
          f"(device_kind={oracle.device_kind!r}): "
          f"identical={same} invocations={res.total_invocations}",
          flush=True)
    if not same:
        raise SmokeFailure("replay differs from the recorded drive")


def main() -> int:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: FAIL — no src/repro beside {__file__}; run it "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [src, ROOT]
    try:
        dev, count = require_tpu()
        from repro.launch.compile_cache import enable_compile_cache
        print(f"[a] compile cache: {enable_compile_cache()}", flush=True)
        kernel_parity()
        path, recorded = dse_query(dev.device_kind)
        replay_check(path, recorded)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL — {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
