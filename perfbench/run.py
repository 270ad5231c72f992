#!/usr/bin/env python3
"""Run one benchmark cell once, on the TPU this process finds.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  ``--trace 0`` prints the cell's end-to-end
metrics; ``--trace 1`` runs the same window under the profiler and
prints its per-layer metrics, the device's busy time and a breakdown.
The last line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` when traced) and, last, ``checks``: each number compared
with the plain reference, beside its limit.  The same numbers are the
last lines of standard error.

Without a TPU, with fewer chips than the cell asks for, or without the
program's sources beside this directory, it exits non-zero and prints
no result.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seconds <= 0:
        log("--seconds must be positive")
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"no program beside the benchmark: {src}/repro is missing")
        return 2
    sys.path[:0] = [HERE, src]
    import harness
    try:
        cell = harness.load_cell(ROOT, args.workload)
    except (harness.CellError, OSError, KeyError) as e:
        log(f"cannot load workload {args.workload!r}: {e}")
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform!r} device(s) ({devices[0].device_kind!r})")
        return 3
    import peaks
    try:
        peaks.peak(devices[0].device_kind)
    except KeyError as e:
        log(str(e))
        return 3
    try:
        result = harness.run_cell(cell, root=ROOT, seed=args.seed,
                                  seconds=args.seconds, trace=bool(args.trace),
                                  t0=T0, log=log)
    except harness.CellError as e:
        log(str(e))
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
