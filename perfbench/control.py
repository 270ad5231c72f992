#!/usr/bin/env python3
"""Readings of the control: each kernel's plain reference put in the
kernel's place, computed one precision step below what the
configuration states (bfloat16 for float32, three bfloat16 passes for
float32 at highest), at the cell's own sizes, on the given seeds.

    python3 perfbench/control.py --workload <name> --seeds 1 2 3

Prints one JSON line per seed: each ``err.<kernel>`` number the
harness compares, as the control reads it, beside the limit.  A sound
limit lies below every control reading.  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def control_readings(cell, seed: int):
    """{number: control reading} for one seed."""
    import numpy as np
    import harness
    import numerics as nx
    cfg = cell.config
    inputs = cell.reference.make_inputs(cfg, seed)
    refs = harness.reference_outputs(cell, inputs, "exact")
    host = {n: tuple(np.asarray(a) for a in args) for n, args in inputs.items()}
    ctl = {n: [tuple(cell.reference.reference(n, nx.CONTROL_OF[k["precision"]],
                                              *host[n]))]
           for n, k in cfg["kernels"].items()}
    return {f"err.{n}": v
            for n, v in harness.kernel_errors(cell, ctl, refs).items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    cell = harness.load_cell(ROOT, args.workload)
    limits = cell.config["limits"]
    for seed in args.seeds:
        got = control_readings(cell, seed)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": {k: {"value": v, "limit": limits[k]}
                                      for k, v in got.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
