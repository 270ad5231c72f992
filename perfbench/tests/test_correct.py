"""The comparison that decides ``correct`` fails what it must.

  * the control -- each kernel's reference computed one precision step
    below the configuration's -- reads above the limits, on three seeds,
    at sizes a CPU test holds;
  * a run of the WAMI cold cell, driven here on the CPU with the kernels in
    interpret mode (the harness's look for a chip is skipped), comes out
    correct when nothing is broken, and not correct with each fault of
    ``faults.py`` planted in the timed path.
"""

import copy
import os

import pytest

import control
import faults
import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEEDS = (1, 2, 2 ** 31 + 7)


def _small(cell):
    cell = copy.copy(cell)
    cell.config = copy.deepcopy(cell.config)
    cfg = cell.config
    cfg["tile"] = 32
    return cell


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_limits(seed):
    cell = _small(harness.load_cell(ROOT, "wami-t128-cold"))
    limits = cell.config["limits"]
    got = control.control_readings(cell, seed)
    failed = [k for k, v in got.items() if v > limits[k]]
    assert failed, f"the control passed every limit: {got}"


@pytest.fixture(scope="module")
def wami():
    return harness.load_cell(ROOT, "wami-t128-cold")


def _run(cell, root, fault=None, seconds=1.0):
    if fault is None:
        import time
        return harness.run_cell(cell, root=str(root), seed=12345,
                                seconds=seconds, trace=False,
                                t0=time.monotonic(), interpret=True,
                                log=lambda m: None)
    return faults.run_with(fault, cell, root=str(root), seed=12345,
                           seconds=seconds, interpret=True)


def test_sound_run_is_correct(wami, tmp_path):
    out = _run(wami, tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0


@pytest.mark.parametrize("fault, number", [
    ("kernel_output", "err.debayer"),
    ("kernel_output", "err.steep_descent"),
    ("half_output", "err.warp"),
    ("oracle_answer", "system_gap"),
    ("session_answer", "system_gap"),
    ("ledger_answer", "mismatches"),
    ("walk_step", "mismatches"),
    ("plan_step", "plan_gap"),
    ("map_choice", "mismatches"),
])
def test_fault_is_caught(wami, tmp_path, fault, number):
    out = _run(wami, tmp_path, fault)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]


def test_cold_query_reusing_compiles_is_caught(wami, tmp_path):
    # every query and warm-up point compiles into the checkout's cache,
    # so the second run finds the first run's programs there
    _run(wami, tmp_path, "reused_compiles")
    out = _run(wami, tmp_path, "reused_compiles")
    assert not out["correct"]
    assert out["checks"]["reused_compiles"]["value"] > 0
