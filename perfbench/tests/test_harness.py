"""The harness as a command and as data: it refuses to run without a
TPU or without the program, and it takes a new cell as data files and a
``BENCHMARK.json`` entry, with no edit to a file it already has."""

import json
import os
import shutil
import subprocess
import sys
import time

import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
ARGS = ["--workload", "wami-t128-cold", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _copy(tmp_path, with_src: bool):
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    if with_src:
        os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    return tmp_path


def _run_py(root, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, "perfbench/run.py", *ARGS],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_program_beside_the_benchmark_exits_nonzero(tmp_path):
    p = _run_py(_copy(tmp_path, with_src=False), {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_no_tpu_exits_nonzero(tmp_path):
    p = _run_py(_copy(tmp_path, with_src=True), {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_a_new_cell_is_data(tmp_path):
    root = _copy(tmp_path, with_src=True)
    cfg = json.load(open(root / "perfbench/configs/wami-perfect512-t128.json"))
    cfg["name"], cfg["reps"] = "wami-reps2", 2
    json.dump(cfg, open(root / "perfbench/configs/wami-reps2.json", "w"))
    traffic = json.load(open(root / "perfbench/traffic/cold.json"))
    traffic["about"] = "the cold mix under another name"
    json.dump(traffic, open(root / "perfbench/traffic/cold-again.json", "w"))
    bench = json.load(open(root / "BENCHMARK.json"))
    bench["configs"].append({"name": "wami-reps2", "source": "x",
                             "file": "perfbench/configs/wami-reps2.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "wami-reps2.cold", "config": "wami-reps2",
                               "traffic": "cold-again", "chips": 1, "why": "x"})
    for m in bench["per_layer"]:
        m["workloads"].append("wami-reps2.cold")
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    cell = harness.load_cell(str(root), "wami-reps2.cold")
    assert cell.config["reps"] == 2
    assert [m["name"] for m in cell.end_to_end] == ["points_per_s", "setup_s"]
    out = harness.run_cell(cell, root=str(root), seed=9, seconds=1.0,
                           trace=False, t0=time.monotonic(), interpret=True,
                           log=lambda m: None)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"points_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
