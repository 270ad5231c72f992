"""fleet-zamba2-7b: one Zamba2-7B hybrid layer at its published widths
as a DSE app.  Kernels run in interpret mode at a small geometry cut
from the app's own (2 heads of 224, 4 scan heads, 256 tokens); the
full-size app is checked without running anything at full size."""

import dataclasses
import functools
import json
import os

import numpy as np
import pytest

from repro.apps.fleet import (ZAMBA2_7B_TP4, fleet_kernel_specs,
                              fleet_knob_spaces, fleet_tmg, fleet_xla_tool)
from repro.apps.fleet import zamba2_7b
from repro.apps.fleet.pipeline import _fleet_inputs, fleet_program
from repro.core import Tracer, WallClock, compose_exhaustive, exhaustive_dse
from repro.core.pallas_oracle import PallasOracle
from repro.core.registry import build_session, get_app, list_apps
from repro.kernels.flash_attention import mha_ref
from repro.kernels.ssd_scan import ssd_ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "perfbench", "configs",
                      "fleet-zamba2-7b-tp4-4k.json")
SMALL = dataclasses.replace(ZAMBA2_7B_TP4, flash_s=256, ssd_s=256,
                            q_heads=2, kv_heads=2, ssd_heads=4,
                            app="fleet-zamba2-small",
                            stem="fleet_zamba2_small_pallas")


def _points(geometry, kernel):
    spec = fleet_kernel_specs(geometry=geometry)[kernel]
    space = fleet_knob_spaces(geometry)[kernel]
    ports = [1 << i for i in range(space.max_ports.bit_length())
             if 1 << i <= space.max_ports]
    return [(p, u) for p in ports for u in range(1, space.max_unrolls + 1)
            if spec.divisible(p, u)]


POINTS = [(k, p, u) for k in ("flash_attention", "ssd_scan")
          for p, u in _points(SMALL, k)]


@functools.lru_cache(maxsize=None)
def _run(kernel, ports, unrolls):
    args = _fleet_inputs(SMALL)[:3] if kernel == "flash_attention" \
        else _fleet_inputs(SMALL)[3:]
    program = fleet_program(SMALL, kernel, ports, unrolls, True)
    out = program.lower(*args).compile()(*args)
    return tuple(np.asarray(o) for o in (out if isinstance(out, (tuple, list))
                                        else (out,)))


def _ref(kernel):
    args = _fleet_inputs(SMALL)
    if kernel == "flash_attention":
        return (np.asarray(mha_ref(*args[:3], causal=True)),)
    return tuple(np.asarray(o) for o in ssd_ref(*args[3:]))


# ----------------------------------------------------------------------
# every knob point computes the whole chip share, the same answer
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kernel,ports,unrolls", POINTS,
                         ids=[f"{k}-p{p}u{u}" for k, p, u in POINTS])
def test_every_point_equals_the_reference_and_the_others(kernel, ports,
                                                         unrolls):
    got = _run(kernel, ports, unrolls)
    ref = _ref(kernel)
    first = _run(kernel, *_points(SMALL, kernel)[0])
    assert len(got) == len(ref)      # attention o; the scan's y and h
    for g, r, f in zip(got, ref, first):
        assert g.shape == r.shape
        scale = float(np.max(np.abs(r)))
        assert np.max(np.abs(g - r)) <= 2e-5 * scale
        assert np.max(np.abs(g - f)) <= 2e-5 * scale


def test_both_knobs_change_the_grid_and_the_inputs_do_not():
    specs = fleet_kernel_specs(geometry=ZAMBA2_7B_TP4)
    for kernel, spec in specs.items():
        steps = {(p, u): spec.grid_steps(*spec.shape, ports=p, unrolls=u)
                 for p, u in _points(ZAMBA2_7B_TP4, kernel)}
        for (p, u), n in steps.items():
            # either knob alone changes the grid
            assert all(m != n for (q, v), m in steps.items()
                       if (q == p) != (v == u)), (kernel, p, u)
        tilings = {tuple(sorted(spec.tiling(p, u).items()))
                   for p, u in steps}
        assert len(tilings) == len(steps), kernel
    # the SSD's inputs are sliced by the knob only in the fleet app
    small = fleet_kernel_specs(geometry=SMALL)["ssd_scan"]
    shapes = {tuple(a.shape for a in small.build(p, u, True)[1])
              for p, u in _points(SMALL, "ssd_scan")}
    assert len(shapes) == 1


# ----------------------------------------------------------------------
# the configuration file's map is the program's
# ----------------------------------------------------------------------
def _config():
    with open(CONFIG) as f:
        return json.load(f)


def test_vmem_and_grid_steps_match_the_config_files_map():
    cfg = _config()
    g = ZAMBA2_7B_TP4
    limit = cfg["tiling_rule"]["vmem_limit_bytes"]
    assert limit == g.vmem_limit_bytes
    for kernel, spec in fleet_kernel_specs(geometry=g).items():
        table = cfg["tiling"][kernel]
        assert sorted(table) == sorted(f"{p}x{u}"
                                       for p, u in _points(g, kernel))
        for key, want in table.items():
            p, u = map(int, key.split("x"))
            got = {k: v for k, v in spec.tiling(p, u).items()
                   if k != "heads"}
            got["vmem_step_bytes"] = spec.vmem_bytes(*spec.shape, ports=p,
                                                     unrolls=u)
            got["grid_steps"] = spec.grid_steps(*spec.shape, ports=p,
                                                unrolls=u)
            assert got == want, (kernel, key)
            # double-buffered, every point fits the VMEM it declares
            assert 2 * want["vmem_step_bytes"] <= limit


def test_config_file_holds_the_published_config_and_the_cut():
    cfg = _config()
    g = ZAMBA2_7B_TP4
    assert cfg["app"] == g.app and cfg["source"] == zamba2_7b.SOURCE
    for key, value in zamba2_7b.PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == (list(value) if isinstance(value, tuple)
                                else value), key
    share = zamba2_7b.CHIP_SHARE
    assert cfg["num_attention_heads"] == share["q_heads"] == g.q_heads
    assert cfg["num_key_value_heads"] == share["kv_heads"] == g.kv_heads
    assert cfg["n_mamba_heads"] == share["ssd_heads"] == g.ssd_heads
    assert cfg["mamba_ngroups"] == share["bc_groups"] == 1
    fa = cfg["kernels"]["flash_attention"]["dims"]
    sc = cfg["kernels"]["ssd_scan"]["dims"]
    assert (fa["tokens"], fa["head_dim"]) == (
        zamba2_7b.PUBLISHED["max_position_embeddings"],
        zamba2_7b.PUBLISHED["attention_head_dim"]) == (g.flash_s, g.head_dim)
    assert (sc["tokens"], sc["heads"], sc["P"], sc["N"]) == (
        g.ssd_s, g.ssd_heads, g.ssd_p, g.ssd_n)
    assert g.ssd_chunk_rows * g.max_unrolls == zamba2_7b.PUBLISHED[
        "chunk_size"]


# ----------------------------------------------------------------------
# registry, sessions and fronts
# ----------------------------------------------------------------------
def test_the_app_resolves_by_name_and_stays_out_of_the_sweeps():
    app = get_app("fleet-zamba2-7b")
    assert app.name not in [a.name for a in list_apps()]
    assert "fleet" in [a.name for a in list_apps()]
    assert app.parity_cases is None
    before = _fleet_inputs.cache_info().currsize
    session = build_session("fleet-zamba2-7b", "analytical")
    assert set(session.spaces) == {"flash_attention", "ssd_scan"}
    app.kernel_specs(0)
    assert _fleet_inputs.cache_info().currsize == before   # nothing made


def test_the_committed_chip_recording_replays_with_nothing_built():
    """The benchmark's set-up replays this recording (missing points
    raise): every point the walk and the mapping ask is in it, and the
    replay makes no input and runs no kernel."""
    before = _fleet_inputs.cache_info().currsize
    session = build_session("fleet-zamba2-7b", "pallas", delta=0.3)
    res = session.run()
    assert session.ledger.tool.device_kind == "TPU v5 lite"
    assert set(res.invocations) == {"flash_attention", "ssd_scan"}
    assert res.mapped and res.theta_max > res.theta_min > 0
    # every infeasible point is an unrolls that does not divide 4096
    for r in session.ledger.records:
        assert r.feasible or 4096 % r.unrolls, (r.component, r.ports,
                                                r.unrolls)
    assert _fleet_inputs.cache_info().currsize == before


def test_the_analytical_stages_are_priced_from_zamba2_7b():
    attn, mamba = zamba2_7b.analytical_stages()
    assert (attn.d_model, attn.n_heads, attn.hd()) == (7168, 32, 224)
    assert (mamba.d_model, mamba.ssm_heads(), mamba.ssm_state) == (3584, 112,
                                                                   64)
    tool = fleet_xla_tool(ZAMBA2_7B_TP4)
    for comp in ("flash_attention", "ssd_scan"):
        assert tool.synthesize(comp, unrolls=2, ports=1).feasible


def test_session_front_is_the_exhaustive_front_at_its_extremes():
    """Priced by a deterministic timer (nothing built), every point of
    the session's front is a point of the front composed from an
    exhaustive enumeration of the knob points, extremes included."""
    g = ZAMBA2_7B_TP4
    specs = {n: dataclasses.replace(s, build=lambda p, u, i: (None, ()))
             for n, s in fleet_kernel_specs(geometry=g).items()}

    def timer(comp, ports, unrolls, _built):
        s = specs[comp]
        return 2e-7 * s.grid_steps(*s.shape, ports=ports,
                                   unrolls=unrolls) + 1e-4

    def oracle():
        return PallasOracle(specs, mode="measure", interpret=True,
                            fallback=fleet_xla_tool(g), timer=timer)

    res = build_session(g.app, "pallas", tool=oracle()).run()
    spaces = fleet_knob_spaces(g)
    ex = exhaustive_dse(list(spaces), oracle(), spaces)
    exact = {(p.perf, p.cost) for p in compose_exhaustive(fleet_tmg(),
                                                          ex.fronts)}
    front = sorted((p.perf, p.cost) for p in res.pareto())
    assert front and set(front) <= exact
    assert front[0] == min(exact) and front[-1] == max(exact)


def test_fleet_fronts_stay_byte_identical(bench_cell_lines,
                                          committed_artifact):
    from benchmarks import fleet_dse
    from benchmarks.scenarios import Cell
    for backend in ("analytical", "pallas"):
        got = bench_cell_lines(fleet_dse, Cell("fleet", "fleet", backend))
        assert got == committed_artifact("fleet", f"fleet-{backend}.csv")


# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------
def test_lower_span_carries_the_tiling_and_reps_the_launches():
    tracer = Tracer(WallClock())
    specs = fleet_kernel_specs(geometry=SMALL)
    oracle = PallasOracle(specs, mode="measure", interpret=True, reps=2,
                          fallback=fleet_xla_tool(SMALL))
    oracle.tracer = tracer
    s = oracle.synthesize("ssd_scan", ports=2, unrolls=4)
    assert s.feasible
    lower, = tracer.spans("pallas.lower")
    assert (lower.attrs["heads_per_step"], lower.attrs["chunk"],
            lower.attrs["heads"]) == (2, 128, 4)
    reps, = tracer.spans("pallas.reps")
    assert reps.attrs["launches"] == 2
