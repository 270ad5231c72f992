"""PallasOracle semantics: feasibility, accounting, record/replay
determinism, fallback routing, and calibration."""

import math

import pytest

from repro.apps.wami.pallas import (default_measurement_path,
                                    wami_measurement_set,
                                    wami_pallas_components,
                                    wami_pallas_oracle, wami_pallas_session,
                                    wami_plm_session)
from repro.core import (CalibratedTool, InvocationRequest, KnobSpace,
                        MeasurementSet, MeasurementStore,
                        MissingMeasurementError, OracleLedger, PallasOracle,
                        Synthesis, cosmos_dse, fit_latency_scales)
from repro.core.tmg import pipeline_tmg


def _fake_timer(name, ports, unrolls, runner):
    """Deterministic stand-in for the wall clock: Amdahl-ish in the
    unrolls, sub-linear benefit in ports, component-dependent offset."""
    return (1e-3 * (32 / unrolls) + 2e-4 * ports ** 0.5
            + 1e-5 * len(name))


def _small():
    comps = wami_pallas_components(tile=32)
    sub = {n: comps[n] for n in ("grayscale", "gradient")}
    spaces = {n: KnobSpace(clock_ns=1.0, max_ports=4, max_unrolls=8)
              for n in sub}
    return sub, spaces


# ----------------------------------------------------------------------
# feasibility + accounting
# ----------------------------------------------------------------------
def test_non_divisible_knobs_are_infeasible_and_counted():
    sub, _ = _small()
    ledger = OracleLedger(PallasOracle(sub, timer=_fake_timer, interpret=True))
    s = ledger.synthesize("gradient", unrolls=5, ports=2)   # 32 % 5 != 0
    assert not s.feasible and math.isinf(s.lam)
    assert ledger.invocations["gradient"] == 1              # Fig. 11 counts it
    assert ledger.failed["gradient"] == 1
    ok = ledger.synthesize("gradient", unrolls=4, ports=2)
    assert ok.feasible and ok.lam > 0 and ok.area > 0


def test_vmem_budget_is_the_lambda_constraint():
    sub, _ = _small()
    oracle = PallasOracle(sub, timer=_fake_timer, interpret=True,
                          vmem_budget=1024)
    s = oracle.synthesize("gradient", unrolls=8, ports=1)
    assert not s.feasible


def test_max_states_cap_discards():
    sub, _ = _small()
    oracle = PallasOracle(sub, timer=_fake_timer, interpret=True)
    s = oracle.synthesize("gradient", unrolls=8, ports=1, max_states=1)
    assert not s.feasible and s.states_per_iter > 1


def test_unknown_component_requires_fallback():
    sub, _ = _small()
    oracle = PallasOracle(sub, timer=_fake_timer, interpret=True)
    with pytest.raises(KeyError):
        oracle.synthesize("matrix_mul", unrolls=2, ports=1)


def test_ports_parallelism_and_area_economics():
    """More banks: lower per-bank latency, higher VMEM area (DESIGN.md §2)."""
    sub, _ = _small()
    oracle = PallasOracle(sub, timer=_fake_timer, interpret=True)
    s1 = oracle.synthesize("gradient", unrolls=4, ports=1)
    s4 = oracle.synthesize("gradient", unrolls=4, ports=4)
    assert s4.lam < s1.lam
    assert s4.area > s1.area


# ----------------------------------------------------------------------
# record / replay
# ----------------------------------------------------------------------
def _front(res):
    return [(p.perf, p.cost) for p in res.pareto()]


def test_replay_is_byte_identical_to_fresh_record(tmp_path):
    sub, spaces = _small()
    tmg = pipeline_tmg(list(sub))
    path = str(tmp_path / "m.json")

    fresh = PallasOracle(sub, mode="record",
                         store=MeasurementStore(path), timer=_fake_timer,
                         interpret=True)
    r1 = cosmos_dse(tmg, fresh, spaces, delta=0.3)
    assert fresh.flush() == path

    replay = PallasOracle(sub, mode="replay",
                          store=MeasurementStore.load(path))
    r2 = cosmos_dse(tmg, replay, spaces, delta=0.3, workers=8)

    assert _front(r1) == _front(r2)
    assert r1.invocations == r2.invocations
    assert [(m.theta_actual, m.cost_actual) for m in r1.mapped] \
        == [(m.theta_actual, m.cost_actual) for m in r2.mapped]


def test_record_resumes_without_retiming_paid_points(tmp_path):
    """A killed recording campaign (autoflushed, never flush()ed) must
    resume from the flushed file and never re-time a paid point."""
    sub, _ = _small()
    path = str(tmp_path / "m.json")
    calls = []

    def counting_timer(name, ports, unrolls, runner):
        calls.append((name, ports, unrolls))
        return _fake_timer(name, ports, unrolls, runner)

    first = PallasOracle(sub, mode="record",
                         store=MeasurementStore(path, flush_every=1),
                         timer=counting_timer, interpret=True)
    first.synthesize("gradient", unrolls=4, ports=2)
    first.synthesize("gradient", unrolls=8, ports=2)
    first.synthesize("grayscale", unrolls=4, ports=1)
    assert len(calls) == 3
    # simulated kill: no flush() — the autoflush already persisted all 3
    resumed_store = MeasurementStore.load(path, flush_every=1)
    assert len(resumed_store) == 3

    second = PallasOracle(sub, mode="record", store=resumed_store,
                          timer=counting_timer, interpret=True)
    s = second.synthesize("gradient", unrolls=4, ports=2)    # paid already
    assert s.feasible and len(calls) == 3                    # not re-timed
    second.synthesize("gradient", unrolls=16, ports=2)       # new point
    assert len(calls) == 4
    assert len(MeasurementStore.load(path)) == 4             # autoflushed


def test_autoflush_batches_by_flush_every(tmp_path):
    import os
    path = str(tmp_path / "m.json")
    store = MeasurementStore(path, flush_every=3)
    store.put(("a", 1, 1), 1.0)
    store.put(("a", 1, 2), 1.0)
    assert not os.path.exists(path)          # below the batch threshold
    store.put(("a", 1, 3), 1.0)
    assert os.path.exists(path)              # third put flushed atomically
    assert len(MeasurementStore.load(path)) == 3


def test_store_roundtrip_and_missing_measurement(tmp_path):
    path = str(tmp_path / "m.json")
    store = MeasurementStore(path, meta={"tile": 32})
    store.put(("gradient", 2, 4), 1.5e-3)
    store.save()
    loaded = MeasurementStore.load(path)
    assert loaded.get(("gradient", 2, 4)) == pytest.approx(1.5e-3)
    assert loaded.meta == {"tile": 32}

    sub, _ = _small()
    replay = PallasOracle(sub, mode="replay", store=loaded)
    s = replay.synthesize("gradient", unrolls=4, ports=2)
    assert s.feasible and s.detail["wall_s"] == pytest.approx(1.5e-3)
    with pytest.raises(MissingMeasurementError):
        replay.synthesize("gradient", unrolls=8, ports=1)


def test_checked_in_recording_drives_wami_end_to_end():
    """Acceptance: cosmos_dse over the full WAMI TMG from the committed
    recording — deterministic, no TPU, fallback prices the 6x6 stages."""
    import os
    assert os.path.exists(default_measurement_path())
    res1 = wami_pallas_session(0.25, workers=4).run()
    res2 = wami_pallas_session(0.25, workers=4).run()
    assert len(res1.characterizations) == 12
    assert len(res1.mapped) >= 5
    assert res1.theta_max > res1.theta_min > 0
    assert _front(res1) == _front(res2)
    assert res1.invocations == res2.invocations


# ----------------------------------------------------------------------
# tile routing + replay-miss policy
# ----------------------------------------------------------------------
def test_non_native_tile_routes_to_fallback():
    from repro.apps.wami.pipeline import wami_hls_tool
    sub, _ = _small()
    oracle = PallasOracle(sub, timer=_fake_timer, interpret=True,
                          native_tile=32, fallback=wami_hls_tool(tile=32))
    native = oracle.synthesize("gradient", unrolls=4, ports=2, tile=32)
    assert native.detail.get("wall_s") is not None       # measured path
    other = oracle.synthesize("gradient", unrolls=4, ports=2, tile=64)
    assert other.feasible and "wall_s" not in other.detail
    assert other.tile == 64


def test_tile_request_without_native_tile_is_an_error():
    """An oracle with no declared native_tile cannot price a tile axis
    — doing so would relabel one tile's measurements as another's."""
    sub, _ = _small()
    oracle = PallasOracle(sub, timer=_fake_timer, interpret=True)
    with pytest.raises(ValueError, match="native_tile"):
        oracle.synthesize("gradient", unrolls=4, ports=2, tile=64)


def test_fallback_priced_native_point_reports_fallback_requirement(tmp_path):
    """missing='fallback' points carry no wall_s; their PLM requirement
    must come from the fallback's logic/PLM split, not be misread as an
    all-memory measured footprint."""
    from repro.apps.wami.pipeline import wami_hls_tool
    sub, _ = _small()
    path = str(tmp_path / "m.json")
    store = MeasurementStore(path)
    store.put(("gradient", 2, 4), 1.5e-3)
    store.save()
    lax = PallasOracle(sub, mode="replay",
                       store=MeasurementStore.load(path),
                       fallback=wami_hls_tool(tile=32), missing="fallback")
    measured = lax.synthesize("gradient", unrolls=4, ports=2)
    req_m = lax.plm_requirement("gradient", measured)
    assert req_m.unit == "bytes" and req_m.area_logic == 0.0
    modelled = lax.synthesize("gradient", unrolls=8, ports=2)
    req_f = lax.plm_requirement("gradient", modelled)
    assert req_f.unit == "mm2" and req_f.area_logic > 0.0
    assert req_f.area_plm == pytest.approx(modelled.detail["area_plm"])


def test_replay_missing_fallback_policy(tmp_path):
    from repro.apps.wami.pipeline import wami_hls_tool
    sub, _ = _small()
    path = str(tmp_path / "m.json")
    store = MeasurementStore(path)
    store.put(("gradient", 2, 4), 1.5e-3)
    store.save()
    strict = PallasOracle(sub, mode="replay",
                          store=MeasurementStore.load(path))
    with pytest.raises(MissingMeasurementError):
        strict.synthesize("gradient", unrolls=8, ports=2)
    lax = PallasOracle(sub, mode="replay",
                       store=MeasurementStore.load(path),
                       fallback=wami_hls_tool(tile=32), missing="fallback")
    hit = lax.synthesize("gradient", unrolls=4, ports=2)
    assert hit.detail["wall_s"] == pytest.approx(1.5e-3)  # recorded point
    miss = lax.synthesize("gradient", unrolls=8, ports=2)
    assert miss.feasible and "wall_s" not in miss.detail  # fallback-priced
    with pytest.raises(ValueError):
        PallasOracle(sub, mode="replay", store=store, missing="fallback")


# ----------------------------------------------------------------------
# MeasurementSet: multi-recording routing
# ----------------------------------------------------------------------
def _store_with(tmp_path, name, tile, entries):
    store = MeasurementStore(str(tmp_path / name),
                             meta={"tile": tile, "interpret": True})
    for key, wall in entries.items():
        store.put(key, wall)
    store.save()
    return store


def test_measurement_set_native_hit_and_multi_tile_routing(tmp_path):
    """Recorded tiles replay measured walls; unrecorded tiles fall
    through to the fallback tool."""
    from repro.apps.wami.pipeline import wami_hls_tool
    s32 = _store_with(tmp_path, "t32.json", 32,
                      {("gradient", 2, 4): 1.0e-3})
    s64 = _store_with(tmp_path, "t64.json", 64,
                      {("gradient", 2, 4): 3.0e-3})
    ms = MeasurementSet()
    ms.add(s32)
    ms.add(s64)
    assert ms.keys() == [(32, "interpret"), (64, "interpret")]
    oracle = PallasOracle(wami_pallas_components(32), mode="replay",
                          measurements=ms,
                          components_factory=wami_pallas_components,
                          fallback=wami_hls_tool(tile=32),
                          native_tile=32, missing="fallback")
    native = oracle.synthesize("gradient", unrolls=4, ports=2)
    assert native.detail["wall_s"] == pytest.approx(1.0e-3)
    t64 = oracle.synthesize("gradient", unrolls=4, ports=2, tile=64)
    assert t64.detail["wall_s"] == pytest.approx(3.0e-3)
    assert t64.tile == 64
    # measured tiles see tile geometry: same knobs, 2x edge => 4x blocks
    assert t64.area > native.area
    t128 = oracle.synthesize("gradient", unrolls=4, ports=2, tile=128)
    assert t128.feasible and "wall_s" not in t128.detail    # fallback
    # facts for a measured non-native tile come from that tile's specs
    assert oracle.cdfg_facts("gradient", t64).trip == 64


def test_measurement_set_missing_error_names_key_and_lists_available(
        tmp_path):
    s32 = _store_with(tmp_path, "t32.json", 32,
                      {("gradient", 2, 4): 1.0e-3})
    oracle = PallasOracle(wami_pallas_components(32), mode="replay",
                          measurements=MeasurementSet().add(s32),
                          native_tile=32, missing="error")
    with pytest.raises(MissingMeasurementError) as exc:
        oracle.synthesize("gradient", unrolls=8, ports=2)
    msg = str(exc.value)
    assert "(tile=32, device='interpret')" in msg      # the missing key
    assert "recorded keys" in msg                      # ...and what exists


def test_recorded_tile_resolves_without_native_tile_declared(tmp_path):
    """The old single-store design raised ValueError for an explicit
    tile even when that tile WAS the recording's — the MeasurementSet
    shim must resolve it instead."""
    store = _store_with(tmp_path, "t32.json", 32,
                        {("gradient", 2, 4): 1.0e-3})
    with pytest.warns(DeprecationWarning):
        oracle = PallasOracle(wami_pallas_components(32), mode="replay",
                              store=MeasurementStore.load(store.path))
    hit = oracle.synthesize("gradient", unrolls=4, ports=2, tile=32)
    assert hit.feasible and hit.detail["wall_s"] == pytest.approx(1.0e-3)
    native = oracle.synthesize("gradient", unrolls=4, ports=2)
    assert native.detail["wall_s"] == pytest.approx(1.0e-3)
    # a genuinely unrecorded tile still errors, naming the missing key
    with pytest.raises((ValueError, MissingMeasurementError),
                       match="tile=64"):
        oracle.synthesize("gradient", unrolls=4, ports=2, tile=64)


def test_legacy_store_shim_warns_and_preserves_cache_keys(tmp_path):
    """PallasOracle(store=...) deprecates but stays byte-compatible:
    same results, same OracleLedger cache keys as measurements=."""
    store = _store_with(tmp_path, "t32.json", 32,
                        {("gradient", 2, 4): 1.0e-3,
                         ("grayscale", 1, 4): 2.0e-3})
    with pytest.warns(DeprecationWarning, match="legacy single-recording"):
        legacy = PallasOracle(wami_pallas_components(32), mode="replay",
                              store=MeasurementStore.load(store.path),
                              native_tile=32)
    modern = PallasOracle(wami_pallas_components(32), mode="replay",
                          measurements=MeasurementSet.from_store(
                              MeasurementStore.load(store.path), tile=32),
                          native_tile=32)
    requests = [InvocationRequest("gradient", unrolls=4, ports=2),
                InvocationRequest("grayscale", unrolls=4, ports=1),
                InvocationRequest("gradient", unrolls=4, ports=2, tile=32)]
    led_a, led_b = OracleLedger(legacy), OracleLedger(modern)
    out_a = led_a.evaluate_batch(requests)
    out_b = led_b.evaluate_batch(requests)
    assert [(s.lam, s.area, s.tile) for s in out_a] \
        == [(s.lam, s.area, s.tile) for s in out_b]
    keys_a = sorted((r.component, r.unrolls, r.ports, r.tile)
                    for r in led_a.records)
    assert keys_a == sorted((r.component, r.unrolls, r.ports, r.tile)
                            for r in led_b.records)
    assert led_a.invocations == led_b.invocations


def test_checked_in_multi_tile_recordings_route_measured_vs_fallback():
    """The REAL recorded artifacts (tile 64 + 128): a multi-tile session
    oracle replays measured walls at both tiles and falls back only on
    genuinely unrecorded tiles — the ROADMAP multi-tile item, exercised
    against the committed recordings rather than mocks."""
    import os
    for tile in (64, 128):
        assert os.path.exists(default_measurement_path(tile))
    from repro.apps.wami.pallas import wami_unit_system
    from repro.apps.wami.pipeline import wami_hls_tool
    ms = wami_measurement_set((64, 128))
    assert ms.tiles("interpret") == (64, 128)
    oracle = PallasOracle(
        wami_pallas_components(128), mode="replay", measurements=ms,
        components_factory=wami_pallas_components,
        fallback=wami_unit_system().calibrated(wami_hls_tool()),
        native_tile=128, missing="fallback")
    s128 = oracle.synthesize("gradient", unrolls=1, ports=1, tile=128)
    s64 = oracle.synthesize("gradient", unrolls=1, ports=1, tile=64)
    s256 = oracle.synthesize("gradient", unrolls=1, ports=1, tile=256)
    assert "wall_s" in s128.detail and "wall_s" in s64.detail
    assert s256.feasible and "wall_s" not in s256.detail
    # distinct recordings, distinct walls
    assert s64.detail["wall_s"] != s128.detail["wall_s"]


def test_plm_session_with_measured_tiles_replays_tile64(tmp_path):
    """wami_plm_session(measured_tiles=(64, 128)) drives the tile axis
    measured-vs-fallback end to end and stays deterministic."""
    res = wami_plm_session(0.25, measured_tiles=(64, 128), workers=4).run()
    measured_t64 = [
        o for m in res.mapped for o in m.outcomes
        if o.synthesis.tile == 64 and "wall_s" in (o.synthesis.detail or {})]
    assert measured_t64, "no mapped tile-64 point replayed a measured wall"
    # the default (single-recording) drive prices ALL tile-64 points
    # through the fallback — the recordings genuinely change the drive
    base = wami_plm_session(0.25, workers=4).run()
    assert not [o for m in base.mapped for o in m.outcomes
                if o.synthesis.tile == 64
                and "wall_s" in (o.synthesis.detail or {})]


# ----------------------------------------------------------------------
# calibration
# ----------------------------------------------------------------------
class _StubModel:
    def synthesize(self, component, *, unrolls, ports, max_states=None):
        return Synthesis(lam=1e-3 * unrolls, area=1.0, ports=ports,
                         unrolls=unrolls)

    def cdfg_facts(self, component, synth):
        raise NotImplementedError


def test_calibration_recovers_scale():
    measured = [("k", p, u, 2.0 * 1e-3 * u)
                for p in (1, 2) for u in (2, 4, 8)]
    fit = fit_latency_scales(_StubModel(), measured)
    assert fit.scale("k") == pytest.approx(2.0)
    assert fit.lam_spread["k"] == pytest.approx(1.0)
    assert fit.scale("unseen") == 1.0

    cal = CalibratedTool(_StubModel(), fit)
    s = cal.synthesize("k", unrolls=4, ports=1)
    assert s.lam == pytest.approx(8e-3)
    assert s.area == 1.0                      # areas stay backend-local


def test_calibration_skips_bad_points():
    fit = fit_latency_scales(_StubModel(), [("k", 1, 4, float("inf")),
                                            ("k", 1, 4, -1.0),
                                            ("k", 1, 4, 4e-3)])
    assert fit.scale("k") == pytest.approx(1.0)   # only the 1x point fits
    assert fit.points["k"] == 1


# ----------------------------------------------------------------------
# device plumbing: where live measurements run, how they are keyed
# ----------------------------------------------------------------------
CHIP = "TPU v5 lite"


def test_record_on_a_chip_kind_never_aliases_the_interpret_store(tmp_path):
    from repro.core.pallas_oracle import open_recording
    chip_path = default_measurement_path(128, CHIP)
    assert chip_path != default_measurement_path(128)
    assert chip_path.endswith("wami_pallas_tile128.tpu_v5_lite.json")
    # a chip campaign refuses to resume an interpret file...
    with pytest.raises(ValueError, match="needs a file of its own"):
        open_recording(default_measurement_path(128), mode="record",
                       tile=128, device_kind=CHIP)
    # ...and a chip-keyed oracle over interpret stores finds no store,
    # so it raises instead of writing chip walls under "interpret"
    sub, _ = _small()
    interp = MeasurementStore(str(tmp_path / "i.json"),
                              meta={"tile": 32, "interpret": True})
    oracle = PallasOracle(sub, mode="record", interpret=True,
                          device_kind=CHIP, timer=_fake_timer,
                          measurements=MeasurementSet().add(interp),
                          native_tile=32)
    with pytest.raises(MissingMeasurementError, match=CHIP):
        oracle.synthesize("gradient", unrolls=4, ports=2)
    assert len(interp) == 0
    # a fresh chip file is tagged and keyed by the chip's kind
    ms = open_recording(str(tmp_path / "c.json"), mode="record", tile=32,
                        device_kind=CHIP)
    assert ms.keys() == [(0, CHIP), (32, CHIP)]
    chip = PallasOracle(sub, mode="record", interpret=True,
                        device_kind=CHIP, timer=_fake_timer,
                        measurements=ms, native_tile=32)
    assert chip.synthesize("gradient", unrolls=4, ports=2).feasible
    saved = MeasurementStore.load(chip.flush())
    assert saved.device_kind == CHIP and len(saved) == 1


def test_live_measurement_without_a_tpu_raises(tmp_path):
    sub, _ = _small()
    for mode in ("measure", "record"):
        with pytest.raises(RuntimeError, match="needs a TPU"):
            PallasOracle(sub, mode=mode, timer=_fake_timer,
                         measurements=MeasurementSet.from_store(
                             MeasurementStore(str(tmp_path / "m.json"))))
    with pytest.raises(RuntimeError, match="needs a TPU"):
        wami_pallas_oracle("record", tile=32,
                           store_path=str(tmp_path / "w.json"))


def test_record_mode_missing_store_raises_instead_of_falling_back(
        tmp_path):
    from repro.apps.wami.pipeline import wami_hls_tool
    store = MeasurementStore(str(tmp_path / "t32.json"),
                             meta={"tile": 32, "interpret": True})
    oracle = PallasOracle(wami_pallas_components(32), mode="record",
                          interpret=True, timer=_fake_timer,
                          measurements=MeasurementSet().add(store),
                          components_factory=wami_pallas_components,
                          fallback=wami_hls_tool(tile=32), native_tile=32)
    assert oracle.synthesize("gradient", unrolls=4, ports=2,
                             tile=32).feasible
    with pytest.raises(MissingMeasurementError, match="tile=64"):
        oracle.synthesize("gradient", unrolls=4, ports=2, tile=64)
    # a component without a kernel still prices analytically
    assert oracle.synthesize("matrix_mul", unrolls=2, ports=1).feasible
    assert oracle.stats["fallback"] == 0 and oracle.stats["timed"] == 1


def test_replay_routes_to_interpret_recordings_on_any_host(monkeypatch):
    """A TPU host replays the committed interpret recordings by default:
    replay never asks the platform."""
    import jax
    from repro.core.registry import build_tool

    class _Chip:
        platform, device_kind = "tpu", CHIP

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Chip()])
    committed = MeasurementStore.load(default_measurement_path(128))
    comp, ports, unrolls = min(committed.entries)
    for oracle in (wami_pallas_oracle("replay"),
                   build_tool("wami", "pallas")):
        assert oracle.device_kind == "interpret"
        s = oracle.synthesize(comp, unrolls=unrolls, ports=ports)
        assert s.detail["wall_s"] == committed.get((comp, ports, unrolls))
    mixed = MeasurementSet().add(committed).add(
        MeasurementStore(meta={"tile": 128, "device_kind": CHIP}))
    assert mixed.replay_kind() == "interpret"


def test_vmem_budget_is_keyed_by_device_kind():
    from repro.core.pallas_oracle import device_vmem_budget
    assert device_vmem_budget(CHIP) == device_vmem_budget("interpret") \
        == 16 * 1024 * 1024
    with pytest.raises(ValueError, match="no VMEM budget"):
        device_vmem_budget("TPU v99")
    sub, _ = _small()
    with pytest.raises(ValueError, match="no VMEM budget"):
        PallasOracle(sub, interpret=True, device_kind="TPU v99",
                     timer=_fake_timer)


def test_compile_cache_dir_prefers_the_env_then_a_fixed_repo_path():
    import os
    from repro.launch.compile_cache import (REPO_CACHE_DIR,
                                            compile_cache_dir)
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/c"}) == "/c"
    assert compile_cache_dir({}) == compile_cache_dir({}) == REPO_CACHE_DIR
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert REPO_CACHE_DIR == os.path.join(repo, ".jax_cache")


class _Refused:
    """A program the compiler refuses at lowering, as Mosaic does."""

    def __init__(self, exc):
        self.exc = exc

    def lower(self, *args):
        raise self.exc


def _spec_with(name, program):
    from repro.core import PallasKernelSpec
    from repro.kernels import wami_gradient
    return PallasKernelSpec(name=name, shape=(32, 32),
                            build=lambda p, u, interpret: program,
                            vmem_bytes=wami_gradient.vmem_bytes,
                            grid_steps=wami_gradient.grid_steps,
                            n_in=4, n_out=2)


def test_compiler_refusal_is_a_recorded_failed_synthesis(tmp_path):
    import jax
    import jax.numpy as jnp
    refused = (_Refused(ValueError("block shape (2, 16) not tileable")), ())
    fine = (jax.jit(lambda x: x + 1.0), (jnp.ones((8, 128)),))
    specs = {"gradient": _spec_with("gradient", refused),
             "grayscale": _spec_with("grayscale", fine)}
    path = str(tmp_path / "m.json")
    ledger = OracleLedger(PallasOracle(
        specs, mode="record", interpret=True,
        measurements=MeasurementSet.from_store(MeasurementStore(path))))
    s = ledger.synthesize("gradient", unrolls=2, ports=2)
    assert not s.feasible and s.detail["refused"].startswith(
        "lowering: ValueError: block shape")
    assert ledger.failed["gradient"] == 1          # counted like Fig. 11
    ok = ledger.synthesize("grayscale", unrolls=2, ports=2)
    assert ok.feasible and ok.detail["wall_s"] > 0
    assert ledger.tool.stats["refused"] == 1 and ledger.tool.stats["timed"] == 1
    ledger.tool.flush()
    replay = PallasOracle(specs, mode="replay",
                          measurements=MeasurementSet.from_store(
                              MeasurementStore.load(path)))
    again = replay.synthesize("gradient", unrolls=2, ports=2)
    assert again.detail == s.detail and not again.feasible


def test_other_exceptions_at_lowering_propagate():
    boom = (_Refused(RuntimeError("not a compiler refusal")), ())
    oracle = PallasOracle({"gradient": _spec_with("gradient", boom)},
                          interpret=True)
    with pytest.raises(RuntimeError, match="not a compiler refusal"):
        oracle.synthesize("gradient", unrolls=2, ports=2)


# ----------------------------------------------------------------------
# the measurement's spans and counters
# ----------------------------------------------------------------------
PALLAS_SPANS = ("pallas.lower", "pallas.compile", "pallas.warmup",
                "pallas.reps")


def _live_specs():
    import jax
    import jax.numpy as jnp
    refused = (_Refused(ValueError("block shape (2, 16) not tileable")), ())
    return {"gradient": _spec_with("gradient", refused),
            "grayscale": _spec_with("grayscale", (
                jax.jit(lambda x: x * 2.0 + 1.0), (jnp.ones((8, 128)),))),
            "warp": _spec_with("warp", (
                jax.jit(lambda x: jnp.tanh(x) - x), (jnp.ones((8, 128)),)))}


def _live_traced_ledger():
    from repro.core import Tracer, WallClock
    tracer = Tracer(WallClock())
    ledger = OracleLedger(PallasOracle(_live_specs(), interpret=True),
                          tracer=tracer)
    for name in ("gradient", "grayscale", "warp"):
        ledger.synthesize(name, unrolls=2, ports=2)
    return ledger.tool, tracer


def test_each_timed_point_has_one_span_per_stage_inside_its_tool_point():
    oracle, tracer = _live_traced_ledger()
    points = {s.attrs["component"]: s for s in tracer.spans("tool.point")}
    for name in ("grayscale", "warp"):
        kids = [s for s in tracer.spans() if s.parent_id
                == points[name].span_id and s.name.startswith("pallas.")]
        assert [s.name for s in kids] == list(PALLAS_SPANS)
        for s in kids:
            assert s.attrs["component"] == name
            assert (s.attrs["ports"], s.attrs["unrolls"]) == (2, 2)
        lower, comp, warm, reps = kids
        # one clock: each stage opens where the previous one closed
        assert lower.end == comp.start and comp.end == warm.start \
            and warm.end == reps.start
        assert "refused" not in lower.attrs and "refused" not in comp.attrs
        assert comp.attrs["cache"] in ("off", "hit", "miss")
        assert 0 < reps.attrs["best_s"] <= reps.end - reps.start
    assert oracle.stats["timed"] == 2


def test_lowering_phases_never_exceed_the_lower_span():
    _, tracer = _live_traced_ledger()
    lowers = tracer.spans("pallas.lower")
    assert len(lowers) == 3
    for s in lowers:
        assert s.attrs["trace_s"] >= 0 and s.attrs["mlir_s"] >= 0
        assert s.attrs["trace_s"] + s.attrs["mlir_s"] <= s.end - s.start
    timed = [s for s in lowers if "refused" not in s.attrs]
    assert all(s.attrs["trace_s"] > 0 and s.attrs["mlir_s"] > 0
               for s in timed)


def test_a_point_refused_at_lowering_has_only_its_lower_span():
    _, tracer = _live_traced_ledger()
    [point] = [s for s in tracer.spans("tool.point")
               if s.attrs["component"] == "gradient"]
    kids = [s for s in tracer.spans() if s.parent_id == point.span_id]
    assert [s.name for s in kids] == ["pallas.lower"]
    assert kids[0].attrs["refused"].startswith(
        "lowering: ValueError: block shape")


def test_counters_share_the_spans_clock_reads():
    oracle, tracer = _live_traced_ledger()
    st = oracle.stats

    def total(name):
        return sum(s.end - s.start for s in tracer.spans(name)
                   if "refused" not in s.attrs)
    assert 0 < st["lower_s"] <= st["compile_s"]
    assert st["lower_s"] == pytest.approx(total("pallas.lower"))
    assert st["compile_s"] == pytest.approx(total("pallas.lower")
                                            + total("pallas.compile"))
    assert st["timed_s"] == pytest.approx(total("pallas.reps"))


def test_cache_hits_and_misses_count_the_compiles_that_asked_the_cache(
        persistent_cache):
    import jax
    import jax.numpy as jnp
    from repro.core import Tracer, WallClock
    tracer = Tracer(WallClock())
    stats = []
    for _ in range(2):               # a new program each time, same HLO
        spec = _spec_with("grayscale", (jax.jit(lambda x: x * 3.0 - 1.0),
                                        (jnp.ones((8, 128)),)))
        oracle = PallasOracle({"grayscale": spec}, interpret=True)
        OracleLedger(oracle, tracer=tracer).synthesize(
            "grayscale", unrolls=2, ports=2)
        stats.append(oracle.stats)
    assert [s.attrs["cache"] for s in tracer.spans("pallas.compile")] \
        == ["miss", "hit"]
    assert [(s["cache_hits"], s["cache_misses"]) for s in stats] \
        == [(0, 1), (1, 0)]


def test_without_a_persistent_cache_every_compile_reads_off(
        compile_cache_at):
    with compile_cache_at(None):
        oracle, tracer = _live_traced_ledger()
    assert {s.attrs["cache"] for s in tracer.spans("pallas.compile")} \
        == {"off"}
    assert oracle.stats["cache_hits"] == oracle.stats["cache_misses"] == 0


def test_untraced_injected_timer_opens_no_span_and_keeps_the_walls():
    from repro.core import NULL_TRACER, Tracer, WallClock
    sub, _ = _small()
    plain = PallasOracle(sub, timer=_fake_timer, interpret=True)
    assert plain.tracer is NULL_TRACER
    tracer = Tracer(WallClock())
    traced = OracleLedger(PallasOracle(sub, timer=_fake_timer,
                                       interpret=True), tracer=tracer)
    for p, u in ((1, 8), (2, 4), (4, 2)):
        a = plain.synthesize("gradient", unrolls=u, ports=p)
        b = traced.synthesize("gradient", unrolls=u, ports=p)
        assert a.detail["wall_s"] == b.detail["wall_s"] \
            == _fake_timer("gradient", p, u, None)
    assert NULL_TRACER.spans() == []
    assert not [s for s in tracer.spans() if s.name.startswith("pallas.")]
    assert plain.stats["compile_s"] == plain.stats["lower_s"] == 0


def test_compile_events_count_only_the_outermost_lowering_phase():
    from repro.launch.compile_cache import CompileEvents
    trace = "/jax/core/compile/jaxpr_trace_duration"
    mlir = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    ev = CompileEvents()
    ev._phase_end(trace, 0.0, 0.5)         # opened before the listener
    ev._phase_start(trace, 10.0)
    ev._phase_start(trace, 11.0)           # a helper traced inside
    ev._phase_end(trace, 11.0, 12.0)
    ev._phase_end(trace, 10.0, 13.0)
    ev._phase_start(mlir, 20.0)
    ev._phase_start(trace, 21.0)           # tracing inside MLIR lowering
    ev._phase_end(trace, 21.0, 22.0)
    ev._phase_end(mlir, 20.0, 24.0)
    ev._phase_start("/jax/other", 1.0)     # other events are ignored
    snap = ev.snapshot()
    assert (snap["trace_s"], snap["mlir_s"]) == (0.5 + 3.0, 4.0)
