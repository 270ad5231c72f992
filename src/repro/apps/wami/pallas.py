"""The WAMI stages as a measured PallasOracle backend (DESIGN.md §2).

Binds the knob-parameterized Pallas kernels under ``repro.kernels`` to
the COSMOS component names, registers WAMI with the App/Backend
registry (:mod:`repro.core.registry`), and keeps the classic session
constructors as thin wrappers over ``build_session("wami", "pallas")``:

  * seven stages are priced by *running* their kernel on a PLM-sized
    tile (``ports`` -> lane-bank grid columns, ``unrolls`` -> rows per
    grid step): debayer, grayscale, gradient, steepest-descent, Hessian,
    warp, change detection;
  * the 6x6 matrix stages (``sd_update``, ``matrix_*``) have no kernel
    worth measuring — a (6, 6) problem never leaves one VPU tile — and
    fall back to the analytical tool inside the same oracle, so the
    full Fig. 8 TMG explores end-to-end;
  * in CI there is no TPU and interpret-mode wall clocks are noise, so
    the default mode replays the recordings checked in under
    ``artifacts/measurements/`` through a
    :class:`~repro.core.pallas_oracle.MeasurementSet` (regenerate:
    ``python examples/wami_pallas.py --record [--tile N]``, which times
    the interpreter on a CPU and the compiled kernels on a TPU, each
    device kind into its own file).

Inputs are baked deterministically per tile size so that record and
replay price the same physical workload; each knob point is one jitted
program (ops wrapper + ``pallas_call``) over those inputs.
"""

from __future__ import annotations

import functools
import os
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from ...core.hlsim import HLSTool
from ...core.pallas_oracle import (MeasurementSet, MeasurementStore,
                                   PallasKernelSpec, PallasOracle,
                                   live_device_kind, open_recording,
                                   recording_file)
from ...core.plm.units import UnitSystem, fit_unit_system
from ...core.registry import App, build_session, register_app
from ...core.session import ExplorationSession
from ...launch.compile_cache import PointProgram
from ...kernels import (wami_change_det, wami_debayer, wami_gradient,
                        wami_grayscale, wami_steep, wami_warp)
from . import components as C
from .knobs import WAMI_TILE_SIZES
from .pipeline import (MATRIX_INV_LATENCY_S, wami_hls_tool,
                       wami_knob_spaces, wami_plm_planner, wami_tmg)

__all__ = ["wami_pallas_components", "wami_pallas_oracle",
           "wami_pallas_session", "wami_unit_system", "wami_plm_session",
           "wami_measurement_set", "wami_parity_cases",
           "default_measurement_path", "WAMI_RECORDED_TILES"]

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".."))

# tiles with a recording checked in under artifacts/measurements/ —
# interpret-mode walls, one store file per tile (ROADMAP: multi-tile
# recordings); sessions load only the native 128 by default so legacy
# walks keep their exact fallback-priced tile axis
WAMI_RECORDED_TILES = (64, 128, 256)


def default_measurement_path(tile: int = C.TILE,
                             device_kind: str = "interpret") -> str:
    """The recording file for (tile, device kind), e.g.
    ``wami_pallas_tile128.json`` (interpret) or
    ``wami_pallas_tile128.tpu_v5_lite.json``."""
    return os.path.join(_REPO_ROOT, "artifacts", "measurements",
                        recording_file(f"wami_pallas_tile{tile}",
                                       device_kind))


def wami_measurement_set(tiles: Sequence[int] = (C.TILE,),
                         *, flush_every: int = 0) -> MeasurementSet:
    """The checked-in WAMI recordings for ``tiles``, as one routing set."""
    return MeasurementSet.load(
        (default_measurement_path(t) for t in tiles),
        flush_every=flush_every)


def wami_pallas_components(tile: int = C.TILE
                           ) -> Dict[str, PallasKernelSpec]:
    """PallasKernelSpec per measurable WAMI stage, on a (tile, tile)
    PLM-resident frame tile with deterministic baked inputs."""
    key = jax.random.PRNGKey(42)
    ks = jax.random.split(key, 8)
    bayer = jax.random.uniform(ks[0], (tile, tile)) * 1023.0
    rgb = jax.random.uniform(ks[1], (tile, tile, 3)) * 255.0
    gray = jax.random.uniform(ks[2], (tile, tile)) * 255.0
    gx = jax.random.normal(ks[3], (tile, tile))
    gy = jax.random.normal(ks[4], (tile, tile))
    sd = jax.random.normal(ks[5], (tile, tile, 6))
    p = jnp.array([0.01, -0.005, 0.8, 0.004, -0.01, -0.6], jnp.float32)
    mu = gray[..., None] + jax.random.normal(ks[6], (tile, tile, 3)) * 8.0
    var = jnp.full((tile, tile, 3), 36.0, jnp.float32)
    w = jnp.full((tile, tile, 3), 1.0 / 3.0, jnp.float32)

    def bake(fn: Callable, *args) -> Callable:
        def build(ports: int, unrolls: int, interpret: bool):
            return PointProgram(jax.jit(functools.partial(
                fn, ports=ports, unrolls=unrolls, use_pallas=True,
                interpret=interpret))), args
        return build

    shape = (tile, tile)
    return {
        "debayer": PallasKernelSpec(
            name="debayer", shape=shape,
            build=bake(wami_debayer.debayer, bayer),
            vmem_bytes=wami_debayer.vmem_bytes,
            grid_steps=wami_debayer.grid_steps, n_in=9, n_out=3),
        "grayscale": PallasKernelSpec(
            name="grayscale", shape=shape,
            build=bake(wami_grayscale.grayscale, rgb),
            vmem_bytes=wami_grayscale.vmem_bytes,
            grid_steps=wami_grayscale.grid_steps, n_in=3, n_out=1),
        "gradient": PallasKernelSpec(
            name="gradient", shape=shape,
            build=bake(wami_gradient.gradient, gray),
            vmem_bytes=wami_gradient.vmem_bytes,
            grid_steps=wami_gradient.grid_steps, n_in=4, n_out=2),
        "steep_descent": PallasKernelSpec(
            name="steep_descent", shape=shape,
            build=bake(wami_steep.steepest_descent, gx, gy),
            vmem_bytes=wami_steep.vmem_bytes,
            grid_steps=wami_steep.grid_steps, n_in=2, n_out=6),
        "hessian": PallasKernelSpec(
            name="hessian", shape=shape,
            build=bake(wami_steep.hessian, sd),
            vmem_bytes=wami_steep.hessian_vmem_bytes,
            grid_steps=wami_steep.grid_steps, n_in=6, n_out=1),
        "warp": PallasKernelSpec(
            name="warp", shape=shape,
            build=bake(wami_warp.warp_affine, gray, p),
            vmem_bytes=wami_warp.vmem_bytes,
            grid_steps=wami_warp.grid_steps, n_in=6, n_out=1),
        "change_det": PallasKernelSpec(
            name="change_det", shape=shape,
            build=bake(wami_change_det.change_detection, gray, mu, var, w),
            vmem_bytes=wami_change_det.vmem_bytes,
            grid_steps=wami_change_det.grid_steps, n_in=10, n_out=10),
    }


def wami_parity_cases(tile: int = C.TILE):
    """(name, pallas_fn, oracle_fn, args) per WAMI stage kernel — the
    interpret-mode parity gate's work list (kernels_micro)."""
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 7)
    bayer = jax.random.uniform(ks[0], (tile, tile)) * 1023.0
    rgb = jax.random.uniform(ks[1], (tile, tile, 3)) * 255.0
    gray = jax.random.uniform(ks[2], (tile, tile)) * 255.0
    gx = jax.random.normal(ks[3], (tile, tile))
    gy = jax.random.normal(ks[4], (tile, tile))
    sd = jax.random.normal(ks[5], (tile, tile, 6))
    # shear terms small enough that every source fraction stays in
    # ~[0.3, 0.7]: the floor() cell choice is then identical between the
    # two compiled programs, so parity is exact instead of flipping
    # gather cells at integer boundaries
    p = jnp.array([1 / 1024, -1 / 2048, 0.5, 1 / 2048, -1 / 1024, 0.5],
                  jnp.float32)
    mu = gray[..., None] + jax.random.normal(ks[6], (tile, tile, 3)) * 8.0
    var = jnp.full((tile, tile, 3), 36.0)
    w = jnp.full((tile, tile, 3), 1.0 / 3.0)
    return [
        ("wami_debayer", wami_debayer.debayer, wami_debayer.debayer_oracle,
         (bayer,)),
        ("wami_grayscale", wami_grayscale.grayscale,
         wami_grayscale.grayscale_oracle, (rgb,)),
        ("wami_gradient", wami_gradient.gradient,
         wami_gradient.gradient_oracle, (gray,)),
        ("wami_steep", wami_steep.steepest_descent,
         wami_steep.steepest_descent_oracle, (gx, gy)),
        ("wami_hessian", wami_steep.hessian, wami_steep.hessian_oracle,
         (sd,)),
        ("wami_warp", wami_warp.warp_affine, wami_warp.warp_affine_oracle,
         (gray, p)),
        ("wami_change_det", wami_change_det.change_detection,
         wami_change_det.change_detection_oracle, (gray, mu, var, w)),
    ]


def wami_pallas_oracle(mode: str = "replay", *, tile: int = C.TILE,
                       store: Optional[MeasurementStore] = None,
                       store_path: Optional[str] = None,
                       measurements: Optional[MeasurementSet] = None,
                       fallback: Optional[HLSTool] = None,
                       interpret: bool = False,
                       flush_every: int = 16,
                       timer=None, **kwargs) -> PallasOracle:
    """The measured WAMI oracle.  Default: deterministic replay from the
    checked-in interpret recording (CI-safe, no TPU); a replay of
    another file reads that file's device kind.  A live drive
    (``measure``/``record``) compiles for the TPU unless ``interpret``
    is asked for, and records into its device kind's own file.  Record
    mode flushes the store every ``flush_every`` timings through the
    atomic rename protocol and resumes from whatever an interrupted
    campaign already flushed — killed recordings never re-pay for timed
    points."""
    # a replay reads its file's device kind; a live drive its own
    live_kind = (None if mode == "replay" else
                 "interpret" if interpret else live_device_kind())
    if measurements is None and mode in ("record", "replay"):
        if store is not None:
            measurements = MeasurementSet.from_store(store, tile=tile)
        else:
            kind = live_kind or "interpret"
            measurements = open_recording(
                store_path or default_measurement_path(tile, kind),
                mode=mode, tile=tile, device_kind=kind,
                flush_every=flush_every)
    return PallasOracle(wami_pallas_components(tile), mode=mode,
                        measurements=measurements,
                        components_factory=wami_pallas_components,
                        fallback=fallback or wami_hls_tool(),
                        interpret=interpret, device_kind=live_kind,
                        timer=timer, native_tile=tile,
                        record_hint=f"re-record with `python examples/"
                                    f"wami_pallas.py --record --tile {tile}`",
                        **kwargs)


def wami_pallas_session(delta: float = 0.25, *, mode: str = "replay",
                        tile: int = C.TILE, workers: int = 1,
                        oracle: Optional[PallasOracle] = None,
                        **kwargs) -> ExplorationSession:
    """An :class:`ExplorationSession` over the WAMI TMG driven by the
    measured backend — ``build_session("wami", "pallas")`` with the
    classic signature (same phases, ledger semantics, and knob spaces
    as :func:`~repro.apps.wami.pipeline.wami_session`)."""
    tool = oracle or wami_pallas_oracle(mode, tile=tile)
    return build_session("wami", "pallas", tool=tool, delta=delta,
                         workers=workers, **kwargs)


def wami_unit_system(tile: int = C.TILE,
                     store: Optional[MeasurementStore] = None
                     ) -> UnitSystem:
    """Exchange rates fitted from the checked-in recording: per-component
    latency scales plus one global bytes-per-mm² area rate.  Derived
    from the store's sorted entries and the deterministic VMEM/area
    formulas — byte-reproducible on any machine holding the recording."""
    store = store or MeasurementStore.load(default_measurement_path(tile))
    return fit_unit_system(store, wami_pallas_components(tile),
                           wami_hls_tool())


def wami_plm_session(delta: float = 0.25, *, tile: int = C.TILE,
                     tile_sizes: Optional[tuple] = (64, 128),
                     measured_tiles: Sequence[int] = (C.TILE,),
                     workers: int = 1, share_plm: bool = True,
                     **kwargs) -> ExplorationSession:
    """The memory-co-design WAMI drive on the checked-in recordings.

    Everything the PLM subsystem adds, wired together (docs/memory.md):

      * the tile knob is a third axis on the tile-scaled components —
        tiles with a recording in ``measured_tiles`` replay measured
        walls through the :class:`MeasurementSet`, other tiles are
        priced by the unit-calibrated analytical fallback
        (``missing="fallback"`` also covers mapped unrolls the recorded
        walk never touched, so the drive stays deterministic and
        machine-free);
      * the fallback reports measured-axis latencies and VMEM-byte areas
        (:func:`wami_unit_system`), so the mixed system front is
        unit-clean;
      * the map phase prices the memory subsystem through the PLM
        planner: the TMG certifies the six LK-loop components mutually
        exclusive and their PLMs become one shared multi-bank memory.

    ``measured_tiles`` defaults to just the native 128 so the classic
    drive stays byte-identical to the single-store era; pass e.g.
    ``(64, 128)`` to replay the tile-64 recording instead of pricing
    that ladder through the fallback (WAMI_RECORDED_TILES lists what is
    on disk).  ``tile_sizes`` defaults to (64, 128) rather than the
    analytical variant's full ``WAMI_TILE_SIZES`` for the same reason:
    the axis stays anchored where measurements exist.
    """
    store = MeasurementStore.load(default_measurement_path(tile))
    units = wami_unit_system(tile, store=store)
    fallback = units.calibrated(wami_hls_tool())
    measurements = MeasurementSet.from_store(store, tile=tile)
    for extra in measured_tiles:
        if extra != tile:
            measurements.add(MeasurementStore.load(
                default_measurement_path(extra)))
    oracle = PallasOracle(wami_pallas_components(tile), mode="replay",
                          measurements=measurements,
                          components_factory=wami_pallas_components,
                          fallback=fallback,
                          native_tile=tile, missing="fallback",
                          record_hint=f"re-record with `python examples/"
                                      f"wami_pallas.py --record --tile "
                                      f"{tile}`")
    # an explicitly empty tile_sizes means "no tile axis" — pass () so
    # build_session does NOT substitute the app's measured default
    return build_session("wami", "pallas", tool=oracle, delta=delta,
                         share_plm=share_plm,
                         tile_sizes=tuple(tile_sizes or ()),
                         workers=workers, **kwargs)


# ----------------------------------------------------------------------
# registration: `get_app("wami")` resolves to this record
# ----------------------------------------------------------------------
register_app(App(
    name="wami",
    description="WAMI Lucas-Kanade + change detection (the paper's "
                "Fig. 8 case study): 12 HLS components + 1 software stage",
    tmg=wami_tmg,
    knob_spaces=wami_knob_spaces,
    analytical=wami_hls_tool,
    fixed={"matrix_inv": MATRIX_INV_LATENCY_S},
    delta=0.25,
    kernel_specs=wami_pallas_components,
    native_tile=C.TILE,
    measurement_path=default_measurement_path,
    recorded_tiles=WAMI_RECORDED_TILES,
    default_tiles=(C.TILE,),
    calibrated_fallback=lambda store=None: wami_unit_system(
        store=store).calibrated(wami_hls_tool()),
    record_hint="re-record with `python examples/wami_pallas.py "
                "--record [--tile N]`",
    plm_planner=wami_plm_planner,
    plm_tile_sizes=WAMI_TILE_SIZES,
    plm_tile_sizes_measured=(64, 128),
    parity_cases=wami_parity_cases,
))
