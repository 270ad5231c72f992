"""The fleet application: a hybrid attention + SSD serving pipeline.

The first non-WAMI workload to run the full COSMOS path (characterize ->
LP -> map -> PLM plan), registered as ``get_app("fleet")``.  The system
is a two-stage ML pipeline — a flash-attention stage feeding an SSD
(Mamba2) scan stage, the attention/SSM hybrid split — and it is priced
by BOTH oracle families:

  * **analytical** — :class:`~repro.core.xlatool.XLATool` over
    (ModelConfig, ShapeSpec) stages: ``ports`` is the stage's fleet
    share (chips), ``unrolls`` the inverse microbatching, cost the
    total HBM claimed (the paper's area);
  * **pallas (calibrated-measured)** — the same two stages as
    :class:`~repro.core.pallas_oracle.PallasKernelSpec`s over the real
    ``kernels/flash_attention`` and ``kernels/ssd_scan`` Pallas
    kernels.  ``ports`` maps onto the kernels' *parallel* grid
    dimension (Q-block columns for attention, head lanes for the SSD
    scan) and ``unrolls`` onto the sequential block depth (KV rows /
    chunk length per grid step) — the same lane-bank reading DESIGN.md
    §2 gives the WAMI kernels.  Interpret-mode walls are recorded under
    ``artifacts/measurements/`` (a chip's walls go to a file of their
    own per device kind) and the XLA roofline's constants are
    fitted to them through :mod:`repro.core.calibrate`
    (:func:`fleet_calibrated_tool`), so the analytical fallback prices
    on the measured axes.

The pipeline TMG uses single-buffer channels: adjacent stages serialize
(Fig. 3 with buffers=1), which the PLM planner's TMG certificate turns
into a shared-memory opportunity — the two stages may time-multiplex
one VMEM pool, exactly the cross-component sharing WAMI's LK loop gets.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ...configs import SHAPES, get_config
from ...core.knobs import KnobSpace
from ...core.pallas_oracle import (MeasurementSet, MeasurementStore,
                                   PallasKernelSpec, PallasOracle,
                                   live_device_kind, open_recording,
                                   recording_file)
from ...core.plm.planner import PLMPlanner
from ...core.plm.units import UnitSystem, fit_unit_system
from ...core.registry import App, build_session, register_app
from ...core.session import ExplorationSession
from ...core.tmg import TMG, pipeline_tmg
from ...core.xlatool import XLATool
from ...kernels.flash_attention import mha, mha_ref
from ...kernels.ssd_scan import ssd, ssd_oracle
from ...launch.compile_cache import PointProgram

__all__ = ["FLASH_S", "FLASH_D", "FLASH_HEADS", "SSD_S", "SSD_P", "SSD_N",
           "SSD_MAX_HEADS", "fleet_tmg", "fleet_knob_spaces",
           "fleet_xla_tool", "fleet_kernel_specs", "fleet_pallas_oracle",
           "fleet_calibrated_tool", "fleet_unit_system", "fleet_session",
           "fleet_parity_cases", "default_measurement_path"]

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".."))

# measured-kernel geometry: small enough that interpret-mode recording
# is minutes, large enough that every knob point changes the grid
FLASH_S = 128          # Sq == Skv tokens per attention launch
FLASH_D = 64           # head dim
FLASH_HEADS = 2        # query heads (GQA 2:1 onto one KV head)
SSD_S = 256            # scan length per launch
SSD_P = 64             # SSD head dim
SSD_N = 64             # SSD state dim
SSD_MAX_HEADS = 8      # the ports axis: parallel head lanes

# analytical stage models: the attention stage prices as a gemma2-9b
# fleet share, the SSD stage as a mamba2-780m share, both on the
# train_4k shape cell (the fleet allocation problem of benchmarks/)
_FLEET_STAGES = {
    "flash_attention": ("gemma2-9b", 0),
    "ssd_scan": ("mamba2-780m", 0),
}


def default_measurement_path(tile: int = 0,
                             device_kind: str = "interpret") -> str:
    """One recording file per device kind for the fleet kernels (no
    tile axis: the kernel geometry is fixed, so everything keys under
    tile 0)."""
    return os.path.join(_REPO_ROOT, "artifacts", "measurements",
                        recording_file("fleet_pallas", device_kind))


# ----------------------------------------------------------------------
# system model + knob spaces
# ----------------------------------------------------------------------
def fleet_tmg(frames_in_flight: int = 2) -> TMG:
    """Single-buffer two-stage pipeline: adjacent stages serialize, so
    the TMG's one-token cycles certify them mutually exclusive and the
    PLM planner may pack both stages onto one shared VMEM pool."""
    return pipeline_tmg(["flash_attention", "ssd_scan"], buffers=1,
                        frames_in_flight=frames_in_flight)


def fleet_knob_spaces() -> Dict[str, KnobSpace]:
    """One knob space for both stages, honest for both backends: ports
    up to 4 (fleet shares / parallel grid lanes), unrolls up to 8
    (microbatch ladder / sequential block depth)."""
    return {n: KnobSpace(clock_ns=1.0, max_ports=4, max_unrolls=8)
            for n in _FLEET_STAGES}


def fleet_xla_tool() -> XLATool:
    """The analytical fleet oracle (roofline prices, HBM-byte areas)."""
    return XLATool({name: (get_config(cfg), SHAPES[shape])
                    for name, (cfg, shape) in _FLEET_STAGES.items()})


# ----------------------------------------------------------------------
# measured kernel specs
# ----------------------------------------------------------------------
def _flash_block_kv(unrolls: int) -> int:
    return 16 * unrolls


def flash_vmem_bytes(H: int, W: int, *, ports: int, unrolls: int,
                     dtype_bytes: int = 4) -> int:
    """Per-grid-step VMEM: q/o/acc tiles of (Sq/ports, d), k/v tiles of
    (16*unrolls, d), plus the (m, l) softmax state rows."""
    bq = W // ports
    bkv = _flash_block_kv(unrolls)
    return dtype_bytes * (3 * bq * FLASH_D + 2 * bkv * FLASH_D + 2 * bq)


def flash_grid_steps(H: int, W: int, *, ports: int, unrolls: int) -> int:
    return FLASH_HEADS * ports * max(1, H // _flash_block_kv(unrolls))


def _ssd_chunk(unrolls: int) -> int:
    return 8 * unrolls


def ssd_vmem_bytes(H: int, W: int, *, ports: int, unrolls: int,
                   dtype_bytes: int = 4) -> int:
    """Per-head-lane VMEM per chunk step: x/y tiles (chunk, P), B/C
    tiles (chunk, N), the dt row, and the carried (P, N) state."""
    chunk = _ssd_chunk(unrolls)
    return dtype_bytes * (2 * chunk * SSD_P + 2 * chunk * SSD_N + chunk
                          + 2 * SSD_P * SSD_N)


def ssd_grid_steps(H: int, W: int, *, ports: int, unrolls: int) -> int:
    return ports * max(1, H // _ssd_chunk(unrolls))


def _fleet_inputs():
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (1, FLASH_S, FLASH_HEADS, FLASH_D))
    k = jax.random.normal(ks[1], (1, FLASH_S, 1, FLASH_D))
    v = jax.random.normal(ks[2], (1, FLASH_S, 1, FLASH_D))
    x = jax.random.normal(ks[3], (1, SSD_S, SSD_MAX_HEADS, SSD_P))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (1, SSD_S, SSD_MAX_HEADS)))
    A = -jnp.exp(jax.random.normal(ks[5], (SSD_MAX_HEADS,)) * 0.3)
    Bm = jax.random.normal(ks[6], (1, SSD_S, SSD_N)) * 0.3
    Cm = jax.random.normal(ks[7], (1, SSD_S, SSD_N)) * 0.3
    return q, k, v, x, dt, A, Bm, Cm


def fleet_kernel_specs(tile: int = 0) -> Dict[str, PallasKernelSpec]:
    """The two fleet stages as measured kernel specs (deterministic
    baked inputs; ``tile`` is accepted for the components-factory
    protocol but the fleet geometry is fixed)."""
    q, k, v, x, dt, A, Bm, Cm = _fleet_inputs()

    def build_flash(ports: int, unrolls: int, interpret: bool):
        return PointProgram(jax.jit(functools.partial(
            mha, causal=True, block_q=FLASH_S // ports,
            block_kv=_flash_block_kv(unrolls), use_pallas=True,
            interpret=interpret))), (q, k, v)

    def build_ssd(ports: int, unrolls: int, interpret: bool):
        return PointProgram(jax.jit(functools.partial(
            ssd, chunk=_ssd_chunk(unrolls), use_pallas=True,
            interpret=interpret))), (x[:, :, :ports, :], dt[:, :, :ports],
                                    A[:ports], Bm, Cm)

    return {
        "flash_attention": PallasKernelSpec(
            name="flash_attention", shape=(FLASH_S, FLASH_S),
            build=build_flash, vmem_bytes=flash_vmem_bytes,
            grid_steps=flash_grid_steps, n_in=3, n_out=1),
        "ssd_scan": PallasKernelSpec(
            name="ssd_scan", shape=(SSD_S, SSD_MAX_HEADS),
            build=build_ssd, vmem_bytes=ssd_vmem_bytes,
            grid_steps=ssd_grid_steps, n_in=4, n_out=2),
    }


def fleet_parity_cases(tile: int = FLASH_S):
    """(name, knobbed_fn, oracle_fn, args) for the parity gate: the
    fleet kernels behind the same (ports, unrolls) calling convention
    the WAMI cases use.  ``tile`` is the attention token count; the scan
    runs SSD_S / FLASH_S times longer, so the default checks both
    kernels at their measured geometry (smoke runs shrink it)."""
    S = max(32, tile)
    S_ssd = S * SSD_S // FLASH_S
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (1, S, FLASH_HEADS, FLASH_D))
    k = jax.random.normal(ks[1], (1, S, 1, FLASH_D))
    v = jax.random.normal(ks[2], (1, S, 1, FLASH_D))
    x = jax.random.normal(ks[3], (1, S_ssd, SSD_MAX_HEADS, SSD_P))
    dt = jax.nn.softplus(jax.random.normal(ks[4],
                                           (1, S_ssd, SSD_MAX_HEADS)))
    A = -jnp.exp(jax.random.normal(ks[5], (SSD_MAX_HEADS,)) * 0.3)
    Bm = jax.random.normal(ks[6], (1, S_ssd, SSD_N)) * 0.3
    Cm = jax.random.normal(ks[7], (1, S_ssd, SSD_N)) * 0.3

    def mha_knobbed(q, k, v, *, ports, unrolls, use_pallas, interpret):
        return mha(q, k, v, causal=True, block_q=max(1, S // ports),
                   block_kv=_flash_block_kv(unrolls),
                   use_pallas=use_pallas, interpret=interpret)

    def mha_oracle(q, k, v):
        return mha_ref(q, k, v, causal=True)

    def ssd_knobbed(x, dt, A, Bm, Cm, *, ports, unrolls, use_pallas,
                    interpret):
        # parity output must be knob-independent: ports only replicates
        # head lanes in the measured spec, so the check runs all heads
        # and lets unrolls (the chunk length) exercise the kernel
        return ssd(x, dt, A, Bm, Cm, chunk=_ssd_chunk(unrolls),
                   use_pallas=use_pallas, interpret=interpret)

    return [
        ("flash_attention", mha_knobbed, mha_oracle, (q, k, v)),
        ("ssd_scan", ssd_knobbed, ssd_oracle, (x, dt, A, Bm, Cm)),
    ]


# ----------------------------------------------------------------------
# oracles + calibration
# ----------------------------------------------------------------------
def fleet_pallas_oracle(mode: str = "replay", *,
                        measurements: Optional[MeasurementSet] = None,
                        fallback=None, interpret: bool = False,
                        flush_every: int = 16, missing: str = "fallback",
                        timer=None, **kwargs) -> PallasOracle:
    """The measured fleet oracle.  Default: deterministic replay of the
    checked-in interpret-mode recording with the *calibrated* XLA tool
    as fallback — the calibrated-measured backend of ``get_app("fleet")``.
    A live drive compiles for the TPU unless ``interpret`` is asked for,
    and records into its device kind's own file."""
    # a replay reads its file's device kind; a live drive its own
    live_kind = (None if mode == "replay" else
                 "interpret" if interpret else live_device_kind())
    if measurements is None and mode in ("record", "replay"):
        kind = live_kind or "interpret"
        measurements = open_recording(default_measurement_path(0, kind),
                                      mode=mode, tile=0, device_kind=kind,
                                      flush_every=flush_every)
    if fallback is None:
        if mode == "replay" and missing == "fallback":
            fallback = fleet_calibrated_tool()
        else:
            fallback = fleet_xla_tool()
    return PallasOracle(fleet_kernel_specs(), mode=mode,
                        measurements=measurements,
                        components_factory=fleet_kernel_specs,
                        fallback=fallback, interpret=interpret,
                        device_kind=live_kind,
                        missing=missing if mode == "replay" else "error",
                        record_hint="re-record with `python benchmarks/"
                                    "fleet_dse.py --record`",
                        timer=timer, **kwargs)


def fleet_unit_system(store: Optional[MeasurementStore] = None
                      ) -> UnitSystem:
    """Exchange rates fitted from the fleet recording: per-stage latency
    scales (measured wall / roofline model) and one global HBM-bytes ->
    VMEM-bytes area rate — the :mod:`repro.core.calibrate` fit applied
    to the XLA tool."""
    store = store or MeasurementStore.load(default_measurement_path())
    return fit_unit_system(store, fleet_kernel_specs(), fleet_xla_tool())


def fleet_calibrated_tool(store: Optional[MeasurementStore] = None):
    """The calibrated-measured analytical fallback: the XLA roofline
    re-scaled onto the measured latency axis and VMEM-byte cost unit."""
    return fleet_unit_system(store).calibrated(fleet_xla_tool())


def fleet_session(delta: float = 0.3, *, backend: str = "analytical",
                  workers: int = 1, share_plm: bool = False,
                  **kwargs) -> ExplorationSession:
    """``build_session("fleet", backend)`` with the fleet defaults."""
    tool = None
    if backend == "pallas":
        tool = fleet_pallas_oracle("replay")
    return build_session("fleet", backend, tool=tool, delta=delta,
                         workers=workers, share_plm=share_plm, **kwargs)


# ----------------------------------------------------------------------
# registration: `get_app("fleet")` resolves to this record
# ----------------------------------------------------------------------
register_app(App(
    name="fleet",
    description="hybrid attention + SSD serving pipeline: flash_attention "
                "-> ssd_scan, priced as fleet shares (XLA roofline) or "
                "measured Pallas kernels",
    tmg=fleet_tmg,
    knob_spaces=lambda **_kw: fleet_knob_spaces(),
    analytical=fleet_xla_tool,
    fixed={},
    delta=0.3,
    kernel_specs=fleet_kernel_specs,
    native_tile=0,
    measurement_path=default_measurement_path,
    recorded_tiles=(0,),
    default_tiles=(0,),
    calibrated_fallback=fleet_calibrated_tool,
    record_hint="re-record with `python benchmarks/fleet_dse.py --record`",
    plm_planner=lambda: PLMPlanner(fleet_tmg()),
    parity_cases=fleet_parity_cases,
))
