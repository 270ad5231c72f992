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
    dimension and ``unrolls`` onto the sequential block depth (KV rows /
    chunk length per grid step) — the same lane-bank reading DESIGN.md
    §2 gives the WAMI kernels.  Interpret-mode walls are recorded under
    ``artifacts/measurements/`` (a chip's walls go to a file of their
    own per device kind) and the XLA roofline's constants are
    fitted to them through :mod:`repro.core.calibrate`
    (:func:`fleet_calibrated_tool`), so the analytical fallback prices
    on the measured axes.

Everything shape-dependent reads a :class:`FleetGeometry` record:
tokens per launch, query/KV heads and head dim, SSD heads, P, N, the
knob -> tiling map and the analytical stand-ins.  :func:`fleet_app` turns a geometry into a registered app;
two are registered:

  * ``fleet`` (:data:`FLEET`) — a small geometry, so interpret-mode
    recording is minutes: attention ``ports`` = Q-block columns, the
    SSD's ``ports`` = the head lanes it computes (so its work grows
    with the knob);
  * ``fleet-zamba2-7b`` (:data:`ZAMBA2_7B_TP4`) — one hybrid layer of
    Zamba2-7B (:mod:`.zamba2_7b`) at its published widths, as one chip
    of a 4-way tensor-parallel deployment computes it: 4096 tokens per
    launch, attention over 8 heads of 224, the scan over 28 heads
    (P 64, N 64, one B/C group).  Every knob point computes the same
    chip-share outputs from the same inputs: ``ports`` is the heads per
    grid step and ``unrolls`` the sequential block (32 KV rows, or 32
    scan tokens, per unroll).  Its recording is the chip's alone
    (``*.tpu_v5_lite.json``); full-size kernels are not interpreted,
    so it has no parity cases and ``list_apps()`` leaves it out.

The pipeline TMG uses single-buffer channels: adjacent stages serialize
(Fig. 3 with buffers=1), which the PLM planner's TMG certificate turns
into a shared-memory opportunity — the two stages may time-multiplex
one VMEM pool, exactly the cross-component sharing WAMI's LK loop gets.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ...configs import SHAPES, ModelConfig, get_config
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
from . import zamba2_7b

__all__ = ["FLASH_S", "FLASH_D", "FLASH_HEADS", "SSD_S", "SSD_P", "SSD_N",
           "SSD_MAX_HEADS", "FleetGeometry", "FLEET", "ZAMBA2_7B_TP4",
           "fleet_app", "fleet_program",
           "fleet_input_shapes", "fleet_tmg", "fleet_knob_spaces",
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


@dataclass(frozen=True)
class FleetGeometry:
    """The shapes of one fleet app and its knob -> tiling map.

    With ``knob_independent`` every (ports, unrolls) point computes the
    whole geometry from the same inputs: ``ports`` is the heads per grid
    step of both kernels, ``unrolls`` the sequential block
    (``flash_kv_rows`` KV rows or ``ssd_chunk_rows`` scan tokens per
    unroll), and the attention's Q block is ``flash_block_q``.
    Otherwise (the ``fleet`` app) attention ``ports`` splits the tokens
    into Q-block columns and the SSD computes ``ports`` of its heads.
    ``vmem_limit_bytes`` is the scoped VMEM the kernels declare (None:
    the compiler's default).  ``stages`` are the (ModelConfig, shape)
    stand-ins the analytical tool prices the two stages as."""

    app: str
    stem: str                      # recording file stem
    flash_s: int                   # Sq == Skv tokens per attention launch
    q_heads: int
    kv_heads: int
    head_dim: int
    ssd_s: int                     # scan tokens per launch
    ssd_heads: int
    ssd_p: int
    ssd_n: int
    stages: Tuple[Tuple[ModelConfig, str], Tuple[ModelConfig, str]]
    knob_independent: bool = False
    flash_block_q: int = 0
    flash_kv_rows: int = 16
    ssd_chunk_rows: int = 8
    flash_max_ports: int = 4
    ssd_max_ports: int = 4
    max_unrolls: int = 8
    vmem_limit_bytes: Optional[int] = None
    recorded_kind: str = "interpret"    # device kind of its recording

    def flash_tiling(self, ports: int, unrolls: int) -> Dict[str, int]:
        """Heads per grid step, Q block and KV block of a knob point."""
        if self.knob_independent:
            return {"heads_per_step": ports, "block_q": self.flash_block_q,
                    "block_kv": self.flash_kv_rows * unrolls}
        return {"heads_per_step": 1, "block_q": self.flash_s // ports,
                "block_kv": self.flash_kv_rows * unrolls}

    def ssd_tiling(self, ports: int, unrolls: int) -> Dict[str, int]:
        """Heads computed, heads per grid step and chunk of a knob point."""
        chunk = self.ssd_chunk_rows * unrolls
        if self.knob_independent:
            return {"heads": self.ssd_heads, "heads_per_step": ports,
                    "chunk": chunk}
        return {"heads": ports, "heads_per_step": 1, "chunk": chunk}


FLEET = FleetGeometry(
    app="fleet", stem="fleet_pallas", flash_s=FLASH_S,
    q_heads=FLASH_HEADS, kv_heads=1, head_dim=FLASH_D, ssd_s=SSD_S,
    ssd_heads=SSD_MAX_HEADS, ssd_p=SSD_P, ssd_n=SSD_N,
    # the attention stage prices as a gemma2-9b fleet share, the SSD
    # stage as a mamba2-780m share, both on the train_4k shape cell
    # (the fleet allocation problem of benchmarks/)
    stages=((get_config("gemma2-9b"), "train_4k"),
            (get_config("mamba2-780m"), "train_4k")))


# one hybrid layer of Zamba2-7B as one chip of its 4-way tensor-parallel
# deployment computes it, a full-context prefill per launch
ZAMBA2_7B_TP4 = FleetGeometry(
    app="fleet-zamba2-7b", stem="fleet_zamba2_7b_pallas",
    flash_s=zamba2_7b.PUBLISHED["max_position_embeddings"],
    q_heads=zamba2_7b.CHIP_SHARE["q_heads"],
    kv_heads=zamba2_7b.CHIP_SHARE["kv_heads"],
    head_dim=zamba2_7b.PUBLISHED["attention_head_dim"],
    ssd_s=zamba2_7b.PUBLISHED["max_position_embeddings"],
    ssd_heads=zamba2_7b.CHIP_SHARE["ssd_heads"],
    ssd_p=zamba2_7b.PUBLISHED["mamba_headdim"],
    ssd_n=zamba2_7b.PUBLISHED["mamba_d_state"],
    stages=tuple((stage, "train_4k")
                 for stage in zamba2_7b.analytical_stages()),
    knob_independent=True, flash_block_q=128, flash_kv_rows=32,
    ssd_chunk_rows=32, flash_max_ports=8, ssd_max_ports=4,
    max_unrolls=8, vmem_limit_bytes=16 * 1024 * 1024,
    recorded_kind="TPU v5 lite")
# the scan kernel shares one B/C group, and its chunk stays within the
# published one
assert zamba2_7b.CHIP_SHARE["bc_groups"] == 1
assert (ZAMBA2_7B_TP4.ssd_chunk_rows * ZAMBA2_7B_TP4.max_unrolls
        <= zamba2_7b.PUBLISHED["chunk_size"])


def default_measurement_path(tile: int = 0, device_kind: Optional[str] = None,
                             geometry: FleetGeometry = FLEET) -> str:
    """One recording file per device kind for the fleet kernels (no
    tile axis: the kernel geometry is fixed, so everything keys under
    tile 0); the geometry's own recording kind by default."""
    return os.path.join(_REPO_ROOT, "artifacts", "measurements",
                        recording_file(geometry.stem,
                                       device_kind or geometry.recorded_kind))


# ----------------------------------------------------------------------
# system model + knob spaces
# ----------------------------------------------------------------------
def fleet_tmg(frames_in_flight: int = 2) -> TMG:
    """Single-buffer two-stage pipeline: adjacent stages serialize, so
    the TMG's one-token cycles certify them mutually exclusive and the
    PLM planner may pack both stages onto one shared VMEM pool."""
    return pipeline_tmg(["flash_attention", "ssd_scan"], buffers=1,
                        frames_in_flight=frames_in_flight)


def fleet_knob_spaces(geometry: FleetGeometry = FLEET
                      ) -> Dict[str, KnobSpace]:
    """The knob space of each stage, honest for both backends: ports
    (fleet shares / heads or lanes per grid step) and unrolls
    (microbatch ladder / sequential block depth)."""
    g = geometry
    return {"flash_attention": KnobSpace(clock_ns=1.0,
                                         max_ports=g.flash_max_ports,
                                         max_unrolls=g.max_unrolls),
            "ssd_scan": KnobSpace(clock_ns=1.0, max_ports=g.ssd_max_ports,
                                  max_unrolls=g.max_unrolls)}


def fleet_xla_tool(geometry: FleetGeometry = FLEET) -> XLATool:
    """The analytical fleet oracle (roofline prices, HBM-byte areas)."""
    shapes = {s.name: s for s in SHAPES}
    (attn, attn_shape), (scan, scan_shape) = geometry.stages
    return XLATool({"flash_attention": (attn, shapes[attn_shape]),
                    "ssd_scan": (scan, shapes[scan_shape])})


# ----------------------------------------------------------------------
# measured kernel specs
# ----------------------------------------------------------------------
def flash_vmem_bytes(H: int, W: int, *, ports: int, unrolls: int,
                     dtype_bytes: int = 4,
                     geometry: FleetGeometry = FLEET) -> int:
    """Per-grid-step VMEM: q/o/acc tiles of (heads per step, Q block,
    d), k/v tiles of (KV heads per step, KV block, d), plus the (m, l)
    softmax state rows."""
    t = geometry.flash_tiling(ports, unrolls)
    hb, bq, bkv = t["heads_per_step"], t["block_q"], t["block_kv"]
    kv_hb = max(1, hb * geometry.kv_heads // geometry.q_heads)
    d = geometry.head_dim
    return dtype_bytes * (3 * hb * bq * d + 2 * kv_hb * bkv * d
                          + 2 * hb * bq)


def flash_grid_steps(H: int, W: int, *, ports: int, unrolls: int,
                     geometry: FleetGeometry = FLEET) -> int:
    t = geometry.flash_tiling(ports, unrolls)
    s = geometry.flash_s
    return (geometry.q_heads // t["heads_per_step"]) * (s // t["block_q"]) \
        * max(1, s // t["block_kv"])


def ssd_vmem_bytes(H: int, W: int, *, ports: int, unrolls: int,
                   dtype_bytes: int = 4,
                   geometry: FleetGeometry = FLEET) -> int:
    """Per-grid-step VMEM: per head of the step, x/y tiles (chunk, P),
    the dt row and the carried (P, N) state with its output tile; the
    B/C tiles (chunk, N) the step's heads share."""
    t = geometry.ssd_tiling(ports, unrolls)
    chunk, P, N = t["chunk"], geometry.ssd_p, geometry.ssd_n
    return dtype_bytes * (t["heads_per_step"] * (2 * chunk * P + chunk
                                                 + 2 * P * N)
                          + 2 * chunk * N)


def ssd_grid_steps(H: int, W: int, *, ports: int, unrolls: int,
                   geometry: FleetGeometry = FLEET) -> int:
    t = geometry.ssd_tiling(ports, unrolls)
    return (t["heads"] // t["heads_per_step"]) \
        * max(1, geometry.ssd_s // t["chunk"])


def _input_draw(geometry: FleetGeometry):
    """The jitted draw of a geometry's inputs from a key: q (1, S, Hq,
    d), k/v (1, S, K, d), x (1, S', H', P), dt (1, S', H'), A (H',),
    B/C (1, S', N)."""
    g = geometry

    @jax.jit
    def draw(key):
        ks = jax.random.split(key, 8)
        q = jax.random.normal(ks[0], (1, g.flash_s, g.q_heads, g.head_dim))
        k = jax.random.normal(ks[1], (1, g.flash_s, g.kv_heads, g.head_dim))
        v = jax.random.normal(ks[2], (1, g.flash_s, g.kv_heads, g.head_dim))
        x = jax.random.normal(ks[3], (1, g.ssd_s, g.ssd_heads, g.ssd_p))
        dt = jax.nn.softplus(jax.random.normal(ks[4],
                                               (1, g.ssd_s, g.ssd_heads)))
        A = -jnp.exp(jax.random.normal(ks[5], (g.ssd_heads,)) * 0.3)
        Bm = jax.random.normal(ks[6], (1, g.ssd_s, g.ssd_n)) * 0.3
        Cm = jax.random.normal(ks[7], (1, g.ssd_s, g.ssd_n)) * 0.3
        return q, k, v, x, dt, A, Bm, Cm

    return draw


@functools.lru_cache(maxsize=None)
def _fleet_inputs(geometry: FleetGeometry = FLEET):
    """The geometry's baked inputs, made on first use (never at import)
    and once per geometry."""
    return _input_draw(geometry)(jax.random.PRNGKey(7))


def fleet_input_shapes(geometry: FleetGeometry = FLEET):
    """The shapes and types of the geometry's inputs, nothing made."""
    return jax.eval_shape(_input_draw(geometry), jax.random.PRNGKey(7))


def fleet_program(geometry: FleetGeometry, kernel: str, ports: int,
                  unrolls: int, interpret: bool) -> PointProgram:
    """The jitted program of one knob point of ``kernel``."""
    g = geometry
    if kernel == "flash_attention":
        t = g.flash_tiling(ports, unrolls)
        fn = functools.partial(
            mha, causal=True, block_q=t["block_q"], block_kv=t["block_kv"],
            block_h=t["heads_per_step"])
    else:
        t = g.ssd_tiling(ports, unrolls)
        fn = functools.partial(ssd, chunk=t["chunk"],
                               block_h=t["heads_per_step"])
    return PointProgram(jax.jit(functools.partial(
        fn, vmem_limit_bytes=g.vmem_limit_bytes, use_pallas=True,
        interpret=interpret)))


def fleet_kernel_specs(tile: int = 0, geometry: FleetGeometry = FLEET
                       ) -> Dict[str, PallasKernelSpec]:
    """The two fleet stages as measured kernel specs (deterministic
    baked inputs, made on the first build; ``tile`` is accepted for the
    components-factory protocol but the fleet geometry is fixed)."""
    g = geometry

    def build_flash(ports: int, unrolls: int, interpret: bool):
        program = fleet_program(g, "flash_attention", ports, unrolls,
                                interpret)
        return program, _fleet_inputs(g)[:3]

    def build_ssd(ports: int, unrolls: int, interpret: bool):
        program = fleet_program(g, "ssd_scan", ports, unrolls, interpret)
        x, dt, A, Bm, Cm = _fleet_inputs(g)[3:]
        n = g.ssd_tiling(ports, unrolls)["heads"]
        if n < g.ssd_heads:
            x, dt, A = x[:, :, :n, :], dt[:, :, :n], A[:n]
        return program, (x, dt, A, Bm, Cm)

    flash_shape = (g.flash_s, g.q_heads if g.knob_independent else g.flash_s)
    return {
        "flash_attention": PallasKernelSpec(
            name="flash_attention", shape=flash_shape, build=build_flash,
            vmem_bytes=functools.partial(flash_vmem_bytes, geometry=g),
            grid_steps=functools.partial(flash_grid_steps, geometry=g),
            n_in=3, n_out=1, tiling=g.flash_tiling),
        "ssd_scan": PallasKernelSpec(
            name="ssd_scan", shape=(g.ssd_s, g.ssd_heads), build=build_ssd,
            vmem_bytes=functools.partial(ssd_vmem_bytes, geometry=g),
            grid_steps=functools.partial(ssd_grid_steps, geometry=g),
            n_in=4, n_out=2, tiling=g.ssd_tiling),
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
                   block_kv=FLEET.flash_kv_rows * unrolls,
                   use_pallas=use_pallas, interpret=interpret)

    def mha_oracle(q, k, v):
        return mha_ref(q, k, v, causal=True)

    def ssd_knobbed(x, dt, A, Bm, Cm, *, ports, unrolls, use_pallas,
                    interpret):
        # parity output must be knob-independent: ports only replicates
        # head lanes in the measured spec, so the check runs all heads
        # and lets unrolls (the chunk length) exercise the kernel
        return ssd(x, dt, A, Bm, Cm, chunk=FLEET.ssd_chunk_rows * unrolls,
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
                        timer=None, geometry: FleetGeometry = FLEET,
                        **kwargs) -> PallasOracle:
    """The measured fleet oracle.  Default: deterministic replay of the
    geometry's checked-in recording with the *calibrated* XLA tool as
    fallback — the calibrated-measured backend of ``get_app("fleet")``.
    A live drive compiles for the TPU unless ``interpret`` is asked for,
    and records into its device kind's own file."""
    g = geometry
    # a replay reads its file's device kind; a live drive its own
    live_kind = (None if mode == "replay" else
                 "interpret" if interpret else live_device_kind())
    if measurements is None and mode in ("record", "replay"):
        kind = live_kind or g.recorded_kind
        measurements = open_recording(
            default_measurement_path(0, kind, geometry=g), mode=mode, tile=0,
            device_kind=kind, flush_every=flush_every)
    if fallback is None:
        if mode == "replay" and missing == "fallback":
            fallback = fleet_calibrated_tool(geometry=g)
        else:
            fallback = fleet_xla_tool(g)
    return PallasOracle(fleet_kernel_specs(geometry=g), mode=mode,
                        measurements=measurements,
                        components_factory=functools.partial(
                            fleet_kernel_specs, geometry=g),
                        fallback=fallback, interpret=interpret,
                        device_kind=live_kind,
                        missing=missing if mode == "replay" else "error",
                        record_hint=_record_hint(g), timer=timer, **kwargs)


def _record_hint(geometry: FleetGeometry) -> str:
    cmd = "python benchmarks/fleet_dse.py --record"
    if geometry.app != FLEET.app:
        cmd += f" --app {geometry.app}"
    return f"re-record with `{cmd}`"


def fleet_unit_system(store: Optional[MeasurementStore] = None,
                      geometry: FleetGeometry = FLEET) -> UnitSystem:
    """Exchange rates fitted from the fleet recording: per-stage latency
    scales (measured wall / roofline model) and one global HBM-bytes ->
    VMEM-bytes area rate — the :mod:`repro.core.calibrate` fit applied
    to the XLA tool."""
    store = store or MeasurementStore.load(
        default_measurement_path(geometry=geometry))
    return fit_unit_system(store, fleet_kernel_specs(geometry=geometry),
                           fleet_xla_tool(geometry))


def fleet_calibrated_tool(store: Optional[MeasurementStore] = None,
                          geometry: FleetGeometry = FLEET):
    """The calibrated-measured analytical fallback: the XLA roofline
    re-scaled onto the measured latency axis and VMEM-byte cost unit."""
    return fleet_unit_system(store, geometry).calibrated(
        fleet_xla_tool(geometry))


def fleet_session(delta: float = 0.3, *, backend: str = "analytical",
                  workers: int = 1, share_plm: bool = False,
                  geometry: FleetGeometry = FLEET,
                  **kwargs) -> ExplorationSession:
    """``build_session(<geometry's app>, backend)`` with the fleet
    defaults."""
    tool = None
    if backend == "pallas":
        tool = fleet_pallas_oracle("replay", geometry=geometry)
    return build_session(geometry.app, backend, tool=tool, delta=delta,
                         workers=workers, share_plm=share_plm, **kwargs)


# ----------------------------------------------------------------------
# registration: `get_app("fleet")` and `get_app("fleet-zamba2-7b")`
# ----------------------------------------------------------------------
def fleet_app(geometry: FleetGeometry, description: str, *,
              parity_cases=None, listed: bool = True) -> App:
    """The registry record of a fleet geometry: one two-stage pipeline,
    its knob spaces, analytical stand-ins, measured kernel specs and
    recording."""
    g = geometry
    return App(
        name=g.app,
        description=description,
        tmg=fleet_tmg,
        knob_spaces=lambda **_kw: fleet_knob_spaces(g),
        analytical=functools.partial(fleet_xla_tool, g),
        fixed={},
        delta=0.3,
        kernel_specs=functools.partial(fleet_kernel_specs, geometry=g),
        native_tile=0,
        measurement_path=functools.partial(default_measurement_path,
                                           geometry=g),
        recorded_tiles=(0,),
        default_tiles=(0,),
        calibrated_fallback=functools.partial(fleet_calibrated_tool,
                                              geometry=g),
        record_hint=_record_hint(g),
        plm_planner=lambda: PLMPlanner(fleet_tmg()),
        parity_cases=parity_cases,
        listed=listed,
    )


register_app(fleet_app(
    FLEET, "hybrid attention + SSD serving pipeline: flash_attention -> "
           "ssd_scan, priced as fleet shares (XLA roofline) or measured "
           "Pallas kernels",
    parity_cases=fleet_parity_cases))

register_app(fleet_app(
    ZAMBA2_7B_TP4, "one Zamba2-7B hybrid layer at its published widths on "
                   "one chip of a 4-way tensor-parallel v5e host: shared "
                   "attention (8 heads of 224) -> Mamba2 scan (28 heads), "
                   "4096 tokens per launch",
    listed=False))
