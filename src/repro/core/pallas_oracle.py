"""PallasOracle: a *measured* execution backend for the COSMOS loop.

Everything the DSE engine has priced so far came from closed-form models
(``HLSTool``'s scheduler, ``XLATool``'s roofline).  This module is the
backend the paper actually assumes exists: an oracle whose numbers come
from running the thing — each (component, knob) point compiles the
component's knob-parameterized Pallas kernel and *times* it
(docs/backends.md walks through the protocol):

  * latency lambda — measured wall clock per launch of the point's
    jitted program (ops wrapper + ``pallas_call``, compiled and warmed
    before the timed reps), divided by ``ports``: the grid columns are
    parallel lane-banks (DESIGN.md §2), so the per-bank effective
    latency is what the TMG composes;
  * area alpha — the VMEM footprint: the double-buffered working set
    summed over the ``ports`` banks, plus a fixed per-bank pipeline
    overhead (the TPU shadow of Mnemosyne's bank-controller area);
  * the lambda-constraint — a knob point is infeasible when the grid
    does not divide (W % ports, H % unrolls), when the double-buffered
    block no longer fits the device kind's VMEM budget, when the TPU
    compiler refuses the kernel (a failed synthesis, tagged
    ``detail["refused"]``), and, like every backend, when
    ``max_states`` caps the Eq. (1) state estimate.

Measurements are memoized per (component, ports, unrolls, tile) — one
physical point is timed exactly once per process, so a batched drive
prices identically to a sequential one — and flow through a
:class:`MeasurementSet` for record/replay: a keyed map
``(tile, device_kind) -> MeasurementStore`` the oracle routes every
request through.  Tiles with a recording replay their measured walls;
unrecorded tiles fall through to the analytical ``fallback`` (or raise,
under ``missing="error"``), so a tile knob axis stays deterministic and
machine-free even when only some tiles are measured.  ``mode="record"``
times and persists, ``mode="replay"`` is fully deterministic and
machine-free (CI has no TPU; the checked-in recordings under
``artifacts/measurements/`` drive the same fronts byte-for-byte).
Components without a Pallas kernel fall back to a wrapped analytical
tool, so a mixed system (the full WAMI TMG) still explores end-to-end.

A live measurement (``measure``/``record``) runs where the caller says:
``interpret=True`` times the Pallas interpreter (device kind
``"interpret"``), ``interpret=False`` compiles for the TPU and tags the
walls with ``jax.devices()[0].device_kind`` — and raises when JAX finds
no TPU, never timing the CPU in its place.
"""

from __future__ import annotations

import json
import os
import re
import threading
import warnings
from dataclasses import dataclass
from typing import (Any, Callable, Dict, Iterable, List, Optional, Tuple)

from .knobs import CDFGFacts, Synthesis, SynthesisTool
from .obs import WallClock
from .oracle import OracleBatchMixin, call_synthesize

__all__ = [
    "PallasKernelSpec",
    "MeasurementStore",
    "MeasurementSet",
    "MissingMeasurementError",
    "PallasOracle",
    "open_recording",
    "VMEM_BUDGETS",
    "device_vmem_budget",
    "live_device_kind",
    "platform_interpret",
    "recording_file",
]

# one physical measurement inside one store: (component, ports, unrolls).
# ``max_states`` is NOT part of the key — feasibility under a cap is
# decided from the deterministic state model, never re-measured.  The
# tile lives one level up: it selects WHICH store via the
# :class:`MeasurementSet` key (tile, device_kind).
MeasureKey = Tuple[str, int, int]

# a MeasurementSet routing key: (tile, device_kind); tile 0 = the
# component's native tile, device_kind "interpret" = CPU interpret mode
SetKey = Tuple[int, str]

# VMEM a kernel's double-buffered blocks may claim, per device kind.
# TPU v5e ("TPU v5 lite"): Mosaic's default scoped-VMEM limit, 16 MiB of
# the core's 128 MiB.  "interpret" keeps the same budget, so interpret
# recordings price exactly as they always have.  A kind missing here is
# an error, not a default: its budget has to be looked up and added.
VMEM_BUDGETS: Dict[str, int] = {
    "interpret": 16 * 1024 * 1024,
    "TPU v5 lite": 16 * 1024 * 1024,
}


def device_vmem_budget(device_kind: str) -> int:
    """The VMEM budget (bytes) of ``device_kind``; unknown kinds raise."""
    try:
        return VMEM_BUDGETS[device_kind]
    except KeyError:
        raise ValueError(
            f"no VMEM budget for device kind {device_kind!r}; known kinds: "
            f"{sorted(VMEM_BUDGETS)} (add it to VMEM_BUDGETS in "
            f"repro/core/pallas_oracle.py)") from None


def live_device_kind() -> str:
    """``jax.devices()[0].device_kind`` of the TPU a compiled (non-
    interpret) measurement runs on; raises when JAX finds no TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"a live Pallas measurement with interpret=False needs a TPU, "
            f"but JAX found platform {dev.platform!r} "
            f"({dev.device_kind!r}); pass interpret=True to time the "
            f"Pallas interpreter, or replay a recording")
    return str(dev.device_kind)


def platform_interpret() -> bool:
    """How the Pallas TPU kernels run on this host: compiled on a TPU
    (False), interpreted on a CPU (True); any other platform raises."""
    import jax
    platform = jax.devices()[0].platform
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas TPU kernels run compiled on a TPU or "
                           f"interpreted on a CPU; JAX found {platform!r}")
    return platform == "cpu"


def recording_file(stem: str, device_kind: str) -> str:
    """The recording file name of ``stem`` for ``device_kind``: the
    interpret recordings keep ``<stem>.json``; a chip's walls go to a
    file of their own, e.g. ``<stem>.tpu_v5_lite.json``."""
    if device_kind == "interpret":
        return f"{stem}.json"
    slug = re.sub(r"[^a-z0-9]+", "_", device_kind.lower()).strip("_")
    return f"{stem}.{slug}.json"


@dataclass(frozen=True)
class PallasKernelSpec:
    """One knob-parameterized kernel, as the oracle sees it.

    ``build(ports, unrolls, interpret)`` returns ``(program, args)``: a
    ``jax.jit``-compiled program covering the ops wrapper and the
    ``pallas_call`` (the apps wrap it in a
    :class:`~repro.launch.compile_cache.PointProgram`), and its baked
    deterministic inputs.  The oracle
    lowers and compiles ``program`` for ``args`` apart from the timed
    reps, then times ``compiled(*args)`` launches.
    ``vmem_bytes``/``grid_steps`` are the kernel package's cost models
    (``(H, W, ports=, unrolls=) -> int``).  ``n_in``/``n_out`` are the
    VMEM blocks the kernel streams per grid step — the Eq. (1)
    gamma_r/gamma_w analogues used for the state estimate.  ``tiling``,
    when given, names the tiling a knob point maps to, for its spans.
    """

    name: str
    shape: Tuple[int, int]                      # (H, W) the stage processes
    build: Callable[[int, int, bool], Tuple[Any, Tuple[Any, ...]]]
    vmem_bytes: Callable[..., int]
    grid_steps: Callable[..., int]
    n_in: int
    n_out: int
    # (ports, unrolls) -> the tiling the knobs map to (block sizes,
    # heads per step), carried on the point's ``pallas.lower`` span
    tiling: Optional[Callable[[int, int], Dict[str, int]]] = None

    def divisible(self, ports: int, unrolls: int) -> bool:
        H, W = self.shape
        return W % ports == 0 and H % unrolls == 0

    def facts(self) -> CDFGFacts:
        return CDFGFacts(gamma_r=self.n_in, gamma_w=self.n_out, eta=1,
                         trip=self.shape[0], has_plm_access=True)

    def states(self, ports: int, unrolls: int) -> int:
        return self.facts().h(unrolls, ports)


class MissingMeasurementError(KeyError):
    """Replay asked for a point the recording does not contain."""


class MeasurementStore:
    """A flat, deterministic JSON store of raw kernel timings.

    Maps (component, ports, unrolls) -> measured wall seconds per
    launch.  The derived quantities (per-bank lambda, VMEM area,
    feasibility) are recomputed by the oracle on replay, so a recording
    survives cost-model refinements.  ``save`` writes sorted keys —
    re-recording an identical machine state diffs clean.  Points the
    TPU compiler refused are kept apart in ``refused`` (key -> reason),
    so a replay reports the same failed syntheses the recording saw.

    ``flush_every`` > 0 makes the store durable *incrementally*: every
    N-th ``put`` rewrites the file through the same atomic
    write-then-rename step the :class:`PersistentOracleCache` uses, so a
    killed recording campaign loses at most the last N-1 timings and a
    restart (the record-mode oracle consults the store before timing)
    never re-pays for a flushed point.  0 keeps the legacy behaviour:
    the file is only written on an explicit ``save``/oracle ``flush``.
    """

    def __init__(self, path: Optional[str] = None,
                 meta: Optional[Dict[str, Any]] = None,
                 flush_every: int = 0):
        self.path = path
        self.meta: Dict[str, Any] = dict(meta or {})
        self.entries: Dict[MeasureKey, float] = {}
        self.refused: Dict[MeasureKey, str] = {}
        self.flush_every = max(0, int(flush_every))
        self._dirty = 0
        self._save_lock = threading.Lock()

    @classmethod
    def load(cls, path: str, *, flush_every: int = 0) -> "MeasurementStore":
        with open(path) as f:
            doc = json.load(f)
        if doc.get("version") != 1:
            raise ValueError(f"unknown measurement-store version "
                             f"{doc.get('version')!r} in {path}")
        store = cls(path=path, meta=doc.get("meta", {}),
                    flush_every=flush_every)
        for k, wall_s in doc["entries"].items():
            store.entries[cls._parse_key(k)] = float(wall_s)
        for k, reason in doc.get("refused", {}).items():
            store.refused[cls._parse_key(k)] = str(reason)
        return store

    @property
    def tile(self) -> int:
        """The tile this recording was made at (0 when untagged)."""
        return int(self.meta.get("tile", 0))

    @property
    def device_kind(self) -> str:
        """Where the walls came from: ``"interpret"`` (CPU interpret
        mode) or the chip's ``device_kind`` the recording tags."""
        kind = self.meta.get("device_kind")
        if kind:
            return str(kind)
        if self.meta.get("interpret", True):
            return "interpret"
        raise ValueError(f"recording {self.path!r} was made off the "
                         f"interpreter but tags no device_kind")

    @staticmethod
    def _key_str(key: MeasureKey) -> str:
        comp, ports, unrolls = key
        return f"{comp}:p{ports}:u{unrolls}"

    @staticmethod
    def _parse_key(k: str) -> MeasureKey:
        comp, p, u = k.rsplit(":", 2)
        return comp, int(p[1:]), int(u[1:])

    def get(self, key: MeasureKey) -> Optional[float]:
        return self.entries.get(key)

    def put(self, key: MeasureKey, wall_s: float) -> None:
        self._write(self.entries, key, float(wall_s))

    def refuse(self, key: MeasureKey, reason: str) -> None:
        """Record that the compiler refused ``key``."""
        self._write(self.refused, key, reason)

    def _write(self, table: Dict[MeasureKey, Any], key: MeasureKey,
               value: Any) -> None:
        if self.flush_every:
            # the write happens under the save lock so a concurrent
            # autoflush never iterates a mutating dict
            with self._save_lock:
                table[key] = value
                self._dirty += 1
                if self._dirty >= self.flush_every and self.path:
                    self._save_locked(self.path)
        else:
            table[key] = value

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        if path is None:
            raise ValueError("MeasurementStore has no path")
        with self._save_lock:
            return self._save_locked(path)

    def _save_locked(self, path: str) -> str:
        doc = {"version": 1, "meta": self.meta,
               "entries": {self._key_str(k): self.entries[k]
                           for k in sorted(self.entries)}}
        if self.refused:
            doc["refused"] = {self._key_str(k): self.refused[k]
                              for k in sorted(self.refused)}
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)     # atomic: a kill leaves old or new, never torn
        self.path = path
        self._dirty = 0
        return path

    def __len__(self) -> int:
        return len(self.entries)


class MeasurementSet:
    """Multi-recording routing table: (tile, device_kind) -> store.

    One oracle can now hold one :class:`MeasurementStore` per measured
    tile (and per device kind — the same tile recorded in interpret mode
    and on real hardware are different recordings).  The oracle resolves
    every request's tile to a set key; a hit replays/records through
    that store, a miss falls through to the analytical fallback or
    raises, per the ``missing`` policy.

    Stores keyed by tile 0 are "native tile" recordings from before the
    tile axis existed; :meth:`from_store` (the legacy one-store shim)
    additionally aliases such a store under its ``meta`` tile so a drive
    that names the tile explicitly still hits the measured walls.
    """

    def __init__(self, stores: Optional[Dict[SetKey, MeasurementStore]] = None):
        self._stores: Dict[SetKey, MeasurementStore] = dict(stores or {})

    # -- construction --------------------------------------------------
    @classmethod
    def from_store(cls, store: MeasurementStore, *, tile: Optional[int] = None,
                   device_kind: Optional[str] = None) -> "MeasurementSet":
        """Back-compat shim: wrap a single legacy store.

        ``tile``/``device_kind`` default to the store's ``meta`` tags.
        When the caller declares no tile (0) but the recording tags one,
        the store is reachable under BOTH keys — tile-0 ("native")
        requests and requests naming the recorded tile resolve to the
        same measured walls, which is what the old single-store design
        got wrong (it errored on the explicit spelling).
        """
        kind = device_kind or store.device_kind
        keyed = tile if tile is not None else store.tile
        out = cls({(int(keyed), kind): store})
        meta_tile = store.tile
        if meta_tile and (int(keyed), kind) != (meta_tile, kind):
            out._stores.setdefault((meta_tile, kind), store)
        if keyed:
            # an explicitly-tiled store also answers "native" requests
            # when it is the only recording for its device kind
            out._stores.setdefault((0, kind), store)
        return out

    @classmethod
    def load(cls, paths: Iterable[str], *, flush_every: int = 0,
             device_kind: Optional[str] = None) -> "MeasurementSet":
        """Load several store files, keyed by their ``meta`` tags."""
        out = cls()
        for path in paths:
            store = MeasurementStore.load(path, flush_every=flush_every)
            out.add(store, device_kind=device_kind)
        return out

    def add(self, store: MeasurementStore, *, tile: Optional[int] = None,
            device_kind: Optional[str] = None) -> "MeasurementSet":
        key = (int(tile if tile is not None else store.tile),
               device_kind or store.device_kind)
        if key in self._stores:
            raise ValueError(f"MeasurementSet already holds a store for "
                             f"key (tile={key[0]}, device={key[1]!r})")
        self._stores[key] = store
        return self

    # -- lookup --------------------------------------------------------
    def get(self, tile: int, device_kind: str) -> Optional[MeasurementStore]:
        return self._stores.get((int(tile), device_kind))

    def keys(self) -> List[SetKey]:
        return sorted(self._stores)

    def tiles(self, device_kind: Optional[str] = None) -> Tuple[int, ...]:
        return tuple(sorted({t for t, k in self._stores
                             if device_kind is None or k == device_kind}))

    def stores(self) -> List[MeasurementStore]:
        """The distinct stores (aliases collapse), in key order."""
        seen: List[MeasurementStore] = []
        for key in self.keys():
            store = self._stores[key]
            if not any(store is s for s in seen):
                seen.append(store)
        return seen

    def save_all(self) -> List[str]:
        """Persist every store that has a path (record-mode flush)."""
        return [s.save() for s in self.stores() if s.path is not None]

    def describe(self) -> str:
        return ", ".join(f"(tile={t}, device={k!r})" for t, k in self.keys()) \
            or "<empty>"

    def replay_kind(self) -> str:
        """The device kind a replay reads by default: the set's only
        kind, else ``"interpret"`` (the committed recordings) when the
        set holds it; a set of several chip kinds must be told which."""
        kinds = sorted({k for _, k in self._stores})
        if len(kinds) == 1:
            return kinds[0]
        if "interpret" in kinds or not kinds:
            return "interpret"
        raise ValueError(f"MeasurementSet holds recordings of several "
                         f"device kinds {kinds}; pass device_kind=")

    def __contains__(self, key: SetKey) -> bool:
        return (int(key[0]), key[1]) in self._stores

    def __len__(self) -> int:
        return len(self._stores)


class PallasOracle(OracleBatchMixin):
    """The measured synthesis oracle (SynthesisTool/Oracle protocols).

    ``mode``:
      * ``"measure"`` — compile + time every new point (memoized);
      * ``"record"``  — measure, and persist every timing into the
        resolved tile's store;
      * ``"replay"``  — never execute; a point absent from the resolved
        store follows the ``missing`` policy below.

    ``measurements`` is a :class:`MeasurementSet` — the multi-recording
    map ``(tile, device_kind) -> MeasurementStore`` every request routes
    through.  The legacy single-store spelling
    (``store=..., native_tile=...``) still works via
    :meth:`MeasurementSet.from_store` but is deprecated.

    ``fallback`` prices components that have no Pallas kernel (e.g. the
    6x6 matrix stages of WAMI) through an analytical tool, so a mixed
    TMG explores end-to-end.  ``timer(component, ports, unrolls, runner)
    -> seconds`` replaces the wall-clock measurement — tests inject a
    deterministic one to make a *fresh* drive byte-comparable to a
    replayed one.

    ``interpret`` says how a live (``measure``/``record``) drive runs the
    kernels: ``True`` times the Pallas interpreter, ``False`` (the
    default) compiles them for the TPU and raises when there is none.
    ``device_kind`` keys the recordings the oracle reads and writes; it
    defaults to ``"interpret"`` or the TPU's ``device_kind`` for a live
    drive, and to :meth:`MeasurementSet.replay_kind` for a replay.  The
    VMEM budget comes from :data:`VMEM_BUDGETS` for that kind unless
    ``vmem_budget`` overrides it.

    ``native_tile`` declares the tile the ``components`` kernel specs
    were built at; a request's tile resolves to it when unset (tile 0).
    A resolved tile with a recording in ``measurements`` replays (or
    records) measured walls.  In replay and measure mode, a tile without
    a recording (or without kernel specs) is routed to the fallback
    tool, which re-prices the component at that tile analytically (pair
    with a unit-calibrated fallback, :mod:`repro.core.plm.units`, to
    keep the axes comparable); in record mode a kernel component with no
    store for the oracle's key raises instead — a recording never mixes
    in analytical prices.  ``components_factory(tile)`` — when
    given — rebuilds the kernel specs at a measured non-native tile so
    multi-tile recordings price with the right geometry.

    ``missing`` picks the replay behaviour for a point absent from the
    resolved recording: ``"error"`` (default) raises
    :class:`MissingMeasurementError` naming the missing
    ``(tile, device_kind)`` key — the strict CI semantics;
    ``"fallback"`` prices it through the fallback tool instead, which is
    what a drive whose walk *extends* the recorded one (e.g. the tile
    knob reshapes the LP and hence the mapped unroll choices) needs to
    stay deterministic and machine-free.
    """

    def __init__(self, components: Dict[str, PallasKernelSpec], *,
                 mode: str = "measure",
                 store: Optional[MeasurementStore] = None,
                 measurements: Optional[MeasurementSet] = None,
                 components_factory: Optional[
                     Callable[[int], Dict[str, PallasKernelSpec]]] = None,
                 fallback: Optional[SynthesisTool] = None,
                 interpret: bool = False,
                 vmem_budget: Optional[int] = None,
                 bank_overhead_bytes: int = 4096,
                 reps: int = 3,
                 native_tile: int = 0,
                 missing: str = "error",
                 device_kind: Optional[str] = None,
                 record_hint: Optional[str] = None,
                 timer: Optional[Callable[..., float]] = None):
        if mode not in ("measure", "record", "replay"):
            raise ValueError(f"unknown mode {mode!r}")
        if missing not in ("error", "fallback"):
            raise ValueError(f"unknown missing policy {missing!r}")
        if missing == "fallback" and fallback is None:
            raise ValueError("missing='fallback' requires a fallback tool")
        if store is not None and measurements is not None:
            raise ValueError("pass either store= (legacy, one recording) "
                             "or measurements= (MeasurementSet), not both")
        if store is not None:
            warnings.warn(
                "PallasOracle(store=...) is the legacy single-recording "
                "surface; pass measurements=MeasurementSet.from_store(...) "
                "(or build a multi-tile set) instead",
                DeprecationWarning, stacklevel=2)
            measurements = MeasurementSet.from_store(
                store, tile=native_tile or None)
        if mode in ("record", "replay") and (measurements is None
                                             or len(measurements) == 0):
            raise ValueError(f"mode={mode!r} requires a MeasurementStore "
                             f"or a non-empty MeasurementSet")
        self.interpret = interpret
        if device_kind is None:
            device_kind = (measurements.replay_kind() if mode == "replay"
                           else "interpret" if interpret
                           else live_device_kind())
        self.device_kind = device_kind
        self.components = dict(components)
        self.mode = mode
        self.measurements = measurements or MeasurementSet()
        self.fallback = fallback
        self.vmem_budget = int(vmem_budget if vmem_budget is not None
                               else device_vmem_budget(device_kind))
        self.bank_overhead_bytes = int(bank_overhead_bytes)
        self.reps = max(1, int(reps))
        self.native_tile = int(native_tile)
        self.missing = missing
        # the app-specific re-record command shown in miss errors (the
        # oracle serves many apps now; a WAMI hint on a fleet miss
        # would point at the wrong recording)
        self.record_hint = record_hint
        self.timer = timer
        self._factory = components_factory
        # tiles whose requests resolve onto the native ``components``
        # specs: the declared native tile, the untagged 0, and — for the
        # legacy shim — whatever tile the native store's meta carries
        self._native_tiles = {0, self.native_tile}
        native_store = self.measurements.get(self.native_tile,
                                             self.device_kind)
        if native_store is not None and native_store.tile:
            self._native_tiles.add(native_store.tile)
        self._specs_cache: Dict[int, Dict[str, PallasKernelSpec]] = {}
        # memo per (component, ports, unrolls, tile): the measured wall,
        # or the compiler's refusal reason (a str)
        self._measured: Dict[Tuple[str, int, int, int], Any] = {}
        # what the live measurements cost: points timed / refused,
        # seconds spent lowering and compiling (``compile_s``, of which
        # ``lower_s`` lowering) vs. in the timed reps, compiles the
        # persistent cache served or missed, and kernel components the
        # fallback tool priced instead
        self.stats: Dict[str, float] = {"timed": 0, "refused": 0,
                                        "fallback": 0, "compile_s": 0.0,
                                        "lower_s": 0.0, "timed_s": 0.0,
                                        "cache_hits": 0, "cache_misses": 0}
        self._lock = threading.Lock()
        # timing under a thread-pool fan-out measures contention, not the
        # kernel: _measure_lock serializes every real measurement even
        # when a ledger/session fans synthesize() out over its own pool;
        # replay never executes and can fan out freely
        self._measure_lock = threading.Lock()
        self.batch_workers = 8 if mode == "replay" else 1

    # ------------------------------------------------------------------
    # routing: request tile -> (specs, store)
    # ------------------------------------------------------------------
    @property
    def store(self) -> Optional[MeasurementStore]:
        """The native-tile recording (legacy surface; may be None)."""
        return self.measurements.get(self.native_tile, self.device_kind)

    def _resolve_tile(self, tile: int) -> int:
        return tile or self.native_tile

    def _store_for(self, resolved: int) -> Optional[MeasurementStore]:
        return self.measurements.get(resolved, self.device_kind)

    def _specs_for(self, resolved: int
                   ) -> Optional[Dict[str, PallasKernelSpec]]:
        if resolved in self._native_tiles:
            return self.components
        if self._factory is None:
            return None
        specs = self._specs_cache.get(resolved)
        if specs is None:
            specs = dict(self._factory(resolved))
            self._specs_cache[resolved] = specs
        return specs

    def _measured_here(self, component: str, resolved: int) -> bool:
        """True when (component, resolved tile) is priced by running /
        replaying a kernel rather than by the fallback tool.  A record
        drive has no fallback for a kernel component: a missing store
        for its key raises."""
        if component not in self.components:
            return False        # kernel coverage is per component name
        if self.mode == "replay":
            return (self._specs_for(resolved) is not None
                    and self._store_for(resolved) is not None)
        if self.mode == "record" and self._store_for(resolved) is None:
            raise MissingMeasurementError(
                f"record mode has no store for {component!r} under key "
                f"(tile={resolved}, device={self.device_kind!r}); stores "
                f"held: {self.measurements.describe()} — open a recording "
                f"for that key (open_recording) instead of pricing the "
                f"kernel analytically")
        return self._specs_for(resolved) is not None

    # ------------------------------------------------------------------
    # measurement
    # ------------------------------------------------------------------
    def _time_program(self, program: Any, args: Tuple[Any, ...],
                      tiling: Optional[Dict[str, int]] = None,
                      **attrs: Any) -> Any:
        """Compile ``program`` for ``args``, warm it up, and return the
        best of ``reps`` timed launches (seconds), each ending in
        ``block_until_ready``.  A kernel the TPU compiler refuses — at
        lowering or at compile time — returns the refusal reason (a
        str) instead; any other exception propagates.

        Each stage is a span on :attr:`tracer` (``pallas.lower``,
        ``pallas.compile``, ``pallas.warmup``, ``pallas.reps``, carrying
        ``attrs``), and the same clock reads feed :attr:`stats`;
        ``pallas.lower`` also carries ``tiling`` and ``pallas.reps`` the
        count of its ``launches``.  A
        program whose executable the point cache holds
        (:class:`~repro.launch.compile_cache.PointProgram`) lowers
        nothing: its ``pallas.lower`` reads ``point_cache="hit"`` and its
        compile counts as a persistent-cache hit."""
        import jax
        from jax.experimental.pallas import tpu as pltpu
        from ..launch.compile_cache import (cache_outcome, compile_events,
                                            point_outcome)
        events = compile_events()
        before = events.snapshot()
        with _Stage(self.tracer, "pallas.lower", _CLOCK.now(),
                    attrs) as lower:
            try:
                lowered = program.lower(*args)
            except (ValueError, NotImplementedError,
                    pltpu.LoweringException) as e:
                lowered = _refusal("lowering", e)
                lower.span.set("refused", lowered)
            after = events.snapshot()
            for key in ("trace_s", "mlir_s"):
                lower.span.set(key, after[key] - before[key])
            point = point_outcome(before, after)
            lower.span.set("point_cache", point)
            for key, value in (tiling or {}).items():
                lower.span.set(key, value)
        if isinstance(lowered, str):
            return lowered
        with _Stage(self.tracer, "pallas.compile", lower.end,
                    attrs) as comp:
            before = events.snapshot()
            try:
                compiled = lowered.compile()
            except jax.errors.JaxRuntimeError as e:
                compiled = _refusal("compile", e)
                comp.span.set("refused", compiled)
            cache = cache_outcome(before, events.snapshot())
            if point == "hit" and cache == "off":
                cache = "hit"      # the executable came from the point entry
            comp.span.set("cache", cache)
        with self._lock:
            self.stats["cache_hits"] += cache == "hit"
            self.stats["cache_misses"] += cache == "miss"
        if isinstance(compiled, str):
            return compiled
        with _Stage(self.tracer, "pallas.warmup", comp.end, attrs) as warm:
            jax.block_until_ready(compiled(*args))
        best = float("inf")
        with _Stage(self.tracer, "pallas.reps", warm.end, attrs) as reps:
            t = _CLOCK.now()
            for _ in range(self.reps):
                jax.block_until_ready(compiled(*args))
                t, t0 = _CLOCK.now(), t
                best = min(best, t - t0)
            reps.span.set("best_s", best)
            reps.span.set("launches", self.reps)
        with self._lock:
            self.stats["lower_s"] += lower.seconds
            self.stats["compile_s"] += comp.end - lower.start
            self.stats["timed_s"] += reps.seconds
        return best

    def _missing_error(self, key: MeasureKey, resolved: int
                       ) -> MissingMeasurementError:
        comp, ports, unrolls = key
        hint = self.record_hint or ("re-record the recording for this key "
                                    "(docs/backends.md)")
        return MissingMeasurementError(
            f"no recorded measurement for {comp!r} (ports={ports}, "
            f"unrolls={unrolls}) under key (tile={resolved}, "
            f"device={self.device_kind!r}); recorded keys: "
            f"{self.measurements.describe()}; {hint}")

    def _wall_s(self, spec: PallasKernelSpec, ports: int, unrolls: int,
                resolved: int) -> Any:
        """The point's wall seconds (float), or the compiler's refusal
        reason (str) — measured once, recorded, or replayed."""
        memo_key = (spec.name, ports, unrolls, resolved)
        key: MeasureKey = (spec.name, ports, unrolls)
        store = self._store_for(resolved)
        with self._lock:
            hit = self._measured.get(memo_key)
        if hit is not None:
            return hit
        recorded = None
        if store is not None:
            recorded = store.get(key)
            if recorded is None:
                recorded = store.refused.get(key)
        if self.mode == "replay":
            if recorded is None:
                raise self._missing_error(key, resolved)
            wall = recorded
        elif self.mode == "record" and recorded is not None:
            # resumed campaign: the point was already paid for (and
            # flushed) by the killed run — never re-time it
            wall = recorded
        else:
            with self._measure_lock:
                with self._lock:              # raced while waiting?
                    hit = self._measured.get(memo_key)
                if hit is not None:
                    return hit
                built = spec.build(ports, unrolls, self.interpret)
                if self.timer is not None:
                    wall = float(self.timer(spec.name, ports, unrolls,
                                            built))
                else:
                    wall = self._time_program(
                        *built, component=spec.name, ports=ports,
                        unrolls=unrolls,
                        tiling=spec.tiling and spec.tiling(ports, unrolls))
                with self._lock:
                    self.stats["refused" if isinstance(wall, str)
                               else "timed"] += 1
        with self._lock:
            # a racing measurement of the same key keeps the first value,
            # so every consumer sees one number per physical point
            wall = self._measured.setdefault(memo_key, wall)
            if self.mode == "record" and recorded is None:
                if isinstance(wall, str):
                    store.refuse(key, wall)
                else:
                    store.put(key, wall)     # may autoflush (flush_every)
        return wall
    # ------------------------------------------------------------------
    # cost composition
    # ------------------------------------------------------------------
    def _area_bytes(self, spec: PallasKernelSpec, ports: int,
                    unrolls: int) -> float:
        H, W = spec.shape
        step = spec.vmem_bytes(H, W, ports=ports, unrolls=unrolls)
        # double-buffered working set in every parallel bank + fixed
        # per-bank pipeline overhead (descriptors, semaphores)
        return float(2 * step * ports + self.bank_overhead_bytes * ports)

    def _infeasible(self, ports: int, unrolls: int, states: int,
                    tile: int = 0) -> Synthesis:
        return Synthesis(lam=float("inf"), area=float("inf"), ports=ports,
                         unrolls=unrolls, states_per_iter=states,
                         feasible=False, tile=tile)

    # ------------------------------------------------------------------
    # SynthesisTool protocol
    # ------------------------------------------------------------------
    def _route_fallback(self, component: str, tile: int) -> bool:
        """True when (component, tile) is priced by the fallback tool:
        the component has no kernel, or the resolved tile has no
        recording (and cannot be measured live)."""
        return not self._measured_here(component, self._resolve_tile(tile))

    def synthesize(self, component: str, *, unrolls: int, ports: int,
                   max_states: Optional[int] = None,
                   tile: int = 0) -> Synthesis:
        resolved = self._resolve_tile(tile)
        measured = self._measured_here(component, resolved)
        if (tile and not measured and not self.native_tile
                and self._factory is None
                and component in self.components):
            # without a declared native tile (or a spec factory, or a
            # recording covering this tile) the oracle cannot tell
            # whether the request matches the kernels — pricing it
            # anyway would fabricate a tile axis out of one tile's
            # measurements (and collide store keys in record mode)
            raise ValueError(
                f"tile={tile} requested for {component!r} but this "
                f"PallasOracle declares no native_tile and no recording "
                f"covers key (tile={tile}, device={self.device_kind!r}) "
                f"(recorded keys: {self.measurements.describe()}); pass "
                f"native_tile= or add a MeasurementStore for that key")
        if not measured:
            if self.fallback is None:
                raise KeyError(f"no Pallas kernel or fallback tool for "
                               f"component {component!r} (tile={tile})")
            return self._fallback_synthesize(component, unrolls, ports,
                                             max_states, tile)
        spec = self._specs_for(resolved)[component]
        if not spec.divisible(ports, unrolls):
            return self._infeasible(ports, unrolls, 0, tile)
        states = spec.states(ports, unrolls)
        if max_states is not None and states > max_states:
            return self._infeasible(ports, unrolls, states, tile)
        H, W = spec.shape
        step = spec.vmem_bytes(H, W, ports=ports, unrolls=unrolls)
        if 2 * step > self.vmem_budget:
            # the TPU lambda-constraint: the double-buffered block no
            # longer fits VMEM — discarded, and counted, like any other
            # failed synthesis
            return self._infeasible(ports, unrolls, states, tile)
        try:
            wall = self._wall_s(spec, ports, unrolls, resolved)
        except MissingMeasurementError:
            if self.missing != "fallback":
                raise
            return self._fallback_synthesize(component, unrolls, ports,
                                             max_states, tile)
        if isinstance(wall, str):
            # the TPU compiler refused this kernel: a failed synthesis,
            # counted by the ledger like any other
            return Synthesis(lam=float("inf"), area=float("inf"),
                             ports=ports, unrolls=unrolls,
                             states_per_iter=states, feasible=False,
                             detail={"refused": wall}, tile=tile)
        lam = wall / ports                       # parallel lane-banks
        area = self._area_bytes(spec, ports, unrolls)
        return Synthesis(
            lam=lam, area=area, ports=ports, unrolls=unrolls,
            states_per_iter=states, feasible=True,
            detail={"wall_s": wall, "vmem_step_bytes": float(step),
                    "grid_steps": float(spec.grid_steps(
                        H, W, ports=ports, unrolls=unrolls))},
            tile=tile)

    def _fallback_synthesize(self, component: str, unrolls: int, ports: int,
                             max_states: Optional[int],
                             tile: int) -> Synthesis:
        if component in self.components:
            with self._lock:
                self.stats["fallback"] += 1
        return call_synthesize(self.fallback, component, unrolls=unrolls,
                               ports=ports, max_states=max_states, tile=tile)

    def cdfg_facts(self, component: str, synth: Synthesis) -> CDFGFacts:
        # a feasible measured-tile synthesis without a measured wall came
        # from the missing="fallback" path: its Eq. (1) facts must match
        # the model that actually scheduled it, or the derived caps get
        # applied across two different state models
        fallback_priced = (self.missing == "fallback" and synth.feasible
                           and "wall_s" not in (synth.detail or {}))
        if self._route_fallback(component, synth.tile) or fallback_priced:
            if self.fallback is None:
                raise KeyError(component)
            return self.fallback.cdfg_facts(component, synth)
        return self._specs_for(
            self._resolve_tile(synth.tile))[component].facts()

    def plm_requirement(self, component: str, synth: Synthesis):
        """The measured component's memory demand: its entire area IS
        VMEM footprint (the TPU shadow of the PLM), so capacity = area
        bytes and the datapath share is zero.  Fallback-priced points
        delegate to the fallback tool — including measured-tile points
        the ``missing="fallback"`` policy priced analytically,
        recognizable by the absence of the measured ``wall_s`` detail."""
        from .plm.spec import PLMRequirement      # lazy: avoid cycles
        if (self._route_fallback(component, synth.tile)
                or "wall_s" not in (synth.detail or {})):
            fn = getattr(self.fallback, "plm_requirement", None)
            return None if fn is None else fn(component, synth)
        area = float(synth.area)
        return PLMRequirement(component=component, capacity=int(area),
                              word_bits=32, ports=synth.ports,
                              area_plm=area, area_logic=0.0,
                              unit="bytes", tile=synth.tile)

    # ------------------------------------------------------------------
    def flush(self) -> Optional[str]:
        """Persist the recordings (record mode); no-op otherwise.
        Returns the native store's path when one was written."""
        if self.mode != "record":
            return None
        saved = self.measurements.save_all()
        native = self.store
        if native is not None and native.path in saved:
            return native.path
        return saved[0] if saved else None


_CLOCK = WallClock()


class _Stage:
    """One stage of a live measurement: a span on the oracle's tracer
    whose two ends are the clock reads the oracle's counters take.
    Opened at ``start`` (the previous stage's end), closed at a read
    taken on exit."""

    __slots__ = ("span", "start", "end")

    def __init__(self, tracer: Any, name: str, start: float,
                 attrs: Dict[str, Any]):
        self.start = self.end = start
        self.span = tracer.span(name, start=start, **attrs)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "_Stage":
        self.span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.end = _CLOCK.now()
        self.span.finish(exc, end=self.end)
        return self.span.__exit__(exc_type, exc, tb)


def _refusal(phase: str, exc: Exception) -> str:
    """The one-line reason a refused kernel is tagged with."""
    first = (str(exc).strip().splitlines() or [""])[0]
    return f"{phase}: {type(exc).__name__}: {first}"


def open_recording(path: str, *, mode: str, device_kind: str,
                   tile: int = 0, flush_every: int = 16) -> MeasurementSet:
    """The record/replay bootstrap every app shares: load ``path`` when
    it exists (replay always loads — a missing file should fail loudly),
    otherwise start a fresh store tagged ``device_kind`` for a record
    campaign, and wrap the result as a single-recording
    :class:`MeasurementSet`.  Record mode autoflushes every
    ``flush_every`` timings; replay never writes.  A record campaign
    never resumes a file made on another device kind: each kind keeps
    its own file (see ``default_measurement_path`` of each app).
    """
    autoflush = flush_every if mode == "record" else 0
    if mode == "replay" or os.path.exists(path):
        store = MeasurementStore.load(path, flush_every=autoflush)
        if mode == "record" and store.device_kind != device_kind:
            raise ValueError(
                f"{path} holds {store.device_kind!r} walls; a "
                f"{device_kind!r} recording needs a file of its own")
    else:
        store = MeasurementStore(path,
                                 meta={"tile": tile,
                                       "interpret": device_kind == "interpret",
                                       "device_kind": device_kind},
                                 flush_every=autoflush)
    return MeasurementSet.from_store(store, tile=tile)
