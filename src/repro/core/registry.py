"""The App/Backend registry: one entry point for every workload x oracle.

COSMOS is compositional — the same characterize -> plan -> map
methodology applies to *any* accelerator — but until this module each
benchmark hand-wired its own ``if backend == "pallas"`` ladder and each
app grew bespoke session constructors.  The registry replaces both
seams with two small declarative records:

  * an :class:`App` bundles everything an
    :class:`~repro.core.session.ExplorationSession` needs about a
    workload: the TMG factory, the per-component knob spaces, fixed
    (software) latencies, the analytical tool, and — when the app has
    measured kernels — the ``PallasKernelSpec`` factory, its recordings
    on disk, the unit-calibrated fallback, and the PLM planner;
  * a :class:`Backend` bundles an oracle factory plus capability
    metadata: measured vs analytical, which recorded tiles it can
    replay for an app, and the calibration hook that puts an analytical
    model onto the measured axes.

``get_app("wami")`` / ``get_backend("pallas")`` resolve by name (apps
self-register on first use via their package import), and
:func:`build_session` is the single session constructor every benchmark
and example drives:

    session = build_session("wami", "pallas", share_plm=True)
    result = session.run()

Registering a new workload is one :func:`register_app` call — see
docs/backends.md for the how-to and the current apps x backends support
matrix.
"""

from __future__ import annotations

import importlib
import os
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple)

from .knobs import KnobSpace
from .pallas_oracle import MeasurementSet, PallasKernelSpec, PallasOracle
from .session import DSEQuery, ExplorationSession
from .tmg import TMG

__all__ = [
    "App",
    "Backend",
    "register_app",
    "register_backend",
    "get_app",
    "get_backend",
    "list_apps",
    "list_backends",
    "build_tool",
    "build_session",
    "build_query_session",
]


@dataclass(frozen=True)
class App:
    """One registered workload: everything a session needs, bundled.

    ``tmg``/``knob_spaces``/``analytical`` are zero-config factories
    (``knob_spaces`` must accept a ``tile_sizes=`` keyword when
    ``plm_tile_sizes`` is non-empty).  ``fixed`` maps software
    transitions to their fixed effective latency.  The measured-backend
    fields are optional: an app without ``kernel_specs`` simply does not
    support measured backends (``Backend.supports`` reports it).

    ``recorded_tiles`` lists every tile with a checked-in recording —
    capability metadata; ``default_tiles`` is the subset sessions load
    unless the caller opts into more (``build_session(tiles=...)``).
    The two differ on purpose: loading a new recording by default would
    silently re-price walks that previously fell back analytically.
    """

    name: str
    description: str
    tmg: Callable[[], TMG]
    knob_spaces: Callable[..., Dict[str, KnobSpace]]
    analytical: Callable[[], Any]
    fixed: Dict[str, float] = field(default_factory=dict)
    delta: float = 0.25
    # measured-backend surface (optional)
    kernel_specs: Optional[Callable[[int],
                                    Dict[str, PallasKernelSpec]]] = None
    native_tile: int = 0
    measurement_path: Optional[Callable[[int], str]] = None
    recorded_tiles: Tuple[int, ...] = ()
    default_tiles: Tuple[int, ...] = ()
    # called as calibrated_fallback(store=<native recording>) when the
    # caller already holds the loaded store, or with no arguments
    calibrated_fallback: Optional[Callable[..., Any]] = None
    record_hint: Optional[str] = None          # app's re-record command
    # memory-co-design surface (optional)
    plm_planner: Optional[Callable[[], Any]] = None
    plm_tile_sizes: Tuple[int, ...] = ()            # analytical tile axis
    plm_tile_sizes_measured: Tuple[int, ...] = ()   # measured-drive axis
    # interpret-mode parity cases: (tile) -> [(name, fn, oracle, args)]
    parity_cases: Optional[Callable[..., List]] = None
    # False: resolved by name alone (get_app), left out of list_apps()
    # and so of every sweep over the registry (the scenario matrix, the
    # parity sweeps, lint's default set) — for apps whose kernels run
    # only at full size on a chip
    listed: bool = True

    def available_tiles(self) -> Tuple[int, ...]:
        """The recorded tiles whose store files exist on disk."""
        if self.measurement_path is None:
            return ()
        return tuple(t for t in self.recorded_tiles
                     if os.path.exists(self.measurement_path(t)))

    def recording_keys(self) -> List[Tuple[int, str, str, int]]:
        """Every recording on disk, as ``(tile, device_kind, file,
        points)`` — the ``(tile, device_kind)`` pairs are exactly the
        :class:`MeasurementSet` routing keys a measured backend can
        replay; ``file`` is the store's basename under
        ``artifacts/measurements/``."""
        out: List[Tuple[int, str, str, int]] = []
        if self.measurement_path is None:
            return out
        from .pallas_oracle import MeasurementStore
        for t in self.recorded_tiles:
            path = self.measurement_path(t)
            if not os.path.exists(path):
                continue
            store = MeasurementStore.load(path)
            out.append((store.tile or t, store.device_kind,
                        os.path.basename(path), len(store.entries)))
        return out

    def describe(self) -> Dict[str, Any]:
        """The app as a plain dict — what doc generation
        (``python -m benchmarks.run --emit-docs``) and skip reasons
        read.  Deterministic: sorted keys, recording basenames only."""
        return {
            "name": self.name,
            "description": self.description,
            "components": sorted(t.name for t in self.tmg().transitions),
            "fixed": sorted(self.fixed),
            "delta": self.delta,
            "measured": self.kernel_specs is not None,
            "native_tile": self.native_tile,
            "recorded_tiles": list(self.recorded_tiles),
            "available_tiles": list(self.available_tiles()),
            "recordings": [
                {"tile": t, "device_kind": kind, "file": name, "points": n}
                for t, kind, name, n in self.recording_keys()],
            "plm_planner": self.plm_planner is not None,
            "plm_tile_sizes": list(self.plm_tile_sizes),
            "plm_tile_sizes_measured": list(self.plm_tile_sizes_measured),
            "parity_cases": self.parity_cases is not None,
            "record_hint": self.record_hint,
        }

    def measurement_set(self, tiles: Optional[Sequence[int]] = None
                        ) -> MeasurementSet:
        """Load the app's recordings for ``tiles`` (default: the app's
        ``default_tiles``) into one routing set."""
        if self.measurement_path is None:
            raise ValueError(f"app {self.name!r} has no recordings")
        use = tuple(tiles if tiles is not None else self.default_tiles)
        return MeasurementSet.load(self.measurement_path(t) for t in use)


@dataclass(frozen=True)
class Backend:
    """One registered oracle family: factory + capability metadata.

    ``make_tool(app, share_plm=..., tiles=..., mode=...)`` returns the
    synthesis tool a session drives for ``app``.  ``measured`` says
    whether prices come from executing kernels (record/replay) or from
    a closed-form model; ``supports``/``supported_tiles`` are the
    capability questions benchmarks ask before wiring a scenario, and
    ``calibrate`` is the hook that returns the app's analytical model
    re-scaled onto this backend's measured axes (None when the backend
    is itself analytical, or the app has no recordings to fit against).
    """

    name: str
    description: str
    measured: bool
    make_tool: Callable[..., Any]
    supports: Callable[[App], bool] = lambda app: True
    supported_tiles: Callable[[App], Tuple[int, ...]] = lambda app: ()
    calibrate: Optional[Callable[[App], Any]] = None
    # why an unsupported app is unsupported, in the app's terms — the
    # scenario matrix reports it as the cell's skip reason
    explain: Optional[Callable[[App], Optional[str]]] = None

    def skip_reason(self, app: App) -> Optional[str]:
        """``None`` when this backend can drive ``app``; otherwise a
        non-empty human-readable reason (what the scenario matrix and
        generated docs print for a skipped cell)."""
        if self.supports(app):
            return None
        if self.explain is not None:
            reason = self.explain(app)
            if reason:
                return reason
        return (f"backend {self.name!r} does not support app "
                f"{app.name!r}")

    def describe(self, apps: Optional[Sequence[App]] = None
                 ) -> Dict[str, Any]:
        """The backend as a plain dict; with ``apps``, a per-app
        capability block (supported / tiles / skip reason)."""
        doc: Dict[str, Any] = {
            "name": self.name,
            "description": self.description,
            "measured": self.measured,
        }
        if apps is not None:
            doc["apps"] = {
                app.name: {
                    "supported": self.supports(app),
                    "tiles": list(self.supported_tiles(app)),
                    "skip_reason": self.skip_reason(app),
                } for app in apps}
        return doc


# ----------------------------------------------------------------------
# the registries
# ----------------------------------------------------------------------
_APPS: Dict[str, App] = {}
_BACKENDS: Dict[str, Backend] = {}

# built-in apps self-register when their package is imported; the lazy
# import (on first lookup) avoids a core -> apps import cycle
_BUILTIN_APP_MODULES: Dict[str, str] = {
    "wami": "repro.apps.wami",
    "fleet": "repro.apps.fleet",
    "fleet-zamba2-7b": "repro.apps.fleet",
}


def register_app(app: App) -> App:
    """Idempotent by name: re-registering the same name replaces the
    entry (module reloads in notebooks would otherwise error)."""
    _APPS[app.name] = app
    return app


def register_backend(backend: Backend) -> Backend:
    _BACKENDS[backend.name] = backend
    return backend


def _ensure_builtin_apps(name: Optional[str] = None) -> None:
    wanted = ([name] if name in _BUILTIN_APP_MODULES
              else list(_BUILTIN_APP_MODULES))
    for key in wanted:
        if key not in _APPS:
            importlib.import_module(_BUILTIN_APP_MODULES[key])


def get_app(name: str) -> App:
    """Resolve a registered workload by name (importing built-ins on
    first use).  Unknown names list what IS registered."""
    if name not in _APPS:
        _ensure_builtin_apps(name)
    try:
        return _APPS[name]
    except KeyError:
        raise KeyError(f"unknown app {name!r}; registered apps: "
                       f"{sorted(_APPS) or '<none>'}") from None


def get_backend(name: str) -> Backend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; registered backends: "
                       f"{sorted(_BACKENDS)}") from None


def list_apps() -> List[App]:
    """The registered apps, by name, less those not ``listed``."""
    _ensure_builtin_apps()
    return [_APPS[n] for n in sorted(_APPS) if _APPS[n].listed]


def list_backends() -> List[Backend]:
    return [_BACKENDS[n] for n in sorted(_BACKENDS)]


# ----------------------------------------------------------------------
# the built-in backends
# ----------------------------------------------------------------------
def _analytical_tool(app: App, **_opts: Any) -> Any:
    return app.analytical()


def _pallas_supports(app: App) -> bool:
    return app.kernel_specs is not None and bool(app.available_tiles())


def _pallas_explain(app: App) -> Optional[str]:
    if app.kernel_specs is None:
        return (f"app {app.name!r} registers no Pallas kernel specs "
                f"(no measured surface)")
    if not app.available_tiles():
        hint = f"; {app.record_hint}" if app.record_hint else ""
        return (f"no recording on disk for tiles "
                f"{list(app.recorded_tiles)} under "
                f"artifacts/measurements/{hint}")
    return None


def _pallas_tool(app: App, *, share_plm: bool = False,
                 tiles: Optional[Sequence[int]] = None,
                 mode: str = "replay", missing: Optional[str] = None,
                 **opts: Any) -> PallasOracle:
    """The measured oracle for ``app``: replay its recordings through a
    :class:`MeasurementSet`, fall back analytically elsewhere.

    Plain drives keep the strict ``missing="error"`` semantics over the
    raw analytical tool; ``share_plm`` drives use the unit-calibrated
    fallback with ``missing="fallback"`` so the tile axis (and any
    mapped point outside the recorded walk) prices deterministically.
    """
    if app.kernel_specs is None:
        raise ValueError(f"app {app.name!r} has no Pallas kernel specs; "
                         f"measured backends are unsupported "
                         f"(supported apps: "
                         f"{[a.name for a in list_apps() if _pallas_supports(a)]})")
    measurements = app.measurement_set(tiles)
    if share_plm or missing == "fallback":
        missing = "fallback"
        if app.calibrated_fallback is not None:
            # hand the hook the already-loaded native recording so the
            # unit fit does not re-read the JSON from disk
            kind = opts.get("device_kind") or measurements.replay_kind()
            fallback = app.calibrated_fallback(
                store=measurements.get(app.native_tile, kind))
        else:
            fallback = app.analytical()
    else:
        fallback = app.analytical()
        missing = missing or "error"
    return PallasOracle(
        app.kernel_specs(app.native_tile), mode=mode,
        measurements=measurements,
        components_factory=app.kernel_specs,
        fallback=fallback, native_tile=app.native_tile,
        missing=missing, record_hint=app.record_hint, **opts)


def _pallas_calibrate(app: App) -> Any:
    if app.calibrated_fallback is None:
        return None
    return app.calibrated_fallback()


register_backend(Backend(
    name="analytical",
    description="closed-form models (HLS scheduler / XLA roofline); "
                "no recordings needed",
    measured=False,
    make_tool=_analytical_tool,
))

register_backend(Backend(
    name="pallas",
    description="measured Pallas kernels via MeasurementSet record/replay; "
                "unrecorded points fall back analytically",
    measured=True,
    make_tool=_pallas_tool,
    supports=_pallas_supports,
    supported_tiles=lambda app: app.available_tiles(),
    calibrate=_pallas_calibrate,
    explain=_pallas_explain,
))


# ----------------------------------------------------------------------
# the one session constructor
# ----------------------------------------------------------------------
def build_tool(app: App | str, backend: Backend | str = "analytical",
               **opts: Any) -> Any:
    """The oracle for (app, backend) without a session around it — what
    single-component benchmarks (fig4) and custom drives use."""
    app = get_app(app) if isinstance(app, str) else app
    backend = get_backend(backend) if isinstance(backend, str) else backend
    return backend.make_tool(app, **opts)


def build_session(app: App | str, backend: Backend | str = "analytical",
                  *, delta: Optional[float] = None, workers: int = 1,
                  share_plm: bool = False,
                  tile_sizes: Optional[Sequence[int]] = None,
                  tiles: Optional[Sequence[int]] = None,
                  tool: Any = None,
                  verify_plans: bool = False,
                  batch_pricing: bool = False,
                  guided: bool = False,
                  **kwargs: Any) -> ExplorationSession:
    """Build the :class:`ExplorationSession` for any registered
    workload x oracle pair.

    ``share_plm`` attaches the app's PLM planner and opens its tile
    axis (``tile_sizes`` overrides the app's per-backend default);
    ``tiles`` selects which recordings a measured backend loads
    (default: the app's ``default_tiles``); ``tool`` injects a
    pre-built oracle (skipping the backend factory).
    ``verify_plans=True`` turns on the strict map-phase post-pass:
    every memory plan the planner emits is independently re-proved
    race-free, capacity-feasible, and dominance-guarded by
    :mod:`repro.core.analysis.verify` before the session accepts it
    (only meaningful together with ``share_plm``).

    ``batch_pricing=True`` wraps an analytical tool in a
    :class:`~repro.core.pricing.BatchPricer` so every oracle request is
    a whole-grid lookup (bit-exact; non-analytical tools pass through
    unchanged).  ``guided=True`` additionally runs surrogate-guided
    characterization (:mod:`repro.core.surrogate`): the Algorithm-1
    walk prices from the grid and only the surrogate's top corner per
    component is confirmed through the real oracle — analytical
    backends only; raises for backends without a grid program.
    Remaining keywords flow to :class:`ExplorationSession`.
    """
    from .pricing import BatchPricer     # lazy: pricing imports backends
    app = get_app(app) if isinstance(app, str) else app
    backend = get_backend(backend) if isinstance(backend, str) else backend
    if tool is None and kwargs.get("ledger") is None:
        # a pre-built ledger already wraps its own tool; building one
        # here would be dead weight (and, for measured backends, I/O)
        tool = backend.make_tool(app, share_plm=share_plm, tiles=tiles)
    if guided:
        target = tool if tool is not None else kwargs["ledger"].tool
        pricer = BatchPricer.wrap(target)
        if not isinstance(pricer, BatchPricer):
            raise ValueError(
                f"guided characterization needs an analytical pricing "
                f"grid; backend {backend.name!r} tool "
                f"{type(target).__name__} has none (batch_pricing/guided "
                f"support HLSTool and XLATool)")
        kwargs.setdefault("pricer", pricer)
        if tool is not None:
            tool = pricer               # share one grid set end to end
    elif batch_pricing and tool is not None:
        tool = BatchPricer.wrap(tool)
    if share_plm:
        if app.plm_planner is not None:
            kwargs.setdefault("memory_planner", app.plm_planner())
        if tile_sizes is None:
            tile_sizes = (app.plm_tile_sizes_measured if backend.measured
                          else app.plm_tile_sizes)
    spaces = (app.knob_spaces(tile_sizes=tuple(tile_sizes))
              if tile_sizes else app.knob_spaces())
    return ExplorationSession(app.tmg(), tool, spaces,
                              delta=app.delta if delta is None else delta,
                              fixed=dict(app.fixed), workers=workers,
                              verify_plans=verify_plans,
                              **kwargs)


def build_query_session(query: DSEQuery, *, workers: Optional[int] = None,
                        **kwargs: Any) -> ExplorationSession:
    """Resolve a :class:`~repro.core.session.DSEQuery` into a session —
    the service's per-tenant resolution point.

    Unknown app/backend names raise the registry's listing errors
    *synchronously* (the service validates at submit time, before a
    query ever occupies a queue slot).  ``workers`` overrides the
    query's own fan-out; remaining keywords (``tool``, ``ledger``,
    ``verify_plans``, ...) flow to :func:`build_session`.
    """
    return build_session(
        query.app, query.backend, delta=query.delta,
        workers=query.workers if workers is None else workers,
        share_plm=query.share_plm, tile_sizes=query.tile_sizes,
        tiles=query.tiles, **kwargs)
