"""Where JAX keeps its persistent compilation cache for this repo's
entry points (``chip_smoke.py``, ``examples/wami_pallas.py``,
``benchmarks/fleet_dse.py``, ``benchmarks/run.py``).

The cache is keyed by its path, so the path must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself, and nothing
    here sets another directory;
  * otherwise — a fixed directory inside the checkout,
    ``<repo>/.jax_cache`` (listed in ``.gitignore``).

The measured kernels compile in well under a second, below JAX's
default one-second floor for caching an entry, so the floor is set to
zero.  Nothing calls :func:`enable_compile_cache` at import: tests and
library users keep JAX's own configuration.

:func:`compile_events` is the process-wide tally of what JAX reports
while a program lowers and compiles (its ``jax.monitoring`` events):
seconds of jaxpr tracing and of jaxpr-to-MLIR conversion, and the
compiles that asked the persistent cache and those it served.  It
registers its listeners on first use, never at import.

:class:`PointProgram` wraps a kernel point's ``jax.jit`` program so that
a point whose executable is already on disk is not lowered again: it
keys the executable by the traced program, before lowering, and keeps
it in ``<cache dir>/pallas-points/`` beside JAX's own entries
(docs/backends.md).
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import tempfile
import threading
import warnings
from typing import Any, Dict, Iterator, Mapping, Optional

__all__ = ["ENV_VAR", "REPO_CACHE_DIR", "POINT_DIR", "compile_cache_dir",
           "enable_compile_cache", "CompileEvents", "cache_outcome",
           "point_outcome", "compile_events", "PointProgram", "point_key"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
POINT_DIR = "pallas-points"          # the point entries, inside the cache dir
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def compile_cache_dir(env: Optional[Mapping[str, str]] = None) -> str:
    """The cache directory in effect: ``JAX_COMPILATION_CACHE_DIR`` when
    it is set in ``env`` (default ``os.environ``), else the fixed
    in-checkout path."""
    env = os.environ if env is None else env
    return env.get(ENV_VAR) or REPO_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


# the two timed phases of lowering, as JAX names them (jax/_src/dispatch.py)
_LOWER_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": "mlir_s"}
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileEvents:
    """Per-thread tallies of JAX's lowering and compile-cache events.

    ``trace_s`` and ``mlir_s`` sum the outermost phases only: a phase
    that begins inside another (a jitted helper traced while its caller
    traces) is part of the outer one, so the two never add up to more
    than the wall they ran in.  ``cache_requests`` counts compiles that
    asked the persistent cache, ``cache_hits`` those it served.  JAX's
    own ``cache_misses`` event counts cache *writes*, which its size and
    compile-time floors skip, so a miss is a request without a hit.
    ``point_requests`` counts lowerings that looked for a point entry
    (:class:`PointProgram`), ``point_hits`` those that found one and so
    lowered nothing.
    """

    def __init__(self):
        self._local = threading.local()

    def _tally(self) -> Dict[str, float]:
        tally = getattr(self._local, "tally", None)
        if tally is None:
            tally = self._local.tally = {"trace_s": 0.0, "mlir_s": 0.0,
                                         "cache_requests": 0,
                                         "cache_hits": 0,
                                         "point_requests": 0,
                                         "point_hits": 0}
            self._local.depth = 0
        return tally

    def snapshot(self) -> Dict[str, float]:
        """This thread's tallies so far (subtract two to get a delta)."""
        return dict(self._tally())

    # -- jax.monitoring listeners ---------------------------------------
    def _phase_start(self, event: str, _value: float, **_kw) -> None:
        # JAX records a timed phase's start time as a scalar
        if event in _LOWER_EVENTS:
            self._tally()
            self._local.depth += 1

    def _phase_end(self, event: str, start: float, end: float,
                   **_kw) -> None:
        key = _LOWER_EVENTS.get(event)
        if key is None:
            return
        tally = self._tally()
        # a phase already open when the listener registered never
        # counted its start: keep the depth from going below zero
        self._local.depth = max(0, self._local.depth - 1)
        if self._local.depth == 0:
            tally[key] += end - start

    def _event(self, event: str, **_kw) -> None:
        if event == _CACHE_REQUEST:
            self._tally()["cache_requests"] += 1
        elif event == _CACHE_HIT:
            self._tally()["cache_hits"] += 1


def cache_outcome(before: Dict[str, float], after: Dict[str, float]) -> str:
    """What the persistent cache did for the compile between two
    snapshots: ``"off"`` (no cache directory, or JAX did not ask it),
    ``"hit"`` or ``"miss"``."""
    import jax
    asked = after["cache_requests"] - before["cache_requests"]
    if not asked or not jax.config.jax_compilation_cache_dir:
        return "off"
    return "hit" if after["cache_hits"] - before["cache_hits"] >= asked \
        else "miss"


def point_outcome(before: Dict[str, float], after: Dict[str, float]) -> str:
    """What the point cache did for the lowering between two snapshots:
    ``"off"`` (not asked: no cache directory, or not a
    :class:`PointProgram`), ``"hit"`` or ``"miss"``."""
    if after["point_requests"] == before["point_requests"]:
        return "off"
    return "hit" if after["point_hits"] > before["point_hits"] else "miss"


_EVENTS: Optional[CompileEvents] = None
_EVENTS_LOCK = threading.Lock()


def compile_events() -> CompileEvents:
    """The process's one :class:`CompileEvents`, registered with
    ``jax.monitoring`` on the first call (JAX keeps listeners for the
    life of the process)."""
    global _EVENTS
    with _EVENTS_LOCK:
        if _EVENTS is None:
            from jax import monitoring
            events = CompileEvents()
            monitoring.register_scalar_listener(events._phase_start)
            monitoring.register_event_time_span_listener(events._phase_end)
            monitoring.register_event_listener(events._event)
            _EVENTS = events
    return _EVENTS


# ----------------------------------------------------------------------
# the point cache: a kernel point's executable, keyed before lowering
# ----------------------------------------------------------------------
class PointProgram:
    """A kernel point's ``jax.jit`` program, for :class:`PallasOracle`.

    ``lower(*args)`` is ``jitted.lower(*args)`` when no persistent-cache
    directory is configured, or when an argument is not a concrete
    ``jax.Array`` (a shape lowered for a described device).  Otherwise
    it traces once and looks the traced program's :func:`point_key` up
    in ``<cache dir>/pallas-points/``.  On a hit nothing is lowered, and
    ``compile()`` loads the stored executable.  On a miss it lowers that
    same trace; ``compile()`` compiles as JAX always does (its own
    persistent cache included) and then stores the executable the
    backend made.  An entry that does not read back whole is a miss,
    and is written again.
    """

    __slots__ = ("jitted",)

    def __init__(self, jitted: Any):
        self.jitted = jitted

    def lower(self, *args):
        import jax
        root = jax.config.jax_compilation_cache_dir
        if not root or not all(isinstance(a, jax.Array) for a in args):
            return self.jitted.lower(*args)
        tally = compile_events()._tally()
        tally["point_requests"] += 1
        traced = self.jitted.trace(*args)
        path = os.path.join(root, POINT_DIR, point_key(traced, args))
        payload = _read_entry(path)
        if payload is None:
            return _PointMiss(traced.lower(), path)
        tally["point_hits"] += 1
        return _PointHit(traced, payload, path)


class _PointHit:
    """A point whose executable is on disk: nothing was lowered."""

    __slots__ = ("_traced", "_payload", "_path")

    def __init__(self, traced: Any, payload: bytes, path: str):
        self._traced, self._payload, self._path = traced, payload, path

    def compile(self):
        import pickle

        import jax
        from jax.experimental.serialize_executable import \
            deserialize_and_load
        try:
            return deserialize_and_load(self._payload, self._traced.in_tree,
                                        self._traced.out_tree)
        except (pickle.UnpicklingError, jax.errors.JaxRuntimeError) as e:
            # whole on disk but refused by this runtime: a refusal here
            # would read as the compiler's, so lower after all and store
            # the executable this runtime makes
            warnings.warn(f"kernel point in {self._path} not loadable, "
                          f"lowered again: {e}")
            return _PointMiss(self._traced.lower(), self._path).compile()


class _PointMiss:
    """A point lowered from its trace.  Its executable is stored once
    the backend has compiled it; a refusal raises and stores nothing.
    One that JAX's own cache served is not stored: an executable loaded
    from disk does not serialize whole again (XLA:CPU drops its
    functions), so the point is stored the next time it compiles."""

    __slots__ = ("_lowered", "_path")

    def __init__(self, lowered: Any, path: str):
        self._lowered, self._path = lowered, path

    def compile(self):
        events = compile_events()
        served = events.snapshot()["cache_hits"]
        compiled = self._lowered.compile()
        if events.snapshot()["cache_hits"] == served:
            _write_entry(self._path, compiled, self._lowered)
        return compiled


def point_key(traced: Any, args: tuple) -> str:
    """The sha256 a point's executable is stored under: the traced
    program (its printed jaxpr, every ``pallas_call``'s grid mapping in
    full, the constants by value), the inputs' types and placement, and
    what else decides the executable: jax and jaxlib, the backend's
    version, the device kind and count, ``XLA_FLAGS``,
    ``LIBTPU_INIT_ARGS`` and JAX's configuration (less its cache and
    logging settings)."""
    import jax
    import jaxlib
    import numpy as np
    h = hashlib.sha256()

    def put(*parts: Any) -> None:
        for part in parts:
            h.update(str(part).encode())
            h.update(b"\0")

    closed = traced.jaxpr
    put(closed)
    for grid in _grid_mappings(closed.jaxpr):
        put(grid)
    for const in closed.consts:
        value = np.asarray(const)
        put(value.dtype, value.shape)
        h.update(value.tobytes())
    for aval, arg in zip(jax.tree.leaves(traced.in_avals), args):
        put(aval.dtype, aval.shape, aval.weak_type, arg.sharding)
    devices = jax.devices()
    put(jax.__version__, jaxlib.__version__,
        devices[0].client.platform_version, devices[0].device_kind,
        len(devices), os.environ.get("XLA_FLAGS", ""),
        os.environ.get("LIBTPU_INIT_ARGS", ""))
    put(sorted((k, v) for k, v in jax.config.values.items()
               if "cache" not in k and "log" not in k))
    return h.hexdigest()


def _grid_mappings(jaxpr: Any) -> Iterator[str]:
    """Each ``pallas_call``'s grid mapping in ``jaxpr`` and the jaxprs
    inside it, in full: the printed jaxpr shows only its grid and block
    shapes, not the index maps."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield _describe(eqn.params["grid_mapping"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)       # a ClosedJaxpr's
                if hasattr(sub, "eqns"):
                    yield from _grid_mappings(sub)


def _describe(obj: Any) -> str:
    """Every field of a dataclass, recursively; a function by its name."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return type(obj).__name__ + "(" + ", ".join(
            f"{f.name}={_describe(getattr(obj, f.name))}"
            for f in dataclasses.fields(obj)) + ")"
    if isinstance(obj, (tuple, list)):
        return "(" + ", ".join(map(_describe, obj)) + ")"
    if callable(obj):
        return getattr(obj, "__qualname__", type(obj).__name__)
    return str(obj)


def _read_entry(path: str) -> Optional[bytes]:
    """The stored executable, or None when the entry is absent or does
    not match the digest written ahead of it."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return None
    digest, payload = blob[:32], blob[32:]
    return payload if hashlib.sha256(payload).digest() == digest else None


def _write_entry(path: str, compiled: Any, lowered: Any) -> None:
    """Store ``compiled`` at ``path`` behind its digest, and the sha256
    of the lowered module's text in ``<path>.mlir.sha256``; each file is
    written whole or not at all."""
    from jax.experimental.serialize_executable import serialize
    try:
        payload = serialize(compiled)[0]
    except (ValueError, NotImplementedError):
        return                       # JAX cannot serialize this executable
    text = hashlib.sha256(lowered.as_text().encode()).hexdigest()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        _write_atomic(path + ".mlir.sha256", (text + "\n").encode())
        _write_atomic(path, hashlib.sha256(payload).digest() + payload)
    except OSError as e:
        warnings.warn(f"kernel point not stored in {path}: {e}")


def _write_atomic(path: str, data: bytes) -> None:
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        os.unlink(tmp)
        raise
