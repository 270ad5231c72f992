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
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Mapping, Optional

__all__ = ["ENV_VAR", "REPO_CACHE_DIR", "compile_cache_dir",
           "enable_compile_cache", "CompileEvents", "cache_outcome",
           "compile_events"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
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
    """

    def __init__(self):
        self._local = threading.local()

    def _tally(self) -> Dict[str, float]:
        tally = getattr(self._local, "tally", None)
        if tally is None:
            tally = self._local.tally = {"trace_s": 0.0, "mlir_s": 0.0,
                                         "cache_requests": 0,
                                         "cache_hits": 0}
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
