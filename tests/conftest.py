"""Shared pytest config.

NOTE: XLA_FLAGS / device-count forcing deliberately NOT set here — smoke
tests and benches run on the single real CPU device; only
launch/dryrun.py (its own process) forces 512 host devices.

When ``hypothesis`` is not installed (it is a test extra, not a runtime
dependency), a stub is installed into ``sys.modules`` BEFORE collection
so the property-test modules still import: every ``@given`` test body is
replaced with a clean ``pytest.skip`` and the rest of each module runs
normally.  ``pip install -e .[test]`` restores the real property tests.
"""

import contextlib
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------
# golden-artifact byte gates (fig10 / pricing / soc cells): one shared
# capture + load pair instead of per-module copies
# ----------------------------------------------------------------------
class CaptureReport:
    """Minimal stand-in for benchmarks.run's Report: keeps the lines
    one cell writes so a test can byte-compare them."""

    def __init__(self):
        self.lines = None

    def write(self, name, lines):
        self.lines = list(lines)

    def csv(self, *args, **kwargs):
        pass


@pytest.fixture
def bench_cell_lines():
    """Run one bench module's cell through a capture report and return
    its output exactly as `benchmarks.run` would write it to disk."""

    def _lines(mod, cell) -> str:
        report = CaptureReport()
        mod.run(report, cell)
        assert report.lines is not None
        return "\n".join(report.lines) + "\n"

    return _lines


@pytest.fixture
def committed_artifact():
    """Read a committed golden file under artifacts/bench/."""

    def _read(*parts) -> str:
        with open(os.path.join(REPO, "artifacts", "bench", *parts)) as f:
            return f.read()

    return _read


@contextlib.contextmanager
def _compile_cache_at(path):
    """JAX's persistent compilation cache in ``path`` (None: no cache)
    for one test, with no floor on what it keeps; JAX's own settings
    come back after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


@pytest.fixture
def compile_cache_at():
    """``with compile_cache_at(path):`` — JAX's persistent cache in
    ``path`` (None: none) for the block."""
    return _compile_cache_at


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent cache in a new directory for the whole test;
    yields that directory."""
    path = str(tmp_path / "cache")
    with _compile_cache_at(path):
        yield path


try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    import types

    import pytest

    def _given(*_args, **_kwargs):
        def deco(fn):
            # zero-arg replacement: pytest must not see the strategy
            # parameters, or it would try to resolve them as fixtures
            def _skipped_property_test():
                pytest.skip("hypothesis not installed "
                            "(pip install -e .[test])")
            _skipped_property_test.__name__ = fn.__name__
            _skipped_property_test.__doc__ = fn.__doc__
            _skipped_property_test.__module__ = fn.__module__
            return _skipped_property_test
        return deco

    def _passthrough(*_args, **_kwargs):
        def deco(fn):
            return fn
        return deco

    class _Strategy:
        """Inert placeholder for strategy objects built at import time."""

        def __init__(self, name="st"):
            self._name = name

        def __call__(self, *args, **kwargs):
            return self

        def __getattr__(self, item):
            return _Strategy(f"{self._name}.{item}")

        def __repr__(self):
            return f"<{self._name} stub>"

    _st = types.ModuleType("hypothesis.strategies")
    _st.__getattr__ = lambda name: _Strategy(f"st.{name}")

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _passthrough
    _hyp.example = _passthrough
    _hyp.assume = lambda *a, **k: True
    _hyp.strategies = _st
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st
