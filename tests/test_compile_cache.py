"""The point cache (``repro.launch.compile_cache.PointProgram``): a kernel
point whose executable the persistent cache directory holds is not
lowered again, and no two points of a kernel share a key.

Every knob point of a kernel computes the same function, so a key that
merged two points would hand back the wrong point's executable with
correct outputs; these tests are what guards the key."""

import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.apps.wami.pallas import wami_pallas_components
from repro.core import OracleLedger, PallasOracle, Tracer, WallClock
from repro.launch.compile_cache import (POINT_DIR, PointProgram,
                                        compile_events, point_key,
                                        point_outcome)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WAMI_STAGES = ("debayer", "grayscale", "gradient", "steep_descent",
               "hessian", "warp", "change_det")


def _entries(cache_dir):
    root = os.path.join(cache_dir, POINT_DIR)
    return sorted(os.listdir(root)) if os.path.isdir(root) else []


def _leaves_equal(a, b):
    a, b = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(a) == len(b) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b))


def _measure(name, tracer, tile=32, ports=2, unrolls=2):
    oracle = PallasOracle({name: wami_pallas_components(tile)[name]},
                          interpret=True)
    OracleLedger(oracle, tracer=tracer).synthesize(
        name, unrolls=unrolls, ports=ports)
    return oracle


def _key(name, ports, unrolls, tile=32):
    program, args = wami_pallas_components(tile)[name].build(
        ports, unrolls, True)
    return point_key(program.jitted.trace(*args), args)


def test_a_fresh_program_for_a_stored_point_lowers_nothing(
        persistent_cache):
    tracer = Tracer(WallClock())
    first = _measure("grayscale", tracer)
    second = _measure("grayscale", tracer)        # a new jax.jit, same point
    lower = tracer.spans("pallas.lower")
    comp = tracer.spans("pallas.compile")
    assert [s.attrs["point_cache"] for s in lower] == ["miss", "hit"]
    assert lower[0].attrs["mlir_s"] > 0 and lower[1].attrs["mlir_s"] == 0
    assert [s.attrs["cache"] for s in comp] == ["miss", "hit"]
    assert (first.stats["cache_hits"], second.stats["cache_hits"]) == (0, 1)
    assert second.stats["timed"] == 1
    # the stored executable computes what a fresh lowering does, bit for bit
    program, args = wami_pallas_components(32)["grayscale"].build(2, 2, True)
    hit = program.lower(*args)
    assert type(hit).__name__ == "_PointHit"
    assert _leaves_equal(hit.compile()(*args),
                         program.jitted.lower(*args).compile()(*args))


@pytest.mark.parametrize("stage", WAMI_STAGES)
def test_every_knob_point_of_a_kernel_has_its_own_key(stage):
    from repro.core.registry import get_app
    app = get_app("wami")
    space = app.knob_spaces()[stage]
    spec = app.kernel_specs(app.native_tile)[stage]
    keys = {}
    for ports in space.ports():
        for unrolls in range(1, space.max_unrolls + 1):
            if spec.divisible(ports, unrolls):
                program, args = spec.build(ports, unrolls, True)
                keys[ports, unrolls] = point_key(
                    program.jitted.trace(*args), args)
    assert len(keys) >= 20
    assert len(set(keys.values())) == len(keys)


@pytest.mark.parametrize("name,ports,unrolls",
                         [("grayscale", 1, 8), ("warp", 2, 4),
                          ("gradient", 4, 2)])
def test_an_entry_keeps_the_hash_of_its_lowered_text(persistent_cache, name,
                                                     ports, unrolls):
    program, args = wami_pallas_components(32)[name].build(ports, unrolls,
                                                           True)
    program.lower(*args).compile()
    key = point_key(program.jitted.trace(*args), args)
    assert _entries(persistent_cache) == [key, key + ".mlir.sha256"]
    with open(os.path.join(persistent_cache, POINT_DIR,
                           key + ".mlir.sha256")) as f:
        stored = f.read().strip()
    fresh = program.jitted.lower(*args).as_text()
    assert stored == hashlib.sha256(fresh.encode()).hexdigest()


POINTS = [("debayer", 1, 8), ("grayscale", 2, 4), ("warp", 4, 2),
          ("change_det", 2, 2)]


def test_keys_are_the_same_in_a_fresh_process():
    script = ("import json, sys\n"
              "from test_compile_cache import POINTS, _key\n"
              "json.dump([_key(*p) for p in POINTS], sys.stdout)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [os.path.join(REPO, "src"), os.path.dirname(__file__)]))
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=REPO,
                         capture_output=True, text=True, check=True).stdout
    assert json.loads(out) == [_key(*p) for p in POINTS]


def _scale_call(c, *, shape=(8, 128), dtype=jnp.float32, transpose=False):
    """A one-kernel program: ``x * c`` in blocks of a quarter, its index
    map swapping the block axes when ``transpose``."""
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * c

    half = (shape[0] // 2, shape[1] // 2)
    index = (lambda i, j: (j, i)) if transpose else (lambda i, j: (i, j))
    spec = pl.BlockSpec(half, index)

    def program(x):
        return pl.pallas_call(kernel, grid=(2, 2), in_specs=[spec],
                              out_specs=spec, interpret=True,
                              out_shape=jax.ShapeDtypeStruct(shape, dtype))(x)
    x = jnp.arange(np.prod(shape), dtype=dtype).reshape(shape)
    jitted = jax.jit(program)
    return point_key(jitted.trace(x), (x,))


def _offset_key(table):
    x = jnp.ones((8, 128), jnp.float32)
    jitted = jax.jit(lambda v: v + table)
    return point_key(jitted.trace(x), (x,))


def test_a_changed_constant_input_or_index_map_gets_a_new_key():
    base = _scale_call(2.0)
    assert _scale_call(2.0) == base
    assert len({base, _scale_call(3.0), _scale_call(2.0, dtype=jnp.bfloat16),
                _scale_call(2.0, shape=(16, 128)),
                _scale_call(2.0, shape=(16, 16)),
                _scale_call(2.0, shape=(16, 16), transpose=True)}) == 6
    table = np.zeros((8, 128), np.float32)
    other = table.copy()
    other[3, 77] = 1.0
    assert _offset_key(table) == _offset_key(table.copy())
    assert _offset_key(table) != _offset_key(other)


def test_without_a_cache_dir_lower_is_jaxs_own(compile_cache_at, tmp_path):
    program, args = wami_pallas_components(32)["grayscale"].build(2, 2, True)
    events = compile_events()
    before = events.snapshot()
    with compile_cache_at(None):
        lowered = program.lower(*args)
    assert isinstance(lowered, jax.stages.Lowered)
    assert point_outcome(before, events.snapshot()) == "off"
    # a shape lowered for a described device is JAX's own path too
    with compile_cache_at(str(tmp_path)):
        shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
        assert isinstance(program.lower(*shapes), jax.stages.Lowered)
    assert os.listdir(tmp_path) == []
    tracer = Tracer(WallClock())
    with compile_cache_at(None):
        _measure("grayscale", tracer)
    [span] = tracer.spans("pallas.lower")
    assert span.attrs["point_cache"] == "off"


def test_a_truncated_entry_is_a_miss_and_is_written_again(persistent_cache):
    # JAX keeps nothing of its own here, so each miss really compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    program, args = wami_pallas_components(32)["hessian"].build(2, 2, True)
    program.lower(*args).compile()
    key = point_key(program.jitted.trace(*args), args)
    path = os.path.join(persistent_cache, POINT_DIR, key)
    whole = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(whole[: len(whole) // 2])
    events = compile_events()
    before = events.snapshot()
    again, _ = wami_pallas_components(32)["hessian"].build(2, 2, True)
    lowered = again.lower(*args)
    assert point_outcome(before, events.snapshot()) == "miss"
    out = lowered.compile()(*args)
    assert _leaves_equal(out, program.jitted(*args))
    before = events.snapshot()                # written whole again: a hit
    third, _ = wami_pallas_components(32)["hessian"].build(2, 2, True)
    assert _leaves_equal(third.lower(*args).compile()(*args), out)
    assert point_outcome(before, events.snapshot()) == "hit"


def test_a_whole_entry_this_runtime_cannot_load_is_lowered_again(
        persistent_cache):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    program, args = wami_pallas_components(32)["grayscale"].build(2, 2, True)
    program.lower(*args).compile()
    key = point_key(program.jitted.trace(*args), args)
    junk = b"not an executable"
    with open(os.path.join(persistent_cache, POINT_DIR, key), "wb") as f:
        f.write(hashlib.sha256(junk).digest() + junk)
    again, _ = wami_pallas_components(32)["grayscale"].build(2, 2, True)
    with pytest.warns(UserWarning, match="not loadable, lowered again"):
        out = again.lower(*args).compile()(*args)
    assert _leaves_equal(out, program.jitted(*args))
    events = compile_events()
    before = events.snapshot()                 # stored again, and loadable
    third, _ = wami_pallas_components(32)["grayscale"].build(2, 2, True)
    assert _leaves_equal(third.lower(*args).compile()(*args), out)
    assert point_outcome(before, events.snapshot()) == "hit"


def test_an_executable_jax_loaded_from_its_cache_is_not_stored(
        persistent_cache):
    program, args = wami_pallas_components(32)["warp"].build(2, 2, True)
    program.jitted.lower(*args).compile()        # JAX's entry, no point entry
    events = compile_events()
    before = events.snapshot()
    again, _ = wami_pallas_components(32)["warp"].build(2, 2, True)
    out = again.lower(*args).compile()(*args)
    after = events.snapshot()
    assert after["cache_hits"] - before["cache_hits"] == 1
    assert point_outcome(before, after) == "miss"
    assert _entries(persistent_cache) == []
    assert _leaves_equal(out, program.jitted(*args))


_unlowerable = jax.extend.core.Primitive("unlowerable")
_unlowerable.def_abstract_eval(lambda x: x)


def test_a_point_refused_at_lowering_writes_nothing(persistent_cache):
    program = PointProgram(jax.jit(_unlowerable.bind))
    with pytest.raises(NotImplementedError):
        program.lower(jnp.ones((8, 128)))
    assert _entries(persistent_cache) == []
