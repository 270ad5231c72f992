"""Shared plumbing for the COSMOS-knob WAMI kernels (DESIGN.md §2).

Every WAMI stage kernel maps the paper's two knobs onto the same
BlockSpec/grid geometry:

  * ``ports``   -> number of column banks: the W axis splits into
    ``ports`` lane-blocks processed by parallel grid columns (the
    multi-bank PLM Mnemosyne would generate, as VMEM tiles);
  * ``unrolls`` -> rows computed per grid step (``block_h``): loop-body
    replication, trading VMEM footprint for fewer grid iterations.

The ops wrappers lay each stage's planes out *banked*: an (H, W, C)
plane stack becomes (H/unrolls, ports, C, unrolls, W/ports), row groups
and lane-banks as leading axes.  A grid cell's block (C, unrolls,
W/ports) then spans the array's trailing dimensions at every knob
point, which is what the TPU's (8, 128) tiling rule asks of a block
that is not itself tile-aligned — so ports > 1 and unrolls that are no
multiple of 8 lower on the chip, not only in interpret mode.

This module holds the knob -> (grid, BlockSpec) translation, the banked
layout, and the VMEM/grid cost models parameterized by the number of
input/output blocks a kernel touches per grid step.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["knob_blocks", "to_banks", "from_banks", "bank_spec",
           "banked_call", "vmem_bytes_model", "grid_steps_model"]


def knob_blocks(H: int, W: int, *, ports: int, unrolls: int
                ) -> Tuple[int, int]:
    """(block_h, block_w) for a knob pair; asserts the divisibility the
    real grid requires (the PallasOracle reports non-divisible knob
    points as infeasible instead of asserting)."""
    assert W % ports == 0, f"W={W} not divisible by ports={ports}"
    assert H % unrolls == 0, f"H={H} not divisible by unrolls={unrolls}"
    return unrolls, W // ports


def to_banks(planes: jnp.ndarray, *, ports: int, unrolls: int
             ) -> jnp.ndarray:
    """(H, W, C) plane stack -> banked (H/unrolls, ports, C, unrolls,
    W/ports): grid cell (i, j) owns rows [i*unrolls, (i+1)*unrolls) of
    lane-bank j, for all C planes."""
    H, W, C = planes.shape
    bh, bw = knob_blocks(H, W, ports=ports, unrolls=unrolls)
    return planes.reshape(H // bh, bh, ports, bw, C).transpose(0, 2, 4, 1, 3)


def from_banks(banked: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`to_banks`: banked -> (H, W, C)."""
    G, P, C, bh, bw = banked.shape
    return banked.transpose(0, 3, 1, 4, 2).reshape(G * bh, P * bw, C)


def bank_spec(channels: int, bh: int, bw: int) -> pl.BlockSpec:
    """Grid cell (i, j) -> the (channels, bh, bw) block of row group i,
    lane-bank j."""
    return pl.BlockSpec((pl.squeezed, pl.squeezed, channels, bh, bw),
                        lambda i, j: (i, j, 0, 0, 0))


def banked_call(body: Callable, planes: jnp.ndarray, n_out: int, *,
                name: str, ports: int, unrolls: int, interpret: bool,
                out_dtype: Optional[jnp.dtype] = None) -> jnp.ndarray:
    """Run ``body(in_ref, out_ref)`` over the (H/unrolls, ports) knob
    grid.  ``planes`` is the stage's (H, W, C) input stack; the kernel
    sees a (C, unrolls, W/ports) input block and writes an (n_out,
    unrolls, W/ports) output block.  Returns the (H, W, n_out) stack.
    Both grid axes are independent (elementwise/stencil stages).
    ``name`` names the ``pallas_call`` (the registry's component name),
    so its device op keeps that name whatever the Python helper is
    called."""
    H, W, C = planes.shape
    bh, bw = knob_blocks(H, W, ports=ports, unrolls=unrolls)
    out = pl.pallas_call(
        body,
        grid=(H // bh, ports),
        in_specs=[bank_spec(C, bh, bw)],
        out_specs=bank_spec(n_out, bh, bw),
        out_shape=jax.ShapeDtypeStruct((H // bh, ports, n_out, bh, bw),
                                       out_dtype or planes.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=name,
    )(to_banks(planes, ports=ports, unrolls=unrolls))
    return from_banks(out)


def vmem_bytes_model(H: int, W: int, *, ports: int, unrolls: int,
                     n_in: int, n_out: int, dtype_bytes: int = 4) -> int:
    """VMEM working set per grid step: ``n_in`` input + ``n_out`` output
    blocks of (unrolls, W/ports) words each."""
    return (n_in + n_out) * unrolls * (W // ports) * dtype_bytes


def grid_steps_model(H: int, W: int, *, ports: int, unrolls: int) -> int:
    """Sequential steps if one core walks the grid (latency model input)."""
    return (H // unrolls) * ports
