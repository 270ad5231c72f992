"""WAMI steepest-descent images + Gauss-Newton Hessian as Pallas kernels.

Two stages of the inverse-compositional Lucas-Kanade template side,
sharing the COSMOS knob geometry of DESIGN.md §2 (``ports`` column
lane-banks x ``unrolls`` rows per grid step):

  * ``steepest_descent_kernel`` — elementwise with global coordinates:
    sd = (gx*x, gx*y, gx, gy*x, gy*y, gy).  The affine-warp Jacobian
    coordinates are rebuilt in-kernel from ``program_id`` block offsets
    + iota, so no coordinate planes are streamed from HBM;
  * ``hessian_kernel`` — the reduction H = sum_x sd(x)^T sd(x): each
    grid step contracts its (6, bh*bw) block on the MXU and accumulates
    into a single (6, 6) output block shared by every step, which forces
    an ``arbitrary`` (sequential) grid walk.  The ops wrapper hands each
    step its block already flattened (the banked layout of
    ``wami_common`` with the (bh, bw) pixels merged), and the
    contraction runs at full f32 precision so the result does not depend
    on the MXU's default bf16 passes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..wami_common import (banked_call, grid_steps_model, knob_blocks,
                           to_banks, vmem_bytes_model)

__all__ = ["steepest_descent_kernel", "hessian_kernel",
           "vmem_bytes", "grid_steps", "hessian_vmem_bytes"]

_N_IN, _N_OUT = 2, 6      # steepest descent: gx, gy -> 6 sd planes


def _sd_kernel(g_ref, sd_ref):
    _, bh, bw = g_ref.shape
    gx, gy = g_ref[0], g_ref[1]
    yy = (jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 0)
          + pl.program_id(0) * bh).astype(gx.dtype)
    xx = (jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 1)
          + pl.program_id(1) * bw).astype(gx.dtype)
    sd_ref[0] = gx * xx
    sd_ref[1] = gx * yy
    sd_ref[2] = gx
    sd_ref[3] = gy * xx
    sd_ref[4] = gy * yy
    sd_ref[5] = gy


def steepest_descent_kernel(gx: jnp.ndarray, gy: jnp.ndarray, *,
                            ports: int = 1, unrolls: int = 8,
                            interpret: bool = False) -> jnp.ndarray:
    """gx, gy: (H, W) image gradients -> sd images (H, W, 6)."""
    return banked_call(_sd_kernel, jnp.stack([gx, gy], axis=-1), _N_OUT,
                       name="steep_descent", ports=ports, unrolls=unrolls,
                       interpret=interpret)


def _hessian_kernel(sd_ref, out_ref):
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    flat = sd_ref[...]                                          # (6, bh*bw)
    out_ref[...] += jax.lax.dot_general(
        flat, flat, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=out_ref.dtype)


def hessian_kernel(sd: jnp.ndarray, *, ports: int = 1, unrolls: int = 8,
                   interpret: bool = False) -> jnp.ndarray:
    """sd: (H, W, 6) steepest-descent images -> Hessian (6, 6)."""
    H, W, _ = sd.shape
    bh, bw = knob_blocks(H, W, ports=ports, unrolls=unrolls)
    flat = to_banks(sd, ports=ports, unrolls=unrolls).reshape(
        H // bh, ports, 6, bh * bw)
    return pl.pallas_call(
        _hessian_kernel,
        grid=(H // bh, ports),
        in_specs=[pl.BlockSpec((pl.squeezed, pl.squeezed, 6, bh * bw),
                               lambda i, j: (i, j, 0, 0))],
        out_specs=pl.BlockSpec((6, 6), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((6, 6), sd.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="hessian",
    )(flat)


vmem_bytes = functools.partial(vmem_bytes_model, n_in=_N_IN, n_out=_N_OUT)
grid_steps = grid_steps_model


def hessian_vmem_bytes(H: int, W: int, *, ports: int, unrolls: int,
                       dtype_bytes: int = 4) -> int:
    """Six sd input blocks + the resident (6, 6) accumulator."""
    return (6 * unrolls * (W // ports) + 36) * dtype_bytes
