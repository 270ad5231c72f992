"""WAMI affine warp (bilinear resample) as a Pallas kernel.

The gather is the part TPUs dislike: arbitrary per-pixel source
addresses do not map onto the VMEM tiling.  Following the wami_gradient
halo recipe (DESIGN.md §2), the ops wrapper performs the address
computation and the four neighbour gathers with XLA — where the
scatter/gather engine lives — and the Pallas kernel consumes six
aligned planes (i00, i01, i10, i11, fx, fy) and does the arithmetic
(the bilinear blend), knob-tiled into ``ports`` lane-banks x
``unrolls`` rows per grid step.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from ..wami_common import banked_call, grid_steps_model, vmem_bytes_model

__all__ = ["warp_blend_kernel", "warp_gather", "vmem_bytes", "grid_steps"]

_N_IN, _N_OUT = 6, 1


def _kernel(v_ref, out_ref):
    i00, i01, i10, i11, fx, fy = (v_ref[k] for k in range(_N_IN))
    top = i00 * (1 - fx) + i01 * fx
    bot = i10 * (1 - fx) + i11 * fx
    out_ref[0] = top * (1 - fy) + bot * fy


def warp_gather(img: jnp.ndarray, p: jnp.ndarray):
    """XLA side: affine source addresses + 4-neighbour gathers.

    x' = (1+p1) x + p2 y + p3 ;  y' = p4 x + (1+p5) y + p6.
    Returns (i00, i01, i10, i11, fx, fy), each (H, W).
    """
    H, W = img.shape
    yy, xx = jnp.meshgrid(jnp.arange(H, dtype=img.dtype),
                          jnp.arange(W, dtype=img.dtype), indexing="ij")
    sx = (1.0 + p[0]) * xx + p[1] * yy + p[2]
    sy = p[3] * xx + (1.0 + p[4]) * yy + p[5]
    x0 = jnp.clip(jnp.floor(sx), 0, W - 2)
    y0 = jnp.clip(jnp.floor(sy), 0, H - 2)
    fx = jnp.clip(sx - x0, 0.0, 1.0)
    fy = jnp.clip(sy - y0, 0.0, 1.0)
    x0i, y0i = x0.astype(jnp.int32), y0.astype(jnp.int32)
    return (img[y0i, x0i], img[y0i, x0i + 1],
            img[y0i + 1, x0i], img[y0i + 1, x0i + 1], fx, fy)


def warp_blend_kernel(img: jnp.ndarray, p: jnp.ndarray, *, ports: int = 1,
                      unrolls: int = 8, interpret: bool = False
                      ) -> jnp.ndarray:
    """img: (H, W), p: affine params (6,) -> warped (H, W)."""
    planes = jnp.stack(warp_gather(img, p), axis=-1)
    return banked_call(_kernel, planes, 1, name="warp", ports=ports,
                       unrolls=unrolls, interpret=interpret,
                       out_dtype=img.dtype)[..., 0]


vmem_bytes = functools.partial(vmem_bytes_model, n_in=_N_IN, n_out=_N_OUT)
grid_steps = grid_steps_model
