"""WAMI debayer (bilinear RGGB demosaic) as a Pallas kernel.

COSMOS knobs follow the shared banked geometry of ``wami_common``
(DESIGN.md §2): ``ports`` column lane-banks x ``unrolls`` rows per grid
step.  Like the gradient,
the halo problem is solved the TPU way: the ops wrapper materializes the
nine shifted views (center + 8-neighbourhood) with XLA slices, and the
kernel consumes aligned blocks.  The RGGB parity pattern is recovered
in-kernel from the global pixel coordinates (``program_id`` x block
offsets + iota), so any block size works — blocks need not align to the
2x2 Bayer quad.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..wami_common import banked_call, grid_steps_model, vmem_bytes_model

__all__ = ["debayer_kernel", "vmem_bytes", "grid_steps"]

_N_IN, _N_OUT = 9, 3


def _kernel(v_ref, rgb_ref):
    _, bh, bw = v_ref.shape
    c, n, s, w, e, nw, ne, sw, se = (v_ref[k] for k in range(_N_IN))
    cross = (n + s + w + e) * 0.25
    diag = (nw + ne + sw + se) * 0.25
    horiz = (w + e) * 0.5
    vert = (n + s) * 0.5

    # global pixel parity: the block at grid cell (i, j) starts at row
    # i*bh, column j*bw of the full frame
    yy = (jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 0)
          + pl.program_id(0) * bh)
    xx = (jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 1)
          + pl.program_id(1) * bw)
    even_y, even_x = (yy % 2) == 0, (xx % 2) == 0
    r_loc = even_y & even_x                  # (0,0)=R
    g1_loc = even_y & (~even_x)              # (0,1)=G
    g2_loc = (~even_y) & even_x              # (1,0)=G
    b_loc = (~even_y) & (~even_x)            # (1,1)=B

    rgb_ref[0] = jnp.where(r_loc, c, jnp.where(g1_loc, horiz,
                           jnp.where(g2_loc, vert, diag)))
    rgb_ref[1] = jnp.where(r_loc | b_loc, cross, c)
    rgb_ref[2] = jnp.where(b_loc, c, jnp.where(g2_loc, horiz,
                           jnp.where(g1_loc, vert, diag)))


def debayer_kernel(bayer: jnp.ndarray, *, ports: int = 1, unrolls: int = 8,
                   interpret: bool = False) -> jnp.ndarray:
    """bayer: (H, W) RGGB mosaic -> (H, W, 3) float32 RGB."""
    img = bayer.astype(jnp.float32)
    p = jnp.pad(img, 1, mode="reflect")
    views = (p[1:-1, 1:-1],                              # c
             p[:-2, 1:-1], p[2:, 1:-1],                  # n, s
             p[1:-1, :-2], p[1:-1, 2:],                  # w, e
             p[:-2, :-2], p[:-2, 2:],                    # nw, ne
             p[2:, :-2], p[2:, 2:])                      # sw, se
    return banked_call(_kernel, jnp.stack(views, axis=-1), _N_OUT,
                       name="debayer", ports=ports, unrolls=unrolls,
                       interpret=interpret)


vmem_bytes = functools.partial(vmem_bytes_model, n_in=_N_IN, n_out=_N_OUT)
grid_steps = grid_steps_model
