"""WAMI grayscale (BT.601 luma) as a Pallas kernel with COSMOS knobs.

Pure elementwise stage: three input planes (R, G, B), one output plane.
``ports``/``unrolls`` follow the shared banked geometry of
``wami_common`` (DESIGN.md §2): column lane-banks x rows per grid step.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from ..wami_common import banked_call, grid_steps_model, vmem_bytes_model

__all__ = ["grayscale_kernel", "vmem_bytes", "grid_steps"]

_N_IN, _N_OUT = 3, 1


def _kernel(rgb_ref, y_ref):
    y_ref[0] = (0.299 * rgb_ref[0] + 0.587 * rgb_ref[1]
                + 0.114 * rgb_ref[2])


def grayscale_kernel(rgb: jnp.ndarray, *, ports: int = 1, unrolls: int = 8,
                     interpret: bool = False) -> jnp.ndarray:
    """rgb: (H, W, 3) with W % ports == 0 and H % unrolls == 0 -> (H, W)."""
    return banked_call(_kernel, rgb, 1, name="grayscale", ports=ports,
                       unrolls=unrolls, interpret=interpret)[..., 0]


vmem_bytes = functools.partial(vmem_bytes_model, n_in=_N_IN, n_out=_N_OUT)
grid_steps = grid_steps_model
