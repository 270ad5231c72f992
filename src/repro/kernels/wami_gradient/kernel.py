"""WAMI gradient as a Pallas kernel with COSMOS-knob-driven BlockSpecs.

This is the paper's port/unroll knob pair made physical on TPU
(DESIGN.md §2):

  * ``ports``   -> number of column banks: the W axis is split into
    ``ports`` lane-blocks processed by parallel grid columns — the
    multi-bank PLM that Mnemosyne would generate, here as VMEM tiles;
  * ``unrolls`` -> rows computed per grid step (``block_h``): the loop
    body replication, trading VMEM footprint for fewer grid iterations.

Both knobs index the banked layout of ``wami_common``, so every knob
point's block spans its array's trailing dimensions and lowers on the
chip.

The halo problem (vertical neighbours across block boundaries) is solved
the TPU way: the ops wrapper materializes the four shifted views with
XLA slices and the kernel consumes aligned blocks — no shared-memory
halo exchange to port from the GPU idiom.

The COSMOS characterization of this kernel (ports x unrolls ->
VMEM bytes x grid steps) is exercised in benchmarks/fig4_motivational.py.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..wami_common import banked_call

__all__ = ["gradient_kernel", "vmem_bytes", "grid_steps"]


def _kernel(v_ref, g_ref):
    left, right, up, down = (v_ref[k] for k in range(4))
    g_ref[0] = (right - left) * 0.5
    g_ref[1] = (down - up) * 0.5


def gradient_kernel(gray: jnp.ndarray, *, ports: int = 1, unrolls: int = 8,
                    interpret: bool = False):
    """Central-difference gradient.  gray: (H, W) with W % ports == 0 and
    H % unrolls == 0.  Returns (gx, gy)."""
    p = jnp.pad(gray, 1, mode="edge")
    views = (p[1:-1, :-2], p[1:-1, 2:], p[:-2, 1:-1], p[2:, 1:-1])
    g = banked_call(_kernel, jnp.stack(views, axis=-1), 2, name="gradient",
                    ports=ports, unrolls=unrolls, interpret=interpret)
    return g[..., 0], g[..., 1]


def vmem_bytes(H: int, W: int, *, ports: int, unrolls: int,
               dtype_bytes: int = 4) -> int:
    """VMEM working set per grid step (4 in + 2 out blocks)."""
    return 6 * unrolls * (W // ports) * dtype_bytes


def grid_steps(H: int, W: int, *, ports: int, unrolls: int) -> int:
    """Sequential steps if one core walks the grid (latency model input)."""
    return (H // unrolls) * ports
