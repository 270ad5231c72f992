"""Oracles: LK steepest-descent images + Gauss-Newton Hessian
(same math as apps.wami.components)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["steepest_descent_ref", "hessian_ref"]


def steepest_descent_ref(gx: jnp.ndarray, gy: jnp.ndarray) -> jnp.ndarray:
    H, W = gx.shape
    yy, xx = jnp.meshgrid(jnp.arange(H, dtype=gx.dtype),
                          jnp.arange(W, dtype=gx.dtype), indexing="ij")
    return jnp.stack([gx * xx, gx * yy, gx, gy * xx, gy * yy, gy], axis=-1)


def hessian_ref(sd: jnp.ndarray) -> jnp.ndarray:
    flat = sd.reshape(-1, 6)
    # full f32 precision: a TPU's default matmul would round to bf16
    return jnp.matmul(flat.T, flat, precision=jax.lax.Precision.HIGHEST)
