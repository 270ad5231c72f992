"""Pure-jnp oracle for the SSD scan: the naive sequential recurrence.

    h_t = exp(A * dt_t) * h_{t-1} + dt_t * (B_t outer x_t)
    y_t = C_t . h_t

O(S) sequential — slow but unambiguous ground truth.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ssd_ref"]


def ssd_ref(x, dt, A, B, C, h0=None):
    """x: (Bz, S, H, P); dt: (Bz, S, H); A: (H,); B, C: (Bz, S, N).

    Returns (y (Bz,S,H,P), h_final (Bz,H,P,N)).
    """
    Bz, S, H, P = x.shape
    N = B.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((Bz, H, P, N), jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.exp(dtt * A)[..., None, None]           # (Bz,H,1,1)
        contrib = (dtt[..., None, None]
                   * xt[..., :, None] * bt[:, None, None, :])  # (Bz,H,P,N)
        h = h * decay + contrib
        y = jnp.einsum("bn,bhpn->bhp", ct, h,
                       precision=jax.lax.Precision.HIGHEST)
        return h, y

    xs = (x.astype(jnp.float32).transpose(1, 0, 2, 3),
          dt.transpose(1, 0, 2),
          B.astype(jnp.float32).transpose(1, 0, 2),
          C.astype(jnp.float32).transpose(1, 0, 2))
    h, ys = lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3), h
