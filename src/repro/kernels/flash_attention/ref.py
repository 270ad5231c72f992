"""Pure-jnp oracle for the flash-attention kernel.

Materializes the full (Sq, Skv) score matrix — O(S^2) memory, fine for
test sizes, numerically the ground truth the kernel must match.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["attention_ref"]


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, window: int = 0,
                  softcap: float = 0.0, q_offset: int = 0) -> jnp.ndarray:
    """q: (B, H, Sq, d); k, v: (B, K, Skv, d) with H % K == 0.

    ``q_offset``: absolute position of q[0] (decode: Skv - Sq).
    """
    B, H, Sq, d = q.shape
    K = k.shape[1]
    G = H // K
    qf = q.astype(jnp.float32) / math.sqrt(d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(B, K, G, Sq, d)
    # full f32 precision: a TPU's default matmul would round to bf16
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bkgqd,bksd->bkgqs", qf, kf, precision=hi)
    if softcap and softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    q_pos = jnp.arange(Sq) + q_offset
    kv_pos = jnp.arange(k.shape[2])
    dist = q_pos[:, None] - kv_pos[None, :]
    ok = jnp.ones_like(dist, dtype=bool)
    if causal:
        ok &= dist >= 0
    if window and window > 0:
        ok &= dist < window
    s = jnp.where(ok[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", p, vf, precision=hi)
    return o.reshape(B, H, Sq, d).astype(q.dtype)
