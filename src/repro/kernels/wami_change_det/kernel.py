"""WAMI change detection (per-pixel GMM, K=3) as a Pallas kernel.

The heaviest WAMI stage: every pixel carries a K=3 Gaussian-mixture
background state (mu, var, w) that is matched, updated, and renormalized
each frame.  Knob geometry per DESIGN.md §2 (``ports`` lane-banks x
``unrolls`` rows per grid step); the gray plane and the 3K mixture
planes ride in one banked stack (``wami_common``), so each grid step
owns the full mixture for its tile.

The argmin/one-hot over K is unrolled by hand (K=3): first-index
tie-breaking matches ``jnp.argmin`` exactly, and the unrolled compares
stay elementwise on the VPU instead of forcing a cross-lane reduction.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from ..wami_common import banked_call, grid_steps_model, vmem_bytes_model

__all__ = ["change_detection_kernel", "vmem_bytes", "grid_steps"]

_K = 3
# gray + 3 state planes of K=3 in; mask + 3 state planes of K=3 out
_N_IN, _N_OUT = 1 + 3 * _K, 1 + 3 * _K


def _first_min_onehot(v0, v1, v2):
    """One-hot of argmin over three planes, first index wins ties."""
    b0 = (v0 <= v1) & (v0 <= v2)
    b1 = (~b0) & (v1 <= v2)
    b2 = ~(b0 | b1)
    return b0, b1, b2


def _kernel(in_ref, out_ref, *, lr, mahal, fg):
    x = in_ref[0:1]                                    # (1, bh, bw)
    mu = in_ref[1:1 + _K]                              # (K, bh, bw)
    var = in_ref[1 + _K:1 + 2 * _K]
    w = in_ref[1 + 2 * _K:]
    d2 = (x - mu) ** 2 / jnp.maximum(var, 1e-4)
    match = d2 < mahal
    any_match = match[0] | match[1] | match[2]
    inf = jnp.inf
    dm = jnp.where(match, d2, inf)
    b0, b1, b2 = _first_min_onehot(dm[0], dm[1], dm[2])
    onehot = (jnp.stack([b0, b1, b2]) & any_match[None]).astype(mu.dtype)

    mu_n = mu + onehot * lr * (x - mu)
    var_n = var + onehot * lr * ((x - mu) ** 2 - var)
    w_n = (1 - lr) * w + lr * onehot
    # no match: replace the weakest component with a fresh one at x
    k0, k1, k2 = _first_min_onehot(w[0], w[1], w[2])
    wh = (jnp.stack([k0, k1, k2]) & (~any_match)[None]).astype(mu.dtype)
    mu_n = mu_n * (1 - wh) + wh * x
    var_n = var_n * (1 - wh) + wh * 25.0
    w_n = w_n * (1 - wh) + wh * lr
    w_n = w_n / (w_n[0] + w_n[1] + w_n[2])[None]
    # foreground: matched component is low-weight, or no match at all
    matched_w = (onehot * w).sum(axis=0)
    mask = (~any_match) | (matched_w < (1.0 - fg))
    out_ref[0] = mask.astype(mu.dtype)
    out_ref[1:1 + _K] = mu_n
    out_ref[1 + _K:1 + 2 * _K] = var_n
    out_ref[1 + 2 * _K:] = w_n


def change_detection_kernel(gray: jnp.ndarray, mu: jnp.ndarray,
                            var: jnp.ndarray, w: jnp.ndarray, *,
                            ports: int = 1, unrolls: int = 8,
                            lr: float = 0.05, mahal_thresh: float = 6.25,
                            fg_thresh: float = 0.7,
                            interpret: bool = False):
    """gray: (H, W); mu/var/w: (H, W, K=3) mixture state.

    Returns (mask (H, W) in {0.0, 1.0}, mu', var', w') with state in the
    (H, W, K) layout of the reference.
    """
    planes = jnp.concatenate([gray[..., None], mu, var, w], axis=-1)
    out = banked_call(
        functools.partial(_kernel, lr=lr, mahal=mahal_thresh, fg=fg_thresh),
        planes, _N_OUT, name="change_det", ports=ports, unrolls=unrolls,
        interpret=interpret)
    return (out[..., 0], out[..., 1:1 + _K], out[..., 1 + _K:1 + 2 * _K],
            out[..., 1 + 2 * _K:])


vmem_bytes = functools.partial(vmem_bytes_model, n_in=_N_IN, n_out=_N_OUT)
grid_steps = grid_steps_model
