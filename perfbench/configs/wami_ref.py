"""Plain reference of the WAMI stage kernels, their seeded inputs, and
their VMEM areas, for the configurations whose ``reference`` is ``wami_ref``.

The stage semantics follow the COSMOS paper's WAMI case study (the
PERFECT suite's wide-area motion imagery kernels): RGGB bilinear
demosaic, BT.601 luma, central-difference gradients, Lucas-Kanade
steepest-descent images and Gauss-Newton Hessian, bilinear affine warp,
and per-pixel K=3 Gaussian-mixture change detection.  Every function is
written in numpy and takes its precision from ``numerics``; nothing here
imports the system under test.

Inputs are drawn so that every discrete decision (the warp's source cell,
the mixture's match and argmin) lies far from its boundary, so float32
and float64 make the same decision: the warp's shear keeps each source
fraction inside [0.43, 0.63], and change detection draws integer gray
levels and integer offsets of the mixture means, none at the match
threshold.
"""

from __future__ import annotations

import numpy as np

import numerics as nx

# fixed affine warp: x' = (1+p0) x + p1 y + p2, y' = p3 x + (1+p4) y + p5
WARP_P = (1 / 1024, -1 / 2048, 0.5, 1 / 2048, -1 / 1024, 0.5)
_K = 3
_LR, _MAHAL, _FG = 0.05, 6.25, 0.7
_VAR = 36.0
# mean offsets in gray levels; 15 is left out: 15**2 / 36 == 6.25, the
# match threshold itself
_OFFSETS = tuple(k for k in range(-30, 31) if abs(k) != 15)


def make_inputs(config, seed: int):
    """``{stage: (array, ...)}`` at the configured tile, made on the
    device in one jitted call from ``seed``."""
    import jax
    import jax.numpy as jnp

    t = int(config["tile"])

    @jax.jit
    def draw(key):
        ks = jax.random.split(key, 9)
        gray_i = jax.random.randint(ks[2], (t, t), 0, 256).astype(jnp.float32)
        offs = jnp.asarray(_OFFSETS, jnp.float32)
        mu = gray_i[..., None] + offs[jax.random.randint(
            ks[6], (t, t, _K), 0, len(_OFFSETS))]
        u = jax.random.uniform(ks[7], (t, t, _K), minval=0.5, maxval=1.5)
        w = u / jnp.sum(u, axis=-1, keepdims=True)
        gray = jax.random.uniform(ks[8], (t, t)) * 255.0
        return {
            "debayer": (jax.random.uniform(ks[0], (t, t)) * 1023.0,),
            "grayscale": (jax.random.uniform(ks[1], (t, t, 3)) * 255.0,),
            "gradient": (gray,),
            "steep_descent": (jax.random.normal(ks[3], (t, t)),
                              jax.random.normal(ks[4], (t, t))),
            "hessian": (jax.random.normal(ks[5], (t, t, 6)),),
            "warp": (gray, jnp.asarray(WARP_P, jnp.float32)),
            "change_det": (gray_i, mu, jnp.full((t, t, _K), _VAR, jnp.float32),
                           w),
        }

    from seeds import prng_key
    return draw(prng_key(seed))


# ----------------------------------------------------------------------
# stage references: numpy arrays in, tuple of numpy arrays out
# ----------------------------------------------------------------------
def debayer(prec, bayer):
    img = nx.cast(prec, bayer)
    H, W = img.shape
    p = np.pad(img, 1, mode="reflect")
    n, s = p[:-2, 1:-1], p[2:, 1:-1]
    w, e = p[1:-1, :-2], p[1:-1, 2:]
    c = p[1:-1, 1:-1]
    quarter, half = nx.cast(prec, 0.25), nx.cast(prec, 0.5)
    cross = (n + s + w + e) * quarter
    diag = (p[:-2, :-2] + p[:-2, 2:] + p[2:, :-2] + p[2:, 2:]) * quarter
    horiz = (w + e) * half
    vert = (n + s) * half
    yy, xx = np.meshgrid(np.arange(H), np.arange(W), indexing="ij")
    even_y, even_x = yy % 2 == 0, xx % 2 == 0
    r = np.where(even_y & even_x, c,
                 np.where(even_y, horiz, np.where(even_x, vert, diag)))
    g = np.where(even_y == even_x, cross, c)
    b = np.where(~even_y & ~even_x, c,
                 np.where(~even_y, horiz, np.where(~even_x, vert, diag)))
    return (np.stack([r, g, b], axis=-1),)


def grayscale(prec, rgb):
    rgb = nx.cast(prec, rgb)
    c = [nx.cast(prec, v) for v in (0.299, 0.587, 0.114)]
    return (c[0] * rgb[..., 0] + c[1] * rgb[..., 1] + c[2] * rgb[..., 2],)


def gradient(prec, gray):
    p = np.pad(nx.cast(prec, gray), 1, mode="edge")
    half = nx.cast(prec, 0.5)
    return ((p[1:-1, 2:] - p[1:-1, :-2]) * half,
            (p[2:, 1:-1] - p[:-2, 1:-1]) * half)


def steep_descent(prec, gx, gy):
    gx, gy = nx.cast(prec, gx, gy)
    H, W = gx.shape
    yy, xx = (nx.cast(prec, a) for a in
              np.meshgrid(np.arange(H), np.arange(W), indexing="ij"))
    return (np.stack([gx * xx, gx * yy, gx, gy * xx, gy * yy, gy], axis=-1),)


def hessian(prec, sd):
    flat = nx.cast("exact" if prec == "exact" else "high", sd).reshape(-1, 6)
    return (nx.einsum(prec, "ki,kj->ij", flat, flat),)


def warp(prec, img, p):
    img, p = nx.cast(prec, img, p)
    H, W = img.shape
    one = nx.cast(prec, 1.0)
    yy, xx = (nx.cast(prec, a) for a in
              np.meshgrid(np.arange(H), np.arange(W), indexing="ij"))
    sx = (one + p[0]) * xx + p[1] * yy + p[2]
    sy = p[3] * xx + (one + p[4]) * yy + p[5]
    x0 = np.clip(np.floor(sx), 0, W - 2)
    y0 = np.clip(np.floor(sy), 0, H - 2)
    fx = np.clip(sx - x0, 0, 1).astype(img.dtype)
    fy = np.clip(sy - y0, 0, 1).astype(img.dtype)
    xi, yi = x0.astype(np.int64), y0.astype(np.int64)
    top = img[yi, xi] * (one - fx) + img[yi, xi + 1] * fx
    bot = img[yi + 1, xi] * (one - fx) + img[yi + 1, xi + 1] * fx
    return (top * (one - fy) + bot * fy,)


def change_det(prec, gray, mu, var, w):
    gray, mu, var, w = nx.cast(prec, gray, mu, var, w)
    lr = nx.cast(prec, _LR)
    one = nx.cast(prec, 1.0)
    x = gray[..., None]
    d2 = (x - mu) ** 2 / np.maximum(var, nx.cast(prec, 1e-4))
    match = d2 < nx.cast(prec, _MAHAL)
    any_match = match.any(axis=-1)
    best = np.argmin(np.where(match, d2, np.inf), axis=-1)
    onehot = (np.eye(_K)[best] * any_match[..., None]).astype(gray.dtype)
    mu_n = mu + onehot * lr * (x - mu)
    var_n = var + onehot * lr * ((x - mu) ** 2 - var)
    w_n = (one - lr) * w + lr * onehot
    wh = (np.eye(_K)[np.argmin(w, axis=-1)]
          * (~any_match)[..., None]).astype(gray.dtype)
    mu_n = mu_n * (one - wh) + wh * x
    var_n = var_n * (one - wh) + wh * nx.cast(prec, 25.0)
    w_n = w_n * (one - wh) + wh * lr
    w_n = w_n / w_n.sum(axis=-1, keepdims=True)
    matched_w = (onehot * w).sum(axis=-1)
    mask = (~any_match) | (matched_w < one - nx.cast(prec, _FG))
    return (mask, mu_n, var_n, w_n)


REFERENCES = {"debayer": debayer, "grayscale": grayscale,
              "gradient": gradient, "steep_descent": steep_descent,
              "hessian": hessian, "warp": warp, "change_det": change_det}


def reference(name: str, prec: str, *inputs):
    """Stage ``name``'s outputs at precision ``prec``."""
    return REFERENCES[name](prec, *inputs)


def area_bytes(config, name: str, ports: int, unrolls: int) -> float:
    """VMEM area of a measured point: the double-buffered working set of
    every lane-bank plus a fixed overhead per bank.  A step holds
    ``vmem_blocks`` (unrolls, tile/ports) float32 blocks and
    ``vmem_words`` resident words."""
    k = config["kernels"][name]
    t = int(config["tile"])
    step = 4 * (k["vmem_blocks"] * unrolls * (t // ports) + k.get("vmem_words", 0))
    return float(2 * step * ports + config["bank_overhead_bytes"] * ports)
