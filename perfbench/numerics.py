"""Precision of the plain references and of their controls.

A reference runs in one of three precisions:

  * ``exact``    -- float64 for every operation;
  * ``high``     -- float32 elementwise work; each matrix product in three
                    bfloat16 passes (the split product XLA calls
                    ``Precision.HIGH``), accumulated in float32;
  * ``bfloat16`` -- every operation rounded to bfloat16.

A configuration states the precision of each kernel (``float32`` or
``float32-highest`` for kernels whose contractions run at
``Precision.HIGHEST``); the control of a kernel is its reference one step
below that: ``bfloat16`` for float32, ``high`` for float32 at highest.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

CONTROL_OF = {"float32": "bfloat16", "float32-highest": "high"}


def dtype(prec: str):
    """The element type of elementwise work at ``prec``."""
    return {"exact": np.float64, "high": np.float32,
            "bfloat16": ml_dtypes.bfloat16}[prec]


def cast(prec: str, *arrays):
    dt = dtype(prec)
    out = tuple(np.asarray(a).astype(dt) for a in arrays)
    return out if len(out) != 1 else out[0]


def _split_bf16(a: np.ndarray):
    a = np.asarray(a, np.float32)
    hi = a.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (a - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def einsum(prec: str, spec: str, a, b):
    """``np.einsum(spec, a, b)`` as a matrix unit computes it at ``prec``."""
    if prec == "exact":
        return np.einsum(spec, np.asarray(a, np.float64),
                         np.asarray(b, np.float64))
    if prec == "bfloat16":
        a16 = np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float32)
        b16 = np.asarray(b).astype(ml_dtypes.bfloat16).astype(np.float32)
        return np.einsum(spec, a16, b16).astype(ml_dtypes.bfloat16)
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    return (np.einsum(spec, ah, bh) + np.einsum(spec, ah, bl)
            + np.einsum(spec, al, bh)).astype(np.float32)


def rel_err(out, ref) -> float:
    """Largest absolute gap over the largest magnitude of ``ref``."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape:
        return float("inf")
    scale = float(np.max(np.abs(ref))) if ref.size else 0.0
    gap = float(np.max(np.abs(out - ref))) if ref.size else 0.0
    if not np.isfinite(gap):
        return float("inf")
    return gap / scale if scale > 0 else gap
