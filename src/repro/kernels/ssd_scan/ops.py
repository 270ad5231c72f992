"""Jitted wrapper for the SSD scan kernel (Pallas or jnp oracle)."""

from __future__ import annotations

import functools

import jax

from .kernel import ssd_scan
from .ref import ssd_ref

__all__ = ["ssd", "ssd_oracle"]


@functools.partial(jax.jit, static_argnames=("chunk", "block_h",
                                             "vmem_limit_bytes",
                                             "use_pallas", "interpret"))
def ssd(x, dt, A, B, C, *, chunk=128, block_h=1, vmem_limit_bytes=None,
        use_pallas=True, interpret=False):
    if use_pallas:
        return ssd_scan(x, dt, A, B, C, chunk=chunk, block_h=block_h,
                        vmem_limit_bytes=vmem_limit_bytes,
                        interpret=interpret)
    return ssd_ref(x, dt, A, B, C)


def ssd_oracle(x, dt, A, B, C):
    return ssd_ref(x, dt, A, B, C)
