"""Jitted public wrapper for the flash-attention kernel.

Accepts the model-layout tensors (B, S, H, hd) and dispatches to the
Pallas kernel (TPU) or the jnp oracle (any backend).  ``interpret=True``
runs the kernel body in Python on CPU — how the tests validate it here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention
from .ref import attention_ref

__all__ = ["mha", "mha_ref"]


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "q_offset", "block_q",
                                             "block_kv", "block_h",
                                             "vmem_limit_bytes",
                                             "use_pallas", "interpret"))
def mha(q, k, v, *, causal=True, window=0, softcap=0.0, q_offset=0,
        block_q=128, block_kv=128, block_h=1, vmem_limit_bytes=None,
        use_pallas=True, interpret=False):
    """q: (B, Sq, H, hd); k, v: (B, Skv, K, hd) -> (B, Sq, H, hd)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if use_pallas:
        o = flash_attention(qt, kt, vt, causal=causal, window=window,
                            softcap=softcap, q_offset=q_offset,
                            block_q=block_q, block_kv=block_kv,
                            block_h=block_h,
                            vmem_limit_bytes=vmem_limit_bytes,
                            interpret=interpret)
    else:
        o = attention_ref(qt, kt, vt, causal=causal, window=window,
                          softcap=softcap, q_offset=q_offset)
    return o.transpose(0, 2, 1, 3)


def mha_ref(q, k, v, **kw):
    kw.pop("use_pallas", None)
    kw.pop("interpret", None)
    kw.pop("block_q", None)
    kw.pop("block_kv", None)
    kw.pop("block_h", None)
    kw.pop("vmem_limit_bytes", None)
    return mha(q, k, v, use_pallas=False, **kw)
