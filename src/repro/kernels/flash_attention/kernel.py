"""Flash attention as a Pallas TPU kernel.

TPU-native adaptation of the GPU flash-attention insight (DESIGN.md §2):
the streaming-softmax tiling is kept, but blocks are sized for VMEM and
the MXU — (block_q x d) and (block_kv x d) tiles with d and block sizes
multiples of 128 so both matmuls hit the 128x128 systolic array, and the
running (m, l, acc) state lives in VMEM scratch across the sequential
KV grid dimension (no shared-memory/warp semantics to port).

Grid: (B, H, Sq/block_q, Skv/block_kv) with the LAST dimension sequential
("arbitrary") — each (b, h, iq) walks its KV blocks in order,
accumulating into scratch, and writes the normalized output tile on the
final block.  GQA is expressed in the k/v BlockSpec index maps (head h
reads KV head h // group), so no KV duplication ever materializes.

Supports: causal masking, sliding windows (gemma2 local layers),
attention soft-capping, and a q_offset for decode alignment.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30
# f32 matmuls at full precision: the MXU's default would round the
# operands to bf16 and drift from the f32 reference
_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, softcap: float,
            q_offset: int, block_q: int, block_kv: int, n_kv: int):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=_HIGHEST)                 # (bq, bkv)
    if softcap and softcap > 0:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
        + q_offset
    kv_pos = ikv * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    dist = q_pos - kv_pos
    ok = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        ok &= dist >= 0
    if window and window > 0:
        ok &= dist < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]                                   # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    # fully-masked rows: p would be exp(NEG_INF - NEG_INF) = 1; zero them
    p = jnp.where(ok, p, 0.0)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=_HIGHEST)
    m_scr[...] = m_new

    @pl.when(ikv == n_kv - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, Sq, d); k, v: (B, K, Skv, d).  Returns (B, H, Sq, d)."""
    B, H, Sq, d = q.shape
    K, Skv = k.shape[1], k.shape[2]
    assert H % K == 0, "GQA requires H % K == 0"
    G = H // K
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    nq, nkv = Sq // block_q, Skv // block_kv
    scale = 1.0 / math.sqrt(d)

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_kv=block_kv, n_kv=nkv)

    return pl.pallas_call(
        kern,
        grid=(B, H, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, iq, ikv: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, iq, ikv: (b, h // G, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, iq, ikv: (b, h // G, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, iq, ikv: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
