"""Flash attention as a Pallas TPU kernel.

TPU-native adaptation of the GPU flash-attention insight (DESIGN.md §2):
the streaming-softmax tiling is kept, but blocks are sized for VMEM and
the MXU — (block_q x d) and (block_kv x d) tiles with d and block sizes
multiples of 128 so both matmuls hit the 128x128 systolic array, and the
running (m, l, acc) state lives in VMEM scratch across the sequential
KV grid dimension (no shared-memory/warp semantics to port).

Grid: (B, H/block_h, Sq/block_q, Skv/block_kv) with the LAST dimension
sequential ("arbitrary") — each (b, head block, iq) walks its KV blocks
in order, accumulating into scratch, and writes the normalized output
tiles on the final block.  A grid step holds ``block_h`` query heads
(one by default) and runs them one after another on the same KV block
index.  GQA is expressed in the k/v BlockSpec index maps (head h reads
KV head h // group), so no KV duplication ever materializes.

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
            q_offset: int, block_q: int, block_kv: int, n_kv: int,
            block_h: int, group: int):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0) + q_offset
    kv_pos = ikv * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    dist = q_pos - kv_pos
    ok = jnp.ones((block_q, block_kv), dtype=jnp.bool_)
    if causal:
        ok &= dist >= 0
    if window and window > 0:
        ok &= dist < window

    # the block's heads one after another; query head j of the block
    # reads KV head j // group of the block's KV heads
    for j in range(block_h):
        q = q_ref[0, j].astype(jnp.float32) * scale            # (bq, d)
        k = k_ref[0, j // group].astype(jnp.float32)           # (bkv, d)
        v = v_ref[0, j // group].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=_HIGHEST)             # (bq, bkv)
        if softcap and softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[j]                                       # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        # fully-masked rows: p would be exp(NEG_INF - NEG_INF) = 1; zero them
        p = jnp.where(ok, p, 0.0)
        l_scr[j] = l_scr[j] * corr + jnp.sum(p, axis=1)
        acc_scr[j] = acc_scr[j] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=_HIGHEST)
        m_scr[j] = m_new

    @pl.when(ikv == n_kv - 1)
    def _finish():
        for j in range(block_h):
            l = l_scr[j]
            o_ref[0, j] = (acc_scr[j] / jnp.maximum(l, 1e-30)[:, None]
                           ).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0,
                    block_q: int = 128, block_kv: int = 128,
                    block_h: int = 1, vmem_limit_bytes: int | None = None,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, Sq, d); k, v: (B, K, Skv, d).  Returns (B, H, Sq, d).

    ``block_h`` query heads share a grid step (H % block_h == 0, and
    block_h a multiple or a divisor of the GQA group H // K);
    ``vmem_limit_bytes`` declares the kernel's scoped VMEM (None keeps
    the compiler's default)."""
    B, H, Sq, d = q.shape
    K, Skv = k.shape[1], k.shape[2]
    assert H % K == 0, "GQA requires H % K == 0"
    G = H // K
    assert H % block_h == 0 and (block_h % G == 0 or G % block_h == 0)
    kv_h = max(1, block_h // G)             # KV heads a grid step reads
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    nq, nkv = Sq // block_q, Skv // block_kv
    scale = 1.0 / math.sqrt(d)

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_kv=block_kv, n_kv=nkv,
        block_h=block_h, group=G)

    def kv_block(b, h, iq, ikv):
        return (b, h * block_h // G // kv_h, ikv, 0)

    return pl.pallas_call(
        kern,
        grid=(B, H // block_h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, block_h, block_q, d),
                         lambda b, h, iq, ikv: (b, h, iq, 0)),
            pl.BlockSpec((1, kv_h, block_kv, d), kv_block),
            pl.BlockSpec((1, kv_h, block_kv, d), kv_block),
        ],
        out_specs=pl.BlockSpec((1, block_h, block_q, d),
                               lambda b, h, iq, ikv: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_h, block_q), jnp.float32),
            pltpu.VMEM((block_h, block_q), jnp.float32),
            pltpu.VMEM((block_h, block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
