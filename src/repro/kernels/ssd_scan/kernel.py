"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

TPU adaptation of the SSD "state-space duality" insight: within a chunk
of Q tokens the recurrence is a (Q x Q) masked-decay attention — an MXU
matmul — and across chunks only the (P x N) state is carried.  The carry
lives in VMEM scratch across a SEQUENTIAL chunk grid dimension, so the
kernel streams x/dt/B/C chunk tiles HBM->VMEM exactly once and never
materializes the (S x S) dual form.

Grid: (Bz, H/block_h, n_chunks), last dimension "arbitrary"
(sequential); a grid step holds ``block_h`` heads (one by default),
which share the step's B/C tiles and their (Q x Q) scores.
Block shapes: x (1,block_h,Q,P), dt as (1,block_h,1,Q) rows, A as a
(1,1) tile per head, B/C (1,Q,N) shared across heads, outputs y
(1,block_h,Q,P) and the final state (1,block_h,P,N) written on the last
chunk.  The kernel needs dt as a column too, and the TPU cannot cheaply
transpose a vector; it reads the column off the diagonal of a (Q, Q)
select instead of taking a (S, 1) input, which the chip lays out in
(8, 128) tiles: 128 lanes for one word, a padding copy XLA writes and
the kernel reads on every launch.  Every block spans its array's two
trailing dimensions or is (8, 128)-aligned, so any chunk that is a
multiple of 8 lowers on the chip.  The within-chunk cumulative sums are
masked reductions over the (Q, Q) causal triangle, and the matmuls run
at full f32 precision.
Q and N default to 128 (lane-width aligned); P is the head dim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan"]

_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(x_ref, dtr_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_scr, *,
            n_chunks: int, block_h: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    Bm = b_ref[...].astype(jnp.float32)        # (Q, N)
    Cm = c_ref[...].astype(jnp.float32)        # (Q, N)
    Q = Bm.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = row >= col
    # the block's heads share B and C, so the (Q, Q) scores once
    s = _dot(Cm, Bm, ((1,), (1,)))                                  # (Q, Q)

    for j in range(block_h):
        x = x_ref[j].astype(jnp.float32)       # (Q, P)
        dt_row = dtr_ref[j].astype(jnp.float32)  # (1, Q)
        # the column form of dt, read off the diagonal (a sum of one
        # term and zeros, so exact)
        dt_col = jnp.sum(jnp.where(row == col, dt_row, 0.0), axis=1,
                         keepdims=True)        # (Q, 1)
        A = a_ref[j]                           # (1, 1)

        a_col, a_row = dt_col * A, dt_row * A  # log-decay steps
        # within-chunk cumulative sums, as a column and as a row
        cum_col = jnp.sum(jnp.where(causal, a_row, 0.0), axis=1,
                          keepdims=True)
        cum_row = jnp.sum(jnp.where(row <= col, a_col, 0.0), axis=0,
                          keepdims=True)

        # intra-chunk dual form: (C_i . B_j) * L_ij * dt_j
        # mask before exp (masked diffs are positive and would overflow)
        L = jnp.exp(jnp.where(causal, cum_col - cum_row, -1e30))
        y = _dot(s * L * dt_row, x, ((1,), (0,)))                   # (Q, P)

        # inter-chunk: y += C_i . (exp(cum_i) * h_in)
        h = h_scr[j]                                                # (P, N)
        y = y + _dot(Cm, h, ((1,), (1,))) * jnp.exp(cum_col)
        y_ref[j] = y.astype(y_ref.dtype)

        # h' = exp(sum a) * h + sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T
        total = jnp.sum(a_row, axis=1, keepdims=True)               # (1, 1)
        rem = jnp.exp(total - cum_col) * dt_col                     # (Q, 1)
        h_scr[j] = jnp.exp(total) * h + _dot(x * rem, Bm, ((0,), (0,)))

    @pl.when(c_idx == n_chunks - 1)
    def _finish():
        hout_ref[...] = h_scr[...]


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, block_h: int = 1,
             vmem_limit_bytes: int | None = None, interpret: bool = False):
    """x: (Bz,S,H,P); dt: (Bz,S,H); A: (H,); B, C: (Bz,S,N).

    Returns (y (Bz,S,H,P), h_final (Bz,H,P,N)).  S % chunk == 0 and
    H % block_h == 0; ``block_h`` heads share a grid step (and its B/C
    scores); ``vmem_limit_bytes`` declares the kernel's scoped VMEM
    (None keeps the compiler's default).
    """
    xt = x.transpose(0, 2, 1, 3)               # (Bz,H,S,P)
    dtt = dt.transpose(0, 2, 1)                # (Bz,H,S)
    Bz, H, S, P = xt.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0 and H % block_h == 0
    n_chunks = S // chunk

    dt_row = dtt.reshape(Bz, H, n_chunks, 1, chunk)
    a3 = A.astype(jnp.float32).reshape(H, 1, 1)

    sq = pl.squeezed
    kern = functools.partial(_kernel, n_chunks=n_chunks, block_h=block_h)
    y, h_fin = pl.pallas_call(
        kern,
        grid=(Bz, H // block_h, n_chunks),
        in_specs=[
            pl.BlockSpec((sq, block_h, chunk, P),
                         lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((sq, block_h, sq, 1, chunk),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((block_h, 1, 1), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((sq, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((sq, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((sq, block_h, chunk, P),
                         lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((sq, block_h, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bz, H, S, P), xt.dtype),
            jax.ShapeDtypeStruct((Bz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_h, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="ssd_scan",
    )(xt, dt_row, a3, B, C)
    return y.transpose(0, 2, 1, 3), h_fin

