"""Plain reference of the fleet kernels (causal softmax attention and the
Mamba2 SSD scan), their seeded inputs, and their VMEM areas, for the
configurations whose ``reference`` is ``fleet_ref``.

Shapes come from each kernel's ``dims`` in the configuration: attention
over ``q_heads`` query heads and ``kv_heads`` KV heads of ``head_dim``
on ``tokens`` tokens, in the model layout (batch, tokens, heads, dim);
the scan over ``heads`` heads of ``P`` channels with an ``N``-wide state
and one group of B/C shared by the heads.  Every function is written in
numpy and takes its precision from ``numerics``; nothing here imports
the system under test.

  * attention: softmax(q k^T / sqrt(d) + causal mask) v, head by head;
  * scan: h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t, from
    h_0 = 0, token by token; outputs y and the final state h.
"""

from __future__ import annotations

import math

import ml_dtypes
import numpy as np

import numerics as nx


def make_inputs(config, seed: int):
    """``{kernel: (array, ...)}`` at the configuration's shapes, made on
    the device in one jitted call from ``seed``.  dt is log-uniform over
    the configuration's ``time_step_min``..``time_step_max``; A is
    -exp(U(0, ln ``a_init_max``))."""
    import jax
    import jax.numpy as jnp

    fa = config["kernels"]["flash_attention"]["dims"]
    sc = config["kernels"]["ssd_scan"]["dims"]
    S, Hq, K, d = fa["tokens"], fa["q_heads"], fa["kv_heads"], fa["head_dim"]
    T, H, P, N = sc["tokens"], sc["heads"], sc["P"], sc["N"]
    lo, hi = math.log(config["time_step_min"]), math.log(config["time_step_max"])
    a_max = math.log(config["a_init_max"])

    @jax.jit
    def draw(key):
        ks = jax.random.split(key, 8)
        return {
            "flash_attention": (
                jax.random.normal(ks[0], (1, S, Hq, d), jnp.float32),
                jax.random.normal(ks[1], (1, S, K, d), jnp.float32),
                jax.random.normal(ks[2], (1, S, K, d), jnp.float32)),
            "ssd_scan": (
                jax.random.normal(ks[3], (1, T, H, P), jnp.float32),
                jnp.exp(jax.random.uniform(ks[4], (1, T, H), jnp.float32,
                                           lo, hi)),
                -jnp.exp(jax.random.uniform(ks[5], (H,), jnp.float32,
                                            0.0, a_max)),
                jax.random.normal(ks[6], (1, T, N), jnp.float32) * 0.3,
                jax.random.normal(ks[7], (1, T, N), jnp.float32) * 0.3),
        }

    from seeds import prng_key
    return draw(prng_key(seed))


# ----------------------------------------------------------------------
# kernel references: numpy arrays in, tuple of numpy arrays out
# ----------------------------------------------------------------------
def _bf16(a):
    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float32)


def matmul(prec: str, a, b):
    """``a @ b`` as a matrix unit computes it at ``prec``, by the rule of
    ``numerics.einsum`` (float64; three bfloat16 passes accumulated in
    float32; bfloat16 operands, rounded to bfloat16), on BLAS."""
    if prec == "exact":
        return np.matmul(np.asarray(a, np.float64), np.asarray(b, np.float64))
    if prec == "bfloat16":
        return np.matmul(_bf16(a), _bf16(b)).astype(ml_dtypes.bfloat16)
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return (np.matmul(ah, bh) + np.matmul(ah, bl)
            + np.matmul(al, bh)).astype(np.float32)


def flash_attention(prec, q, k, v):
    q, k, v = nx.cast(prec, q, k, v)
    _, S, Hq, d = q.shape
    G = Hq // k.shape[2]
    scale = nx.cast(prec, 1.0 / math.sqrt(d))
    causal = np.tril(np.ones((S, S), dtype=bool))
    out = np.empty(q.shape, dtype=q.dtype)
    for h in range(Hq):
        s = matmul(prec, q[0, :, h] * scale, k[0, :, h // G].T)
        s = np.where(causal, s.astype(q.dtype), nx.cast(prec, -np.inf))
        p = np.exp(s - s.max(axis=1, keepdims=True))
        o = matmul(prec, p, v[0, :, h // G]).astype(q.dtype)
        out[0, :, h] = o / p.sum(axis=1, keepdims=True, dtype=q.dtype)
    return (out,)


def ssd_scan(prec, x, dt, A, B, C):
    x, dt, A, B, C = nx.cast(prec, x, dt, A, B, C)
    _, T, H, P = x.shape
    N = B.shape[-1]
    h = np.zeros((H, P, N), dtype=x.dtype)
    y = np.empty(x.shape, dtype=x.dtype)
    for t in range(T):
        decay = np.exp(dt[0, t] * A)[:, None, None]
        h = h * decay + (dt[0, t][:, None] * x[0, t])[:, :, None] \
            * B[0, t][None, None, :]
        y[0, t] = matmul(prec, h, C[0, t]).astype(x.dtype)
    return (y, h[None])


REFERENCES = {"flash_attention": flash_attention, "ssd_scan": ssd_scan}


def reference(name: str, prec: str, *inputs):
    """Kernel ``name``'s outputs at precision ``prec``."""
    return REFERENCES[name](prec, *inputs)


# ----------------------------------------------------------------------
# the knob -> tiling map, and the VMEM area it gives
# ----------------------------------------------------------------------
def vmem_step_bytes(config, name: str, ports: int, unrolls: int) -> int:
    """VMEM of one grid step at the tiling the configuration's map gives
    the point (float32 words): attention holds q, o and the accumulator
    (heads per step x Q block x d), k and v (KV heads per step x KV
    block x d) and the (m, l) softmax rows; the scan holds, per head of
    the step, x and y (chunk x P), the dt row and the (P, N) state with
    its output tile, and the B/C tiles (chunk x N) the heads share."""
    dims = config["kernels"][name]["dims"]
    t = config["tiling"][name][f"{ports}x{unrolls}"]
    hb = t["heads_per_step"]
    if name == "flash_attention":
        d, bq, bkv = dims["head_dim"], t["block_q"], t["block_kv"]
        kv_hb = max(1, hb * dims["kv_heads"] // dims["q_heads"])
        return 4 * (3 * hb * bq * d + 2 * kv_hb * bkv * d + 2 * hb * bq)
    P, N, c = dims["P"], dims["N"], t["chunk"]
    return 4 * (hb * (2 * c * P + c + 2 * P * N) + 2 * c * N)


def area_bytes(config, name: str, ports: int, unrolls: int) -> float:
    """VMEM area of a measured point: the double-buffered working set of
    every bank plus a fixed overhead per bank."""
    step = vmem_step_bytes(config, name, ports, unrolls)
    return float(2 * step * ports + config["bank_overhead_bytes"] * ports)
