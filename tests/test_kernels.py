"""Pallas kernels vs their jnp oracles: shape/dtype sweeps (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import mha, mha_ref
from repro.kernels.ssd_scan import ssd, ssd_oracle
from repro.kernels.wami_gradient import gradient, gradient_oracle

KEY = jax.random.PRNGKey(7)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------
@pytest.mark.parametrize("B,Sq,Skv,H,K,d", [
    (1, 128, 128, 4, 4, 64),       # MHA
    (2, 128, 128, 8, 2, 64),       # GQA 4:1
    (1, 256, 256, 4, 2, 32),       # small head dim
    (1, 128, 256, 4, 2, 64),       # Sq < Skv (chunked prefill)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref(B, Sq, Skv, H, K, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, d), dtype)
    k = jax.random.normal(ks[1], (B, Skv, K, d), dtype)
    v = jax.random.normal(ks[2], (B, Skv, K, d), dtype)
    off = Skv - Sq
    o1 = mha(q, k, v, q_offset=off, use_pallas=True, interpret=True,
             block_q=64, block_kv=64)
    o2 = mha_ref(q, k, v, q_offset=off)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert float(jnp.abs(o1.astype(jnp.float32)
                         - o2.astype(jnp.float32)).max()) < tol


@pytest.mark.parametrize("window,softcap", [(64, 0.0), (0, 30.0), (32, 20.0)])
def test_flash_window_softcap(window, softcap):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    o1 = mha(q, k, v, window=window, softcap=softcap, use_pallas=True,
             interpret=True, block_q=64, block_kv=64)
    o2 = mha_ref(q, k, v, window=window, softcap=softcap)
    assert float(jnp.abs(o1 - o2).max()) < 2e-5


def test_flash_block_size_invariance():
    """Output must not depend on the BlockSpec tiling."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    outs = [mha(q, k, v, use_pallas=True, interpret=True,
                block_q=bq, block_kv=bk)
            for bq, bk in ((64, 64), (128, 128), (64, 256), (256, 64))]
    for o in outs[1:]:
        assert float(jnp.abs(o - outs[0]).max()) < 1e-5


# ----------------------------------------------------------------------
# SSD scan
# ----------------------------------------------------------------------
@pytest.mark.parametrize("Bz,S,H,P,N,chunk", [
    (2, 128, 4, 32, 64, 32),
    (1, 256, 2, 64, 128, 128),
    (2, 64, 8, 16, 32, 64),       # chunk == S (single chunk)
])
def test_ssd_matches_sequential(Bz, S, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Bz, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bz, S, H)) * 0.5)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (Bz, S, N)) * 0.3
    C = jax.random.normal(ks[4], (Bz, S, N)) * 0.3
    y1, h1 = ssd(x, dt, A, B, C, chunk=chunk, use_pallas=True, interpret=True)
    y2, h2 = ssd_oracle(x, dt, A, B, C)
    assert float(jnp.abs(y1 - y2).max()) < 1e-4
    assert float(jnp.abs(h1 - h2).max()) < 1e-4


def test_ssd_chunk_invariance():
    ks = jax.random.split(KEY, 5)
    Bz, S, H, P, N = 1, 128, 2, 16, 32
    x = jax.random.normal(ks[0], (Bz, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bz, S, H)) * 0.5)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (Bz, S, N)) * 0.3
    C = jax.random.normal(ks[4], (Bz, S, N)) * 0.3
    outs = [ssd(x, dt, A, B, C, chunk=c, use_pallas=True, interpret=True)[0]
            for c in (16, 32, 64, 128)]
    for o in outs[1:]:
        assert float(jnp.abs(o - outs[0]).max()) < 1e-4


@pytest.mark.parametrize("block_h", [1, 2, 4])
def test_head_blocks_match_the_oracles(block_h):
    """Several heads per grid step: attention under GQA 2:1 (block_h
    below, at and above the group) and the scan, whose heads share B/C."""
    ks = jax.random.split(KEY, 8)
    q = jax.random.normal(ks[0], (1, 128, 4, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    o = mha(q, k, v, block_q=64, block_kv=32, block_h=block_h,
            use_pallas=True, interpret=True)
    assert float(jnp.abs(o - mha_ref(q, k, v)).max()) < 2e-5
    x = jax.random.normal(ks[3], (1, 64, 4, 16))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (1, 64, 4)) * 0.5)
    A = -jnp.exp(jax.random.normal(ks[5], (4,)) * 0.3)
    B = jax.random.normal(ks[6], (1, 64, 16)) * 0.3
    C = jax.random.normal(ks[7], (1, 64, 16)) * 0.3
    y1, h1 = ssd(x, dt, A, B, C, chunk=16, block_h=block_h,
                 use_pallas=True, interpret=True)
    y2, h2 = ssd_oracle(x, dt, A, B, C)
    assert float(jnp.abs(y1 - y2).max()) < 1e-4
    assert float(jnp.abs(h1 - h2).max()) < 1e-4


# ----------------------------------------------------------------------
# WAMI gradient (the COSMOS-knob kernel)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("ports", [1, 2, 4])
@pytest.mark.parametrize("unrolls", [4, 8, 16])
def test_wami_gradient_knob_sweep(ports, unrolls):
    img = jax.random.normal(KEY, (64, 128)) * 10
    gx1, gy1 = gradient(img, ports=ports, unrolls=unrolls, interpret=True)
    gx2, gy2 = gradient_oracle(img)
    assert float(jnp.abs(gx1 - gx2).max()) < 1e-6
    assert float(jnp.abs(gy1 - gy2).max()) < 1e-6


def test_wami_gradient_vmem_model():
    from repro.kernels.wami_gradient import grid_steps, vmem_bytes
    # more ports => smaller blocks, more (parallel) grid steps
    assert vmem_bytes(128, 128, ports=4, unrolls=8) \
        == vmem_bytes(128, 128, ports=1, unrolls=8) // 4
    assert grid_steps(128, 128, ports=4, unrolls=8) \
        == 4 * grid_steps(128, 128, ports=1, unrolls=8)
