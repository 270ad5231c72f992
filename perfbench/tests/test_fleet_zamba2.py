"""The fleet-zamba2 configuration's plain reference, the work counts of
its kernels, and the two roofline readers, on small sizes made by hand."""

import copy
import json
import math
import os
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import kernel_work
import peaks
from repro.core.obs import Tracer

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(BENCH, "configs", "fleet-zamba2-7b-tp4-4k.json")
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def _ref():
    return harness._module(os.path.join(BENCH, "configs", "fleet_ref.py"))


def _small_config():
    with open(CONFIG) as f:
        cfg = json.load(f)
    small = copy.deepcopy(cfg)
    small["kernels"]["flash_attention"]["dims"].update(
        tokens=64, q_heads=4, kv_heads=2, head_dim=24)
    small["kernels"]["ssd_scan"]["dims"].update(tokens=48, heads=3, P=8, N=5)
    return cfg, small


# ----------------------------------------------------------------------
# the reference against float64 numpy written out another way
# ----------------------------------------------------------------------
def _attention_f64(q, k, v):
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    _, S, H, d = q.shape
    G = H // k.shape[2]
    out = np.zeros_like(q)
    for h in range(H):
        for i in range(S):
            s = np.array([q[0, i, h] @ k[0, j, h // G] for j in range(i + 1)])
            w = np.exp(s / math.sqrt(d) - np.max(s / math.sqrt(d)))
            out[0, i, h] = (w / w.sum()) @ v[0, :i + 1, h // G]
    return out


def _scan_f64(x, dt, A, B, C):
    """The dual form: y_t = sum_{s<=t} exp(sum_{s<r<=t} dt_r A) dt_s
    (C_t . B_s) x_s, and h_T the same sum's state."""
    x, dt, A, B, C = (np.asarray(a, np.float64) for a in (x, dt, A, B, C))
    _, T, H, P = x.shape
    y = np.zeros_like(x)
    h = np.zeros((1, H, P, B.shape[-1]))
    for hd in range(H):
        cum = np.cumsum(dt[0, :, hd] * A[hd])
        for t in range(T):
            w = np.exp(cum[t] - cum[:t + 1]) * dt[0, :t + 1, hd]
            y[0, t, hd] = (w * (B[0, :t + 1] @ C[0, t])) @ x[0, :t + 1, hd]
        w = np.exp(cum[-1] - cum) * dt[0, :, hd]
        h[0, hd] = (x[0, :, hd] * w[:, None]).T @ B[0]
    return y, h


def test_make_inputs_follows_the_configuration():
    _, small = _small_config()
    ref = _ref()
    a = ref.make_inputs(small, 3000000019)
    b = ref.make_inputs(small, 3000000019)
    q, k, v = a["flash_attention"]
    x, dt, A, B, C = a["ssd_scan"]
    assert q.shape == (1, 64, 4, 24) and k.shape == v.shape == (1, 64, 2, 24)
    assert x.shape == (1, 48, 3, 8) and dt.shape == (1, 48, 3)
    assert A.shape == (3,) and B.shape == C.shape == (1, 48, 5)
    assert all(t.dtype == np.float32 for t in (q, k, v, x, dt, A, B, C))
    dt = np.asarray(dt)
    assert small["time_step_min"] <= dt.min() and dt.max() <= small[
        "time_step_max"] * (1 + 1e-6)
    assert np.all(-16.0 * (1 + 1e-6) <= np.asarray(A)) and np.all(
        np.asarray(A) <= -1.0 + 1e-6)
    for n in a:
        for u, w in zip(a[n], b[n]):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(w))


def test_exact_reference_equals_float64_written_another_way():
    _, small = _small_config()
    ref = _ref()
    inputs = {n: tuple(np.asarray(t) for t in v)
              for n, v in ref.make_inputs(small, 11).items()}
    o, = ref.reference("flash_attention", "exact", *inputs["flash_attention"])
    np.testing.assert_allclose(o, _attention_f64(*inputs["flash_attention"]),
                               rtol=0, atol=1e-12)
    y, h = ref.reference("ssd_scan", "exact", *inputs["ssd_scan"])
    y2, h2 = _scan_f64(*inputs["ssd_scan"])
    np.testing.assert_allclose(y, y2, rtol=0, atol=1e-12)
    np.testing.assert_allclose(h, h2, rtol=0, atol=1e-12)
    # one step below: a visible error, far above float64 rounding
    import numerics as nx
    for name in ("flash_attention", "ssd_scan"):
        exact = ref.reference(name, "exact", *inputs[name])
        for prec in ("high", "bfloat16"):
            got = ref.reference(name, prec, *inputs[name])
            err = max(nx.rel_err(g, e) for g, e in zip(got, exact))
            assert 1e-9 < err < 0.2, (name, prec, err)


def test_area_bytes_restates_the_map_in_the_file():
    cfg, _ = _small_config()
    ref = _ref()
    for name, table in cfg["tiling"].items():
        for key, t in table.items():
            p, u = map(int, key.split("x"))
            assert ref.vmem_step_bytes(cfg, name, p, u) == t["vmem_step_bytes"]
            assert ref.area_bytes(cfg, name, p, u) == float(
                2 * t["vmem_step_bytes"] * p + cfg["bank_overhead_bytes"] * p)


# ----------------------------------------------------------------------
# kernel work, by hand
# ----------------------------------------------------------------------
def test_kernel_work_counts_by_hand():
    # 2 query heads on 1 KV head of 3, 4 tokens: 10 causal pairs a head
    f, b = kernel_work.flash_attention(
        {"tokens": 4, "q_heads": 2, "kv_heads": 1, "head_dim": 3})
    assert f == 4 * 2 * 3 * 10
    assert b == 4 * (2 * 4 * 3 + 2 * 4 * 3 + 4 * 3 + 4 * 3)
    # 5 tokens of 2 heads, P 3, N 2, one B/C group
    f, b = kernel_work.ssd_scan({"tokens": 5, "heads": 2, "P": 3, "N": 2})
    assert f == 4 * 3 * 2 * 5 * 2
    words = (5 * 2 * 3 + 5 * 2 + 2 + 5 * 2 + 5 * 2) + (5 * 2 * 3 + 2 * 3 * 2)
    assert b == 4 * words
    # the cell itself: the attention bound by operations, the scan by bytes
    cfg, _ = _small_config()
    peak = peaks.peak("TPU v5 lite")
    fa = kernel_work.roofline_s(cfg, "flash_attention", peak)
    sc = kernel_work.roofline_s(cfg, "ssd_scan", peak)
    assert fa == pytest.approx(4 * 8 * 224 * 4096 * 4097 / 2 / 197e12)
    assert sc == pytest.approx(kernel_work.ssd_scan(
        cfg["kernels"]["ssd_scan"]["dims"])[1] / 819e9)


def test_op_names_map_to_their_kernel():
    assert kernel_work.op_kernel("%ssd_scan.3") == "ssd_scan"
    assert kernel_work.op_kernel("flash_attention") == "flash_attention"
    assert kernel_work.op_kernel("_flash_attention.12") == "flash_attention"
    assert kernel_work.op_kernel("%copy.1") == "copy"


# ----------------------------------------------------------------------
# the roofline readers
# ----------------------------------------------------------------------
def _span(tracer, name, start, end, **attrs):
    tracer.span(name, start=start, **attrs).finish(end=end)


def _run(device_ops, tracer, traced=True):
    cfg, _ = _small_config()
    run = harness.Run(cell=SimpleNamespace(config=cfg), seconds=10.0)
    run.t_start, run.t_stop = 100.0, 110.0
    run.tracer = tracer
    run.trace = ({"device_ops": device_ops, "busy_s": 1.0, "window_s": 10.0}
                 if traced else None)
    return run


def _launches(tracer, component, at, launches=3):
    _span(tracer, "pallas.warmup", at, at + 0.1, component=component)
    _span(tracer, "pallas.reps", at + 0.1, at + 0.4, component=component,
          launches=launches)


@pytest.mark.parametrize("kernel", ["flash_attention", "ssd_scan"])
def test_roofline_reader_counts_the_windows_launches(kernel, monkeypatch):
    monkeypatch.setattr(peaks, "peak", lambda kind: PEAK)
    other = "ssd_scan" if kernel == "flash_attention" else "flash_attention"
    tr = Tracer()
    _launches(tr, kernel, 101.0)              # 1 + 3 launches
    _launches(tr, kernel, 105.0, launches=2)  # 1 + 2
    _launches(tr, kernel, 111.0)              # after the window
    _launches(tr, other, 102.0)
    ops = [[f"%{kernel}.1", 0.5], [f"%{kernel}.7", 0.25], ["%copy.1", 9.0]]
    run = _run(ops, tr)
    cfg = run.cell.config
    want = 100.0 * 7 * kernel_work.roofline_s(cfg, kernel, PEAK) / 0.75
    got = harness.read_metric(BENCH, f"{kernel}_roofline", run)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("kernel", ["flash_attention", "ssd_scan"])
def test_roofline_reader_finds_nothing_to_read(kernel, monkeypatch):
    monkeypatch.setattr(peaks, "peak", lambda kind: PEAK)
    name = f"{kernel}_roofline"
    tr = Tracer()
    _launches(tr, kernel, 101.0)
    ops = [[f"%{kernel}.1", 0.5]]
    # untraced
    assert harness.read_metric(BENCH, name, _run(ops, tr, traced=False)) \
        is None
    # the kernel's op is not in the trace (another program ran)
    assert harness.read_metric(BENCH, name, _run([["%copy", 1.0]], tr)) \
        is None
    # reps spans that do not say how many launches they made
    bare = Tracer()
    _span(bare, "pallas.warmup", 101.0, 101.1, component=kernel)
    _span(bare, "pallas.reps", 101.1, 101.4, component=kernel)
    assert harness.read_metric(BENCH, name, _run(ops, bare)) is None
