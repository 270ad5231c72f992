"""The work of one launch of each fleet kernel, independent of its
tiling, and its share of the chip's roofline in a traced run.

A kernel's roofline time per launch is the larger of its operations at
the chip's bf16 peak and its bytes at the chip's HBM bandwidth
(``peaks.json``).  Its share is that time, times the launches the
program's ``pallas.warmup`` and ``pallas.reps`` spans made in the
window (``launches`` on each ``pallas.reps`` span), over the seconds the
kernel's device op ran in the window.
"""

from __future__ import annotations

import re
from typing import Optional, Tuple

WORD = 4        # float32


def flash_attention(dims) -> Tuple[float, float]:
    """(operations, bytes) of causal attention: 4 H d per causal
    (query, key) pair, S (S + 1) / 2 pairs per head; q, k, v read once
    and o written once."""
    S, H, K, d = dims["tokens"], dims["q_heads"], dims["kv_heads"], \
        dims["head_dim"]
    flops = 4.0 * H * d * S * (S + 1) / 2
    return flops, float(WORD * S * d * (2 * H + 2 * K))


def ssd_scan(dims) -> Tuple[float, float]:
    """(operations, bytes) of the SSD recurrence: 4 P N per token and
    head (the state's decay-and-update and its read-out); x, dt, A, B, C
    read once, y and the final state written once."""
    S, H, P, N = dims["tokens"], dims["heads"], dims["P"], dims["N"]
    G = dims.get("bc_groups", 1)
    flops = 4.0 * P * N * S * H
    words = 2 * S * H * P + S * H + H + 2 * S * N * G + H * P * N
    return flops, float(WORD * words)


WORK = {"flash_attention": flash_attention, "ssd_scan": ssd_scan}


def roofline_s(config, kernel: str, peak) -> float:
    """Seconds one launch of ``kernel`` takes at the chip's roofline."""
    flops, nbytes = WORK[kernel](config["kernels"][kernel]["dims"])
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def op_kernel(op: str) -> str:
    """The kernel a device op belongs to: ``%ssd_scan.3`` -> ``ssd_scan``."""
    return re.sub(r"\.\d+$", "", op.lstrip("%_"))


def launches_in_window(run, kernel: str) -> Optional[int]:
    """Launches of ``kernel`` inside the window: one per ``pallas.warmup``
    span, ``launches`` per ``pallas.reps`` span; None when a reps span
    does not say how many it made."""
    n = 0
    for name in ("pallas.warmup", "pallas.reps"):
        for s in run.tracer.spans(name):
            if s.attrs.get("component") != kernel or not (
                    run.t_start <= s.start and s.end <= run.t_stop):
                continue
            if name == "pallas.warmup":
                n += 1
            elif "launches" in s.attrs:
                n += int(s.attrs["launches"])
            else:
                return None
    return n


def roofline_share(run, kernel: str, peak=None) -> Optional[float]:
    """Share (%) of the chip's roofline ``kernel`` ran at in the window;
    None untraced, or where the kernel's op or launches are absent."""
    if run.trace is None or run.tracer is None:
        return None
    device_s = sum(s for op, s in run.trace["device_ops"]
                   if op_kernel(op) == kernel)
    if device_s <= 0:
        return None
    launches = launches_in_window(run, kernel)
    if not launches:
        return None
    if peak is None:
        import jax
        import peaks
        peak = peaks.peak(jax.devices()[0].device_kind)
    return 100.0 * launches * roofline_s(run.cell.config, kernel, peak) \
        / device_s
