"""Pallas TPU kernels for the perf-critical hot spots.

Each kernel ships as <name>/kernel.py (pl.pallas_call + BlockSpec),
<name>/ops.py (jitted wrapper with an XLA fallback) and <name>/ref.py
(pure-jnp oracle).  Validated with interpret=True on CPU, compiled for
a described v5e chip by tests/test_tpu_compile.py, and compiled on the
chip against the oracles by chip_smoke.py; the dry-run lowers the XLA
path (DESIGN.md Section 6).

The wami_* kernels additionally expose the COSMOS knob pair (``ports``
-> lane-bank grid columns, ``unrolls`` -> rows per grid step; shared
plumbing in ``wami_common.py``) plus ``vmem_bytes``/``grid_steps`` cost
models — they are the measurable substrate of the ``PallasOracle``
backend (DESIGN.md Section 2, docs/backends.md).
"""
