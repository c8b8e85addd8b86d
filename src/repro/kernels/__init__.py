"""Pallas TPU kernels for the Cheetah pruning hot path (paper §4/§7).

Each kernel: <name>.py (pl.pallas_call + BlockSpec VMEM tiling),
validated against ref.py (pure-jnp oracle with identical block
semantics) in interpret mode on the CPU, compiled for a described v5e
by tests/test_tpu_compile.py, and run compiled on the chip by
chip_smoke.py. Public API in ops.py.
"""
from . import ops, parallel, ref
