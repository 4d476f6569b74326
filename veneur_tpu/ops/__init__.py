"""Pallas TPU kernels for the hot flush-path reductions.

The XLA-compiled sketch kernels (veneur_tpu/sketches/) hit the north-star
latency targets on their own; the kernels here are hand-tiled Pallas
variants for the pieces where explicit VMEM residency buys further
headroom at scale.  Each module exposes a drop-in replacement for its XLA
twin and is validated against it in tests (interpret mode on CPU, native
on TPU).
"""
