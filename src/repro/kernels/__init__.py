"""Pallas TPU kernels for the compute hot spots.

Each kernel ships as <name>/kernel.py (pl.pallas_call + BlockSpec VMEM
tiling), <name>/ops.py (jit'd wrapper) and <name>/ref.py (pure-jnp oracle);
tests/test_kernels.py sweeps shapes/dtypes in interpret mode.

- spmv: the semiring push as destination-tiled one-hot MXU matmuls
  (plus_times) and a masked segmented reduce (min/max semirings)
"""
