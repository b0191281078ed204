"""Pallas TPU kernels for the serving hot spots.

Each kernel: <name>.py (pl.pallas_call + explicit VMEM BlockSpecs),
ops.py (jit'd wrappers), ref.py (pure-jnp oracles). Compiled for
Mosaic on a TPU and run in interpret mode elsewhere
(``decode_attention.default_interpret``).
"""
