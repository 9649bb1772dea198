"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships three artifacts:
  * ``<name>.py`` — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling
  * ``ops.py``    — jit'd public wrappers with shape plumbing + impl select
  * ``ref.py``    — pure-jnp oracles used by the allclose test sweeps

Every kernel's ``interpret=None`` default resolves by backend
(``ops.is_cpu_backend``): interpret mode on the CPU (Pallas does not lower
to the XLA CPU backend), the compiled Mosaic kernel on a TPU.
"""
