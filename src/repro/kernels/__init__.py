"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package has:
  kernel.py -- pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    -- jit'd public wrapper (shape checks, dtype policy, interpret flag)
  ref.py    -- pure-jnp oracle used by the allclose test sweeps

The kernels compile for TPU only; ``platform.py`` decides where they run.
On the CPU the model takes the jnp reference paths, and tests check each
kernel against its oracle in Pallas's interpreter (``interpret`` passed
explicitly); ``tests/test_tpu_compile.py`` compiles the served ones for a
described v5e.
"""
