"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel subpackage ships:
  kernel.py — ``pl.pallas_call`` body with explicit BlockSpec VMEM tiling
  ops.py    — jit'd public wrapper (padding, dtype plumbing, platform switch)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels run natively by default (``interpret=False``), so no caller on a TPU
runs one interpreted by accident. On the CPU they run only under
``interpret=True``, which evaluates the kernel body block-by-block — bitwise
semantics, no MXU. ``ops`` defaults to the jnp reference on CPU for speed and
to the kernel on TPU; tests pass ``interpret=True`` to validate the bodies.
"""


def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"
