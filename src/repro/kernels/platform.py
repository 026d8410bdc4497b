"""Where the Pallas kernels run: compiled on a TPU, interpreted on the CPU.

Every kernel wrapper that is not handed ``interpret`` asks
``interpret_default`` once per trace.  Interpret mode exists for the CPU
test suite and the CPU examples only; on the chip every kernel compiles,
and a backend that is neither is refused instead of silently interpreted.
"""
from __future__ import annotations

import jax


def interpret_default() -> bool:
    """True on the CPU backend, False on a TPU; raises on anything else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas TPU kernels have no {backend!r} lowering")
