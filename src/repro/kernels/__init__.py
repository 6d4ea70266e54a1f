"""Pallas TPU kernels for the paper's memory-movement hot spots."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in the interpreter: on the CPU
    backend they must, on TPU they never do. Any other platform has no
    lowering for these kernels and is refused."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"no Pallas lowering for platform {platform!r}; "
                       "these kernels run on TPU (or interpreted on CPU)")
