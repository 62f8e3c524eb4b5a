"""What every Pallas kernel here must respect to compile for the TPU.

- Index maps return int32 block indices.  Under ``jax_enable_x64`` a
  Python literal traces as int64, which Mosaic cannot return from an
  index map (``failed to legalize operation 'func.return'``).
- Vector operands are 32-bit or narrower: the TPU has no 64-bit vector
  unit, and XLA's f64 emulation does not reach inside a kernel's custom
  call.  Interpret mode (the CPU test path) takes f64.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

#: the vector unit's lane width: the minor dimension of every tile
LANES = 128


def block_at(ndim: int, axis: Optional[int] = 0):
    """Index map for an ``ndim``-D block: grid step ``i`` selects block
    ``i`` along ``axis`` and block 0 along every other axis
    (``axis=None``: the same block at every step)."""

    def index_map(i):
        zero = jnp.zeros((), jnp.int32)
        return tuple(i if d == axis else zero for d in range(ndim))

    return index_map


def reject_f64(kernel: str, *arrays) -> None:
    """Refuse 64-bit operands before Mosaic lowering, with the reason."""
    for a in arrays:
        if jnp.dtype(a.dtype).itemsize == 8:
            raise ValueError(
                f"{kernel}: {jnp.dtype(a.dtype).name} operands cannot be "
                f"compiled for the TPU (no 64-bit vector unit); use f32 "
                f"or bf16, or the jnp reference path")
