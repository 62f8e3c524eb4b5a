"""Jit'd dispatch wrappers for the Pallas kernels.

On TPU ``"auto"`` and ``"pallas"`` run the compiled kernel or raise
(an f64 operand raises before lowering); nothing there is interpreted
and nothing gives way to the reference.  Off TPU — the CPU-test seam
only — the kernels run under ``interpret=True`` (the kernel body
executed step by step for correctness).  ``mode`` resolution:

- ``"auto"``    — pallas on TPU, reference on CPU (fast tests/benches)
- ``"pallas"``  — force the kernel (interpret=True off-TPU): oracle tests
- ``"ref"``     — force the jnp reference
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref
from repro.kernels.fused_cg import fused_cg_update_pallas
from repro.kernels.stencil7 import stencil7_pallas
from repro.nvm import gf256 as _gf256


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(mode: str) -> str:
    if mode == "auto":
        return "pallas" if _on_tpu() else "ref"
    return mode


@functools.partial(jax.jit, static_argnames=("mode", "bz"))
def stencil7(u: jax.Array, mode: str = "auto", bz: int = 8) -> jax.Array:
    """7-point stencil SpMV; drop-in for :func:`repro.kernels.ref.stencil7_ref`."""
    m = _resolve(mode)
    if m == "ref":
        return _ref.stencil7_ref(u)
    return stencil7_pallas(u, bz=bz, interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("mode", "bm"))
def fused_cg_update(
    x: jax.Array,
    r: jax.Array,
    p: jax.Array,
    ap: jax.Array,
    alpha: jax.Array,
    inv_diag: jax.Array,
    mode: str = "auto",
    bm: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused PCG vector update; drop-in for the 4-op jnp sequence.
    ``bm=None`` lets the kernel pick the largest legal row tile."""
    m = _resolve(mode)
    if m == "ref":
        return _ref.fused_cg_update_ref(x, r, p, ap, alpha, inv_diag)
    return fused_cg_update_pallas(x, r, p, ap, alpha, inv_diag, bm=bm,
                                  interpret=not _on_tpu())


def rs_encode(data: Sequence[np.ndarray], nparity: int,
              mode: str = "auto") -> List[np.ndarray]:
    """GF(2^8) P/Q parity encode; drop-in for
    :func:`repro.nvm.gf256.rs_encode` and **the registered fused-encode
    toggle**: persistence backends route every parity encode through
    here (repro-lint rule RL204) so one seam decides between the numpy
    reference and the fused Pallas kernel — both bit-identical.

    ``mode="auto"`` keeps numpy off-TPU (the fast host path) and the
    Pallas kernel on TPU; ``"pallas"`` forces the kernel (interpreted
    off-TPU — the oracle-test and fused-persist path); ``"ref"`` forces
    numpy.
    """
    m = _resolve(mode)
    if m == "ref":
        return _gf256.rs_encode(data, nparity)
    from repro.kernels.gf256_encode import gf256_rs_encode_pallas

    return gf256_rs_encode_pallas(data, nparity, interpret=not _on_tpu())
