"""Pallas TPU kernel: 7-point Poisson stencil SpMV (the PCG hot spot).

TPU-native design (DESIGN.md §2): the 3-D grid is tiled into **z-slabs**
held in VMEM.  Each program instance owns one slab of shape
``(bz, ny, nx)`` plus the two neighbouring z-planes (the halo), brought in
as separate 1-plane blocks so the slab itself is fetched exactly once
from HBM.  In-slab neighbour access is pure VREG shuffling; the stencil is
a VPU (8x128 vector unit) workload — arithmetic intensity ~1 flop/byte,
so the kernel's job is to reach the HBM bandwidth roofline by avoiding
any re-fetch of ``u``.

The y/x neighbours are ``pltpu.roll`` rotations along the sublane and
lane axes with the wrapped row/column masked to zero (homogeneous
Dirichlet) by an iota compare; Mosaic cannot lower 1-wide concatenates
along those axes.  The z neighbours are plane-granular concatenates on
the untiled leading axis.

Alignment: ``nx`` must be a multiple of 128 (lanes) and ``ny`` a
multiple of 8 (sublanes) for the TPU compile; interpret mode takes any
size.

The z-halo planes use *clamped* index maps (block index ``i*bz - 1`` /
``(i+1)*bz`` clamped into range); the kernel masks the contribution at
the physical domain boundary (homogeneous Dirichlet), so the clamp's
duplicated plane is never read into the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import block_at, reject_f64


def _shift(u, axis: int, forward: bool):
    """``u`` shifted by one along ``axis`` with zero fill: ``forward``
    gives ``out[j] = u[j-1]`` (the minus neighbour), otherwise
    ``out[j] = u[j+1]``."""
    n = u.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, u.shape, axis)
    # int32 shift: Mosaic's rotate takes no 64-bit operand (x64 mode)
    shift, edge = (jnp.int32(1), 0) if forward else (jnp.int32(n - 1), n - 1)
    return jnp.where(idx == edge, jnp.zeros((), u.dtype),
                     pltpu.roll(u, shift, axis))


def _stencil7_kernel(prev_ref, cur_ref, nxt_ref, out_ref, *, bz: int, nblocks: int):
    i = pl.program_id(0)
    u = cur_ref[...]  # (bz, ny, nx) slab in VMEM

    # z-neighbours: shift within the slab; edge rows take the halo planes.
    prev_plane = prev_ref[...]  # (1, ny, nx): plane i*bz - 1 (clamped)
    nxt_plane = nxt_ref[...]    # (1, ny, nx): plane (i+1)*bz (clamped)
    prev_plane = jnp.where(i == 0, jnp.zeros_like(prev_plane), prev_plane)
    nxt_plane = jnp.where(i == nblocks - 1, jnp.zeros_like(nxt_plane), nxt_plane)
    if bz == 1:
        z_minus, z_plus = prev_plane, nxt_plane
    else:
        z_minus = jnp.concatenate([prev_plane, u[:-1]], axis=0)
        z_plus = jnp.concatenate([u[1:], nxt_plane], axis=0)

    out_ref[...] = (6.0 * u - z_minus - z_plus
                    - _shift(u, 1, True) - _shift(u, 1, False)
                    - _shift(u, 2, True) - _shift(u, 2, False))


def stencil7_pallas(u: jax.Array, bz: int = 8, interpret: bool = False) -> jax.Array:
    """``A @ u`` for the 7-point stencil via a z-slab Pallas kernel."""
    if not interpret:
        reject_f64("stencil7_pallas", u)
    nz, ny, nx = u.shape
    if nz % bz != 0:
        raise ValueError(f"nz={nz} not divisible by z-block {bz}")
    nblocks = nz // bz

    def prev_map(i):
        # plane index i*bz - 1, clamped to >= 0 (masked at i == 0)
        return block_at(3)(jnp.maximum(i * bz - 1, 0))

    def next_map(i):
        # plane index (i+1)*bz, clamped to <= nz-1 (masked at last block)
        return block_at(3)(jnp.minimum((i + 1) * bz, nz - 1))

    kernel = functools.partial(_stencil7_kernel, bz=bz, nblocks=nblocks)
    return pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, ny, nx), prev_map),
            pl.BlockSpec((bz, ny, nx), block_at(3)),
            pl.BlockSpec((1, ny, nx), next_map),
        ],
        out_specs=pl.BlockSpec((bz, ny, nx), block_at(3)),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        name="stencil7",
        interpret=interpret,
    )(u, u, u)
