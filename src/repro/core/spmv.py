"""Sharded PCG iteration for the production mesh (dry-run / roofline path).

The solver state lives as 3-D grids ``(nz, ny, nx)`` with the z axis
sharded across **all** mesh axes (the paper's row-block distribution: each
device owns a z-slab = one "process" block).  Under ``jit`` the 7-point
stencil's z-neighbour access lowers to a nearest-neighbour halo exchange
(``collective-permute``) and the dot products to ``all-reduce`` — exactly
the communication structure of distributed PCG over MPI.

ESR variants (what the roofline measures):

- ``esr_mode="none"`` / ``"nvm"`` — plain iteration.  NVM-ESR persistence
  happens **off the device graph** (host pull of the local shard; zero
  collectives, zero device RAM), so the compiled HLO is identical to the
  unprotected solver: the paper's headline claim, visible structurally.
- ``esr_mode="inmemory"`` — the iteration additionally materializes the
  peer-RAM redundancy: ``p`` is all-gathered and kept replicated for two
  successive iterations (``O(2n)`` extra bytes *per device*, an
  ``all-gather`` of n values per iteration in the collective schedule).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.poisson import stencil7


def _grid_sharding(mesh: Mesh, shard_axes) -> NamedSharding:
    return NamedSharding(mesh, P(shard_axes, None, None))


# ----------------------------------------------------------------------
# Deterministic block-hierarchical reductions (sharded exactness).
#
# A global ``jnp.vdot`` lets XLA pick a reduction order per compiled
# program, so the same mathematical dot produces different low-order
# bits unsharded vs sharded (and even between two sharded layouts).
# The zoo's bit-exactness contract — a sharded solve reproduces the
# unsharded trajectory exactly — therefore pins the order explicitly:
#
# 1. per-block partial sums, one block at a time: a loop over the
#    device's blocks whose body reduces one block of fixed length.  The
#    body is the same program whatever the number of blocks per device,
#    so the partials are bitwise identical under any 1-D block sharding.
#    A single ``reshape(nblocks, -1).sum(axis=1)`` is NOT enough: the
#    TPU tiles that reduce by its row count (8 rows unsharded, 2 per
#    device on four chips), and the tiling sets the summation order;
# 2. sharded, the loop runs per device under ``shard_map`` and an
#    explicit replication constraint gathers the partials (the only
#    collective — an all-gather of ``nblocks`` scalars);
# 3. an UNROLLED left-to-right add chain combines them.  ``jnp.sum``
#    over the partials is NOT enough: XLA fuses it context-dependently
#    and reassociates across shardings, which is exactly the
#    nondeterminism being excluded.
# ----------------------------------------------------------------------
def _blockwise(nblocks: int, mesh: Optional[Mesh], block_fn, in_specs,
               out_spec):
    """``f(*vectors) -> (nblocks, ...)``: ``block_fn`` applied to each
    block's slice (the last axis of every operand, in ``nblocks`` equal
    blocks), one block per loop trip; per device under ``shard_map``
    when ``mesh`` is given, then replicated."""

    def local(nloc, *vs):
        m = vs[-1].shape[-1] // nloc

        def one(i):
            return block_fn(*(jax.lax.dynamic_slice_in_dim(
                v, i * m, m, axis=v.ndim - 1) for v in vs))

        return jax.lax.map(one, jnp.arange(nloc))

    if mesh is None:
        return partial(local, nblocks)
    nloc = nblocks // int(mesh.shape["data"])
    sharded = compat.shard_map(partial(local, nloc), mesh, in_specs,
                               out_spec)
    rep = NamedSharding(mesh, P())
    return lambda *vs: jax.lax.with_sharding_constraint(sharded(*vs), rep)


def _left_to_right(parts: jax.Array) -> jax.Array:
    """``parts[0] + parts[1] + ...`` over the leading axis, unrolled."""
    acc = parts[0]
    for i in range(1, parts.shape[0]):
        acc = acc + parts[i]
    return acc


def make_det_dot(nblocks: int, mesh: Optional[Mesh] = None):
    """Build ``dot(a, b)``: a block-hierarchical, order-pinned inner
    product that is bitwise identical across device shardings (and
    equal to the unsharded result).  ``mesh`` is the 1-D ``data`` mesh
    of a sharded operator (None for single-device runs)."""
    partials = _blockwise(
        # repro-lint: noqa[RL201] -- the order-pinned per-block partial itself: one fixed-length block per loop trip
        nblocks, mesh, lambda a, b: jnp.sum(a * b),
        (P("data"), P("data")), P("data"))

    return lambda a, b: _left_to_right(partials(a, b))


def make_det_rowdots(nblocks: int, mesh: Optional[Mesh] = None):
    """Row-batched :func:`make_det_dot`: ``rowdots(M, w)[i] == det_dot(M[i],
    w)`` for an ``(rows, n)`` matrix — the Arnoldi projection shape.  The
    per-row partials use the same block-hierarchical order, so the result
    is bitwise sharding-independent like the scalar form."""
    partials = _blockwise(
        nblocks, mesh, lambda m_rows, w: (m_rows * w[None, :]).sum(axis=1),
        (P(None, "data"), P("data")), P("data", None))

    return lambda m_rows, w: _left_to_right(partials(m_rows, w))


def make_sharded_pcg_step(
    mesh: Mesh,
    shard_axes=("pod", "data", "model"),
    esr_mode: str = "nvm",
    dtype=jnp.float32,
) -> Tuple[Callable, Callable]:
    """Build (step_fn, spec_fn) for one sharded PCG iteration.

    ``step_fn(state) -> state`` where state is a dict of grids + scalars.
    ``spec_fn(nz, ny, nx) -> (in_shardings, input ShapeDtypeStructs)``.
    """
    axes = tuple(a for a in shard_axes if a in mesh.axis_names)
    gshard = _grid_sharding(mesh, axes)
    rep = NamedSharding(mesh, P())

    def step(state: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        x, r, z, p, rz = state["x"], state["r"], state["z"], state["p"], state["rz"]
        ap = stencil7(p)                                   # halo exchange on z
        # repro-lint: noqa[RL201] -- roofline dry-run path modeling the paper's MPI all-reduce; outside the zoo exactness contract
        pap = jnp.sum(p * ap)                              # all-reduce
        alpha = rz / pap
        x = x + alpha * p
        r = r - alpha * ap
        zn = r * (1.0 / 6.0)                               # Jacobi M^{-1}
        # repro-lint: noqa[RL201] -- roofline dry-run path modeling the paper's MPI all-reduce; outside the zoo exactness contract
        rz_new = jnp.sum(r * zn)                           # all-reduce
        beta = rz_new / rz
        pn = zn + beta * p
        out = dict(x=x, r=r, z=zn, p=pn, rz=rz_new, beta=beta)
        if esr_mode == "inmemory":
            # Algorithm 2 (ASpMV surplus): replicate p into peer RAM for two
            # successive iterations -> all-gather + 2n replicated residency.
            red_cur = jax.lax.with_sharding_constraint(pn, rep)
            out["esr_red_prev"] = state["esr_red_cur"]
            out["esr_red_cur"] = red_cur
        return out

    def spec(nz: int, ny: int, nx: int):
        grid = jax.ShapeDtypeStruct((nz, ny, nx), dtype)
        scalar = jax.ShapeDtypeStruct((), dtype)
        shardings = dict(x=gshard, r=gshard, z=gshard, p=gshard, rz=rep)
        structs = dict(x=grid, r=grid, z=grid, p=grid, rz=scalar)
        if esr_mode == "inmemory":
            shardings["esr_red_cur"] = rep
            structs["esr_red_cur"] = grid
        return shardings, structs

    return step, spec


def nvm_persist_host(state: Dict[str, jax.Array]) -> np.ndarray:
    """NVM-ESR persistence tap: pull the local ``p`` shard to the host.

    In a real pod each host pulls only its addressable shards
    (``jax.Array.addressable_shards``) and hands the bytes to the NVM
    backend (local pool or PRD window).  No collective, no device memory.
    """
    shards = state["p"].addressable_shards
    return np.concatenate([np.asarray(s.data).reshape(-1) for s in shards])


def make_shardmap_pcg_step(
    mesh: Mesh,
    shard_axes=("pod", "data", "model"),
    esr_mode: str = "nvm",
    dtype=jnp.float32,
):
    """Optimized distributed PCG iteration (§Perf hillclimb A1/A2).

    The auto-GSPMD stencil (pad+slice) makes XLA exchange 3-5 z-plane
    slabs per neighbour (~265 MiB/chip on the 1024^3 grid).  This version
    uses ``shard_map`` with explicit single-plane ``ppermute`` halos — the
    information-theoretic minimum (2 planes/chip) — and the fused-update
    algebra of ``kernels/fused_cg.py`` (on TPU the local stencil and the
    fused update ARE the Pallas kernels; the jnp bodies here are their
    ref semantics, which XLA fuses on CPU).

    Boundary devices receive ppermute's zero-fill — exactly homogeneous
    Dirichlet.
    """
    axes = tuple(a for a in shard_axes if a in mesh.axis_names)
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    up_perm = [(i, i + 1) for i in range(nshards - 1)]    # send last plane up
    down_perm = [(i + 1, i) for i in range(nshards - 1)]  # send first plane down

    def stencil_local(u, lo, hi):
        zm = jnp.concatenate([lo, u[:-1]], axis=0)
        zp = jnp.concatenate([u[1:], hi], axis=0)
        zero_y = jnp.zeros_like(u[:, :1, :])
        ym = jnp.concatenate([zero_y, u[:, :-1, :]], axis=1)
        yp = jnp.concatenate([u[:, 1:, :], zero_y], axis=1)
        zero_x = jnp.zeros_like(u[:, :, :1])
        xm = jnp.concatenate([zero_x, u[:, :, :-1]], axis=2)
        xp = jnp.concatenate([u[:, :, 1:], zero_x], axis=2)
        return 6.0 * u - zm - zp - ym - yp - xm - xp

    def step_local(state):
        x, r, z, p, rz = state["x"], state["r"], state["z"], state["p"], state["rz"]
        lo = jax.lax.ppermute(p[-1:], axes, up_perm)    # plane from below
        hi = jax.lax.ppermute(p[:1], axes, down_perm)   # plane from above
        ap = stencil_local(p, lo, hi)
        # repro-lint: noqa[RL201] -- shard_map roofline kernel: psum-of-partials is the modeled MPI collective itself
        pap = jax.lax.psum(jnp.sum(p * ap, dtype=jnp.float32), axes)
        alpha = (rz / pap).astype(p.dtype)
        # fused update (Pallas fused_cg on TPU): one pass, fp32 partials
        xn = x + alpha * p
        rn = r - alpha * ap
        zn = rn * (1.0 / 6.0)
        # repro-lint: noqa[RL201] -- shard_map roofline kernel: psum-of-partials is the modeled MPI collective itself
        rz_new = jax.lax.psum(jnp.sum(rn.astype(jnp.float32) * zn.astype(jnp.float32)), axes)
        beta = (rz_new / rz).astype(p.dtype)
        pn = zn + beta * p
        out = dict(x=xn, r=rn, z=zn, p=pn, rz=rz_new, beta=beta)
        if esr_mode == "inmemory":
            out["esr_red_prev"] = state["esr_red_cur"]
            out["esr_red_cur"] = jax.lax.all_gather(pn, axes, tiled=True)
        return out

    grid_spec = P(axes, None, None)
    in_specs = dict(x=grid_spec, r=grid_spec, z=grid_spec, p=grid_spec, rz=P())
    out_specs = dict(x=grid_spec, r=grid_spec, z=grid_spec, p=grid_spec,
                     rz=P(), beta=P())
    if esr_mode == "inmemory":
        in_specs["esr_red_cur"] = P()
        out_specs["esr_red_prev"] = P()
        out_specs["esr_red_cur"] = P()

    step = compat.shard_map(step_local, mesh=mesh, in_specs=(in_specs,),
                            out_specs=out_specs)

    def spec(nz: int, ny: int, nx: int):
        grid = jax.ShapeDtypeStruct((nz, ny, nx), dtype)
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        shardings = {k: NamedSharding(mesh, v) for k, v in in_specs.items()}
        structs = dict(x=grid, r=grid, z=grid, p=grid, rz=scalar)
        if esr_mode == "inmemory":
            structs["esr_red_cur"] = grid
        return shardings, structs

    return step, spec


def lower_pcg_step(
    mesh: Mesh,
    nz: int,
    ny: int,
    nx: int,
    esr_mode: str = "nvm",
    dtype=jnp.float32,
    shard_axes=("pod", "data", "model"),
    variant: str = "auto",
):
    """Lower one sharded PCG iteration on ``mesh`` (dry-run entry point).

    ``variant="auto"`` is the GSPMD baseline; ``"shardmap"`` is the
    hillclimbed explicit-halo version (§Perf).
    """
    if variant == "shardmap":
        step, spec = make_shardmap_pcg_step(mesh, shard_axes, esr_mode, dtype)
    else:
        step, spec = make_sharded_pcg_step(mesh, shard_axes, esr_mode, dtype)
    shardings, structs = spec(nz, ny, nx)
    with mesh:
        jitted = jax.jit(
            step,
            in_shardings=(shardings,),
            out_shardings=None,
        )
        return jitted.lower(structs)
