"""Problem operators for the PCG solver.

The paper's workload is the 7-point stencil of the 3-D Poisson equation
(the HPCG kernel).  We implement it matrix-free — ``A`` is never
materialized globally; per-block restrictions needed by exact state
reconstruction (``A[f,f]``, ``A[f,~f]``) are derived from the stencil by
masked application (DESIGN.md §1).

Block convention: the flat index space ``I = [0, n)`` is split into
``nblocks`` contiguous equal blocks — block ``b`` owns
``I_b = [b*bs, (b+1)*bs)``.  For the stencil, blocks are z-slabs, exactly
the paper's row-block distribution of ``A``.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def stencil7(u: jax.Array) -> jax.Array:
    """7-point Poisson stencil with homogeneous Dirichlet boundary.

    ``(A u)[i,j,k] = 6 u[i,j,k] - sum of 6 face neighbours`` on a
    ``(nz, ny, nx)`` grid; out-of-domain neighbours are zero.

    ``6 u`` is formed by additions only (``2u`` and ``4u`` are exact, so
    the sum rounds once, like ``6.0 * u``).  A multiply here lets XLA's
    CPU backend contract ``6 u - a`` into a fused multiply-add in some
    fusions and not in others, and whether it does depends on the
    sharding — which broke the sharded bit-exactness contract
    (DESIGN.md §10).

    Jitted so that an eager call (``init_state``, recovery) compiles the
    pad and the shifts as one program: the TPU compiler aborts on an
    eager ``jnp.pad`` of a z-sharded f64 grid on its own.
    """
    p = jnp.pad(u, 1)
    u2 = u + u
    return (
        (u2 + u2) + u2
        - p[:-2, 1:-1, 1:-1]
        - p[2:, 1:-1, 1:-1]
        - p[1:-1, :-2, 1:-1]
        - p[1:-1, 2:, 1:-1]
        - p[1:-1, 1:-1, :-2]
        - p[1:-1, 1:-1, 2:]
    )


@dataclass(frozen=True)
class BlockPartition:
    """Contiguous equal-size block partition of ``[0, n)``."""

    n: int
    nblocks: int

    def __post_init__(self):
        if self.n % self.nblocks != 0:
            raise ValueError(f"n={self.n} not divisible by nblocks={self.nblocks}")

    @property
    def block_size(self) -> int:
        return self.n // self.nblocks

    def restrict(self, x: jax.Array, blocks: Sequence[int]) -> jax.Array:
        """``x[I_F]`` for the union F of ``blocks`` (concatenated, flat)."""
        xb = x.reshape(self.nblocks, self.block_size)
        return xb[jnp.asarray(blocks)].reshape(-1)

    def zero_blocks(self, x: jax.Array, blocks: Sequence[int]) -> jax.Array:
        """``x`` with ``x[I_F] = 0``."""
        xb = x.reshape(self.nblocks, self.block_size)
        return xb.at[jnp.asarray(blocks)].set(0.0).reshape(-1)

    def embed(self, v: jax.Array, blocks: Sequence[int]) -> jax.Array:
        """Scatter a concatenated union vector back into a zero full vector."""
        xb = jnp.zeros((self.nblocks, self.block_size), v.dtype)
        vb = v.reshape(len(blocks), self.block_size)
        return xb.at[jnp.asarray(blocks)].set(vb).reshape(-1)

    def scatter(self, x: jax.Array, v: jax.Array, blocks: Sequence[int]) -> jax.Array:
        """``x`` with ``x[I_F] <- v``."""
        xb = x.reshape(self.nblocks, self.block_size)
        vb = v.reshape(len(blocks), self.block_size)
        return xb.at[jnp.asarray(blocks)].set(vb).reshape(-1)


class StencilOperator:
    """Matrix-free 7-point stencil operator on a 3-D grid.

    Blocks are z-slabs: ``nblocks`` must divide ``nz``.
    """

    def __init__(self, nz: int, ny: int, nx: int, nblocks: int = 1, dtype=jnp.float64):
        self.grid = (nz, ny, nx)
        self.n = nz * ny * nx
        self.dtype = dtype
        if nz % nblocks != 0:
            raise ValueError(f"nz={nz} not divisible by nblocks={nblocks}")
        self.partition = BlockPartition(self.n, nblocks)

    @property
    def nblocks(self) -> int:
        return self.partition.nblocks

    def apply(self, x: jax.Array) -> jax.Array:
        return stencil7(x.reshape(self.grid)).reshape(-1).astype(x.dtype)

    def diag(self) -> jax.Array:
        return jnp.full((self.n,), 6.0, self.dtype)

    # ------- restrictions used by exact state reconstruction -------
    def offblock_apply(self, x: jax.Array, blocks: Sequence[int]) -> jax.Array:
        """``A[F, ~F] @ x[~F]``: apply with x zeroed on F, restrict to F."""
        xm = self.partition.zero_blocks(x, blocks)
        return self.partition.restrict(self.apply(xm), blocks)

    def inblock_apply(self, v: jax.Array, blocks: Sequence[int]) -> jax.Array:
        """``A[F, F] @ v`` for the (possibly multi-block) union F."""
        xf = self.partition.embed(v, blocks)
        return self.partition.restrict(self.apply(xf), blocks)

    def to_dense(self) -> np.ndarray:
        eye = jnp.eye(self.n, dtype=self.dtype)
        return np.asarray(jax.vmap(self.apply)(eye).T)


class DenseOperator:
    """Explicit SPD matrix operator (used by property tests)."""

    def __init__(self, a: np.ndarray, nblocks: int = 1):
        a = np.asarray(a)
        if a.ndim != 2 or a.shape[0] != a.shape[1]:
            raise ValueError("square matrix required")
        self.a = jnp.asarray(a)
        self.n = a.shape[0]
        self.dtype = self.a.dtype
        self.partition = BlockPartition(self.n, nblocks)

    @property
    def nblocks(self) -> int:
        return self.partition.nblocks

    def apply(self, x: jax.Array) -> jax.Array:
        return self.a @ x

    def diag(self) -> jax.Array:
        return jnp.diagonal(self.a)

    def offblock_apply(self, x: jax.Array, blocks: Sequence[int]) -> jax.Array:
        xm = self.partition.zero_blocks(x, blocks)
        return self.partition.restrict(self.apply(xm), blocks)

    def inblock_apply(self, v: jax.Array, blocks: Sequence[int]) -> jax.Array:
        xf = self.partition.embed(v, blocks)
        return self.partition.restrict(self.apply(xf), blocks)

    def to_dense(self) -> np.ndarray:
        return np.asarray(self.a)


def random_spd(n: int, seed: int = 0, cond: float = 50.0) -> np.ndarray:
    """Well-conditioned random SPD matrix for tests."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    eigs = np.geomspace(1.0, cond, n)
    return (q * eigs) @ q.T


# ======================================================================
# Preconditioners.  ``apply`` computes z = P r.  Reconstruction needs
# ``block_solve`` (solve P[F,F] r_F = v) and ``offblock_apply``
# (P[F,~F] r[~F]); both are trivial/local for the families below, which
# is precisely why they are the standard choices for ESR-enabled PCG.
# ======================================================================
class IdentityPreconditioner:
    def __init__(self, op):
        self.op = op

    def apply(self, r: jax.Array) -> jax.Array:
        return r

    def block_solve(self, v: jax.Array, blocks: Sequence[int]) -> jax.Array:
        return v

    def offblock_apply(self, r: jax.Array, blocks: Sequence[int]) -> jax.Array:
        return jnp.zeros_like(self.op.partition.restrict(r, blocks))


class JacobiPreconditioner:
    """P = D^{-1}; diagonal, hence P[F,~F] = 0 and block solves are local."""

    def __init__(self, op):
        self.op = op
        self.inv_diag = 1.0 / op.diag()

    def apply(self, r: jax.Array) -> jax.Array:
        return r * self.inv_diag

    def block_solve(self, v: jax.Array, blocks: Sequence[int]) -> jax.Array:
        # P[F,F] r_F = v  =>  r_F = v / inv_diag[F]
        return v / self.op.partition.restrict(self.inv_diag, blocks)

    def offblock_apply(self, r: jax.Array, blocks: Sequence[int]) -> jax.Array:
        return jnp.zeros_like(self.op.partition.restrict(r, blocks))


class BlockJacobiPreconditioner:
    """P = blockdiag(A[s,s]^{-1}) aligned with the process blocks.

    ``apply`` solves the per-block systems with cached dense Cholesky
    factors (test scale) — production would use local CG.  For
    reconstruction, ``P[F,F]^{-1} = blockdiag(A[s,s])``: the *forward*
    local stencil application, so ``block_solve`` is exact and cheap.
    """

    def __init__(self, op):
        self.op = op
        bs = op.partition.block_size
        blocks = []
        for b in range(op.nblocks):
            cols = jax.vmap(lambda v: op.inblock_apply(v, [b]))(jnp.eye(bs, dtype=op.dtype))
            blocks.append(np.asarray(cols.T))
        self._factors = [np.linalg.cholesky(blk) for blk in blocks]
        self._chol = jnp.asarray(np.stack(self._factors))

    def apply(self, r: jax.Array) -> jax.Array:
        part = self.op.partition
        rb = r.reshape(part.nblocks, part.block_size)

        def solve_one(chol, rhs):
            y = jax.scipy.linalg.solve_triangular(chol, rhs, lower=True)
            return jax.scipy.linalg.solve_triangular(chol.T, y, lower=False)

        return jax.vmap(solve_one)(self._chol, rb).reshape(-1)

    def block_solve(self, v: jax.Array, blocks: Sequence[int]) -> jax.Array:
        # P[F,F] r_F = v  =>  r_F = blockdiag(A[s,s]) v : per-block forward apply
        part = self.op.partition
        vb = v.reshape(len(blocks), part.block_size)
        outs = [self.op.inblock_apply(vb[i], [b]) for i, b in enumerate(blocks)]
        return jnp.concatenate(outs)

    def offblock_apply(self, r: jax.Array, blocks: Sequence[int]) -> jax.Array:
        return jnp.zeros_like(self.op.partition.restrict(r, blocks))


PRECONDITIONERS = {
    "identity": IdentityPreconditioner,
    "jacobi": JacobiPreconditioner,
    "block_jacobi": BlockJacobiPreconditioner,
}


def make_poisson_problem(
    nz: int, ny: int, nx: int, nblocks: int, dtype=jnp.float64, seed: int = 0
) -> Tuple[StencilOperator, jax.Array]:
    """Stencil operator + smooth right-hand side (paper's benchmark problem)."""
    op = StencilOperator(nz, ny, nx, nblocks, dtype)
    z, y, x = jnp.meshgrid(
        jnp.linspace(0, 1, nz), jnp.linspace(0, 1, ny), jnp.linspace(0, 1, nx), indexing="ij"
    )
    b = jnp.sin(jnp.pi * x) * jnp.sin(jnp.pi * y) * jnp.sin(jnp.pi * z) + 0.1
    return op, b.reshape(-1).astype(dtype)
