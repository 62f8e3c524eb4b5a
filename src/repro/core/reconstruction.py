"""Exact state reconstruction for PCG (paper Algorithm 3 / 5).

Given the persisted minimal set ``(p^(k-1)_F, p^(k)_F, beta^(k-1))`` for
the failed block union F, plus the surviving shards of ``x, r`` and the
static data (A rows, P rows, b — regenerated matrix-free here), the full
failed state is reconstructed *exactly* (to solver precision):

    z_F = p^(k)_F - beta^(k-1) * p^(k-1)_F                      (line 4)
    solve  P[F,F] r_F = z_F - P[F,~F] r_~F                      (lines 5-6)
    solve  A[F,F] x_F = b_F - r_F - A[F,~F] x_~F                (lines 7-8)

The local solves run on the replacement node; ``A[F,F]`` is SPD (principal
submatrix of an SPD matrix), so we solve with a dense Cholesky for small
blocks or matrix-free local CG for large ones.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.state import PCGState


#: the most recent replacement-node CG solve: ``iterations`` taken and
#: the ``maxiter`` cap.  A solve that used every iteration stopped short
#: of ``tol`` (the relative residual floor the precision allows).
last_local_cg: Dict[str, int] = {}


def _local_cg(apply_fn, rhs: jax.Array, tol: float = 1e-14, maxiter: int = 10000) -> jax.Array:
    """Matrix-free CG on the failed-block operator (replacement-node
    solve); records its iteration count in :data:`last_local_cg`."""

    def body(carry):
        x, r, p, rs, it = carry
        ap = apply_fn(p)
        # repro-lint: noqa[RL201] -- replacement-node local solve: single-block, single-device by construction
        alpha = rs / jnp.vdot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        # repro-lint: noqa[RL201] -- replacement-node local solve: single-block, single-device by construction
        rs_new = jnp.vdot(r, r)
        p = r + (rs_new / rs) * p
        return x, r, p, rs_new, it + 1

    def cond(carry):
        _, _, _, rs, it = carry
        return jnp.logical_and(rs > tol * tol * rs0, it < maxiter)

    x0 = jnp.zeros_like(rhs)
    # repro-lint: noqa[RL201] -- replacement-node local solve: single-block, single-device by construction
    rs0 = jnp.vdot(rhs, rhs)
    init = (x0, rhs, rhs, rs0, jnp.asarray(0))
    x, _, _, _, it = jax.lax.while_loop(cond, body, init)
    last_local_cg.update(iterations=int(it), maxiter=maxiter)
    return x


def _local_dense_solve(apply_fn, rhs: jax.Array) -> jax.Array:
    """Materialize A[F,F] column-by-column and Cholesky-solve (small F)."""
    m = rhs.shape[0]
    eye = jnp.eye(m, dtype=rhs.dtype)
    a_ff = jax.vmap(apply_fn)(eye).T
    chol = jnp.linalg.cholesky(a_ff)
    y = jax.scipy.linalg.solve_triangular(chol, rhs, lower=True)
    return jax.scipy.linalg.solve_triangular(chol.T, y, lower=False)


def solve_local(apply_fn, rhs: jax.Array, method: str = "auto") -> jax.Array:
    if method == "auto":
        method = "dense" if rhs.shape[0] <= 1024 else "cg"
    if method == "dense":
        return _local_dense_solve(apply_fn, rhs)
    if method == "cg":
        return _local_cg(apply_fn, rhs)
    raise ValueError(f"unknown local solve method {method!r}")


def solve_x_from_residual(
    op,
    b: jax.Array,
    x_surviving: jax.Array,
    r_f: jax.Array,
    failed: Sequence[int],
    local_method: str = "auto",
) -> jax.Array:
    """Algorithm 3 lines 7-8: solve ``A[F,F] x_F = b_F - r_F - A[F,~F] x_{~F}``
    and return the full ``x`` with the failed union restored."""
    part = op.partition
    x_clean = part.scatter(x_surviving, jnp.zeros_like(r_f), failed)
    w = part.restrict(b, failed) - r_f - op.offblock_apply(x_clean, failed)
    x_f = solve_local(lambda u: op.inblock_apply(u, failed), w, local_method)
    return part.scatter(x_surviving, x_f, failed)


def residual_on_failed(op, b: jax.Array, x: jax.Array,
                       failed: Sequence[int]) -> jax.Array:
    """``r_F = b_F - A[F,F] x_F - A[F,~F] x_{~F}`` — the direct residual
    restriction, used by solvers whose recovery set contains ``x`` itself
    (weighted Jacobi, restarted GMRES)."""
    part = op.partition
    return (part.restrict(b, failed)
            - op.inblock_apply(part.restrict(x, failed), failed)
            - op.offblock_apply(x, failed))


def reconstruct_direction_form(
    op,
    precond,
    b: jax.Array,
    state_surviving,
    failed_blocks: Sequence[int],
    p_prev_f: jax.Array,
    p_cur_f: jax.Array,
    beta: float,
    local_method: str = "auto",
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Algorithm 3 core for any solver with the three-term direction
    structure ``p^(k) = z^(k) + beta^(k) p^(k-1)`` (PCG, Chebyshev).

    ``state_surviving`` carries valid ``x, r, z, p`` on surviving blocks
    (failed shards may be garbage — they are overwritten).
    ``p_prev_f``/``p_cur_f`` are the persisted shards for the failed
    union, concatenated in ``failed_blocks`` order.  Returns the fully
    restored ``(x, r, z, p)``.
    """
    part = op.partition
    failed = list(failed_blocks)

    # Line 4: z_F = p^(k)_F - beta * p^(k-1)_F
    z_f = p_cur_f - beta * p_prev_f

    # Lines 5-6: solve P[F,F] r_F = z_F - P[F,~F] r_{~F}
    r_clean = part.scatter(state_surviving.r, jnp.zeros_like(z_f), failed)
    v = z_f - precond.offblock_apply(r_clean, failed)
    r_f = precond.block_solve(v, failed)

    # Lines 7-8: solve A[F,F] x_F = b_F - r_F - A[F,~F] x_{~F}
    x = solve_x_from_residual(op, b, state_surviving.x, r_f, failed, local_method)

    # Reassemble; p_F comes straight from the redundancy.
    r = part.scatter(state_surviving.r, r_f, failed)
    z = part.scatter(state_surviving.z, z_f, failed)
    p = part.scatter(state_surviving.p, p_cur_f, failed)
    return x, r, z, p


def reconstruct(
    op,
    precond,
    b: jax.Array,
    state_surviving: PCGState,
    failed_blocks: Sequence[int],
    p_prev_f: jax.Array,
    p_cur_f: jax.Array,
    beta: float,
    local_method: str = "auto",
    dot=jnp.vdot,
) -> PCGState:
    """Run Algorithm 3 and return the fully reconstructed PCG state at ``k``.

    ``dot`` must match the solve loop's inner product (the zoo passes the
    order-pinned one) so the restored ``rz`` is bitwise what the unfailed
    trajectory would carry."""
    x, r, z, p = reconstruct_direction_form(
        op, precond, b, state_surviving, failed_blocks,
        p_prev_f, p_cur_f, beta, local_method)
    rz = dot(r, z)  # global reduction (replaces the replicated scalar)
    return PCGState(
        x=x, r=r, z=z, p=p, rz=rz,
        beta_prev=jnp.asarray(beta, x.dtype), k=state_surviving.k,
    )
