"""The :class:`RecoverableSolver` interface.

An ESR-recoverable solver is a fixed-point/Krylov iteration whose lost
state is exactly derivable from (a) a few persisted vectors/scalars — its
:class:`~repro.core.state.RecoverySchema` — plus (b) the surviving shards
and (c) static data (``A`` rows, ``P`` rows, ``b``; regenerated
matrix-free here).  The generic driver (:mod:`repro.solvers.driver`)
handles scheduling, failure injection, snapshots, and reporting; each
solver supplies:

- ``init_state`` / ``make_step``: the jitted iteration over a NamedTuple
  state pytree that carries an integer ``k`` (completed iterations) and
  a residual vector ``r`` (for convergence monitoring: the driver reads
  ``||r||`` through :func:`device_norm`, reduced on the device).
- ``recovery_set``: extraction of the minimal persisted payload.
- ``reconstruct``: the paper's Algorithm 3/5 pattern — rebuild the failed
  shards exactly from persisted + surviving + static data.
- ``wipe``: the failure model (which state fields live in failed VM).
"""
from __future__ import annotations

import abc
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.state import RecoverySchema, RecoverySet, wipe_vectors


def solver_dot(op):
    """The inner product a zoo solver must use: block-hierarchical with a
    pinned combine order (:func:`repro.core.spmv.make_det_dot`), so the
    trajectory is bitwise identical whether ``op`` is a plain operator or
    a :class:`~repro.distributed.sharding.ShardedOperator` on any shard
    count — the sharded-exactness contract (DESIGN.md §10)."""
    from repro.core.spmv import make_det_dot

    return make_det_dot(op.nblocks, getattr(op, "mesh", None))


@functools.lru_cache(maxsize=None)
def _sq_norm(nblocks: int, mesh):
    """The jitted ``v -> det_dot(v, v)`` for one block layout, built once
    so a solve's warm-up traces it and later calls reuse the program."""
    from repro.core.spmv import make_det_dot

    dot = make_det_dot(nblocks, mesh)

    def sq_norm(v):
        return dot(v, v)

    return jax.jit(sq_norm)


def device_norm(op, v) -> float:
    """``||v||_2`` of a (possibly device-sharded) vector in ``op``'s block
    layout: the convergence norm of every solver, solo and in the service.

    The sum of squares is :func:`solver_dot`'s order-pinned reduction, run
    on the device, so the bits are the same on any shard count (DESIGN.md
    §10); only that f64 scalar comes back to the host, which takes an
    IEEE square root of it.  Nothing of ``v`` itself is copied to the
    host."""
    sq = _sq_norm(op.nblocks, getattr(op, "mesh", None))(v)
    return math.sqrt(float(sq))


def base_operator(op):
    """Unwrap a :class:`~repro.distributed.sharding.ShardedOperator` (or
    any delegating wrapper exposing ``base``) for code that dispatches on
    the concrete operator type, e.g. closed-form spectral bounds."""
    return getattr(op, "base", op)


class RecoverableSolver(abc.ABC):
    """Base class / protocol for ESR-recoverable iterative solvers."""

    #: registry name ("pcg", "jacobi", ...)
    name: str = ""
    #: minimal recovery set declaration (drives backend slot layout)
    schema: RecoverySchema
    #: state fields holding block-distributed vectors (failure wipes them)
    state_vector_fields: Sequence[str] = ()
    #: state fields holding non-replicated reduction scalars (NaN'd on
    #: failure; restored by reconstruction)
    state_nan_scalars: Sequence[str] = ()

    #: whether the solver offers a :meth:`lane_step` for the batched
    #: multi-tenant service path (DESIGN.md §12); GMRES's restart-cycle
    #: step is host-orchestrated and stays solo-only
    batchable = False

    # ------------------------------------------------------------------
    @abc.abstractmethod
    def init_state(self, op, precond, b, x0=None):
        """State after 0 completed iterations (pytree with ``k`` and ``r``)."""

    @abc.abstractmethod
    def make_step(self, op, precond):
        """Return the jitted one-iteration transition ``state -> state``.

        Called once per solve, after :meth:`init_state` (so solvers may
        close over per-solve derived static data, e.g. BiCGStab's shadow
        residual).
        """

    @abc.abstractmethod
    def recovery_set(self, state) -> RecoverySet:
        """The minimal persisted payload at this iteration (host arrays)."""

    @abc.abstractmethod
    def reconstruct(self, op, precond, b, snapshot, failed_blocks,
                    sets: Sequence[RecoverySet], local_method: str = "auto"):
        """Exactly rebuild the failed shards at ``snapshot.k``.

        ``sets`` holds the recovered payload unions, oldest -> newest,
        with ``sets[-1].k == snapshot.k`` and ``len(sets) ==
        schema.history``; each union vector is concatenated in
        ``failed_blocks`` order.
        """

    # ------------------------------------------------------------------
    @classmethod
    def lane_step(cls, op_apply, precond_apply, dot, params):
        """Un-jitted one-iteration transition for ONE lane of a batched
        (vmapped) solve — the multi-tenant service path (DESIGN.md §12).

        Unlike :meth:`make_step`, which may close over per-solve Python
        constants, every per-tenant quantity (Chebyshev recurrence
        coefficients, the Jacobi weight, BiCGStab's shadow residual)
        arrives through ``params`` as *traced* values, so one compiled
        ``vmap`` body serves heterogeneous tenants.  Solvers share the
        step body with :meth:`make_step` (a module-level builder), so
        the solo path stays bit-identical.
        """
        raise NotImplementedError(
            f"solver {cls.name!r} has no batched lane step "
            f"(batchable={cls.batchable})")

    def lane_params(self):
        """The per-lane ``params`` pytree :meth:`lane_step` consumes, read
        off a solver built for this tenant (after :meth:`init_state` for
        solvers whose params are derived there).  Default: none."""
        return {}

    # ------------------------------------------------------------------
    def wipe(self, state, partition, blocks):
        """Simulate failure: failed shards of every distributed vector (and
        any non-replicated reduction scalar) become garbage."""
        return wipe_vectors(state, partition, blocks,
                            self.state_vector_fields, self.state_nan_scalars)

    # ------------------------------------------------------------------
    def host_shard(self, arr) -> np.ndarray:
        """Device -> host pull of a persisted vector (the NVM-ESR tap is a
        host-side copy of the local shard; no collective)."""
        return np.asarray(arr)

    @classmethod
    def from_problem(cls, op=None, precond=None, **opts) -> "RecoverableSolver":
        """Registry hook: build a solver tuned to (op, precond).  The
        default ignores the problem; solvers needing derived parameters
        (Chebyshev bounds, Jacobi weight) override this."""
        return cls(**opts)


class IterateOnlyRecovery:
    """Shared implementation for solvers whose minimal recovery set is the
    iterate itself — schema ``{x}``, history 1 (weighted Jacobi, restarted
    GMRES).  The state class must be ``(x, r, k)``; reconstruction is a
    scatter of the persisted shard plus the direct residual restriction
    ``r_F = b_F - A[F,F] x_F - A[F,~F] x_{~F}`` (no local solve)."""

    state_cls: type
    state_vector_fields = ("x", "r")
    state_nan_scalars = ()

    def init_state(self, op, precond, b, x0=None):
        x0 = jnp.zeros_like(b) if x0 is None else x0
        return self.state_cls(x=x0, r=b - op.apply(x0),
                              k=jnp.zeros((), jnp.int32))

    def recovery_set(self, state) -> RecoverySet:
        return RecoverySet(k=int(state.k), scalars={},
                           vectors={"x": self.host_shard(state.x)})

    def reconstruct(self, op, precond, b, snapshot, failed_blocks,
                    sets: Sequence[RecoverySet], local_method: str = "auto"):
        from repro.core.reconstruction import residual_on_failed

        part = op.partition
        failed = list(failed_blocks)
        x_f = jnp.asarray(sets[-1].vectors["x"], b.dtype)
        x = part.scatter(snapshot.x, x_f, failed)
        r = part.scatter(snapshot.r, residual_on_failed(op, b, x, failed), failed)
        return self.state_cls(x=x, r=r, k=snapshot.k)
