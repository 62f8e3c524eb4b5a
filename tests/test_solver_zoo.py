"""The solver zoo acceptance suite.

Every registered solver must (1) converge on the 3-D Poisson problem and
(2) after an injected multi-block failure at mid-solve, recover through
BOTH NVM-ESR backends with a post-recovery state matching the
failure-free run to solver precision — the paper's exactness claim,
generalized beyond PCG.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    JacobiPreconditioner,
    NVMESRHomogeneous,
    make_poisson_problem,
)
from repro.solvers import (
    SOLVERS,
    FailurePlan,
    SolveConfig,
    make_backend,
    make_solver,
    solve,
    spectral_bounds,
)

NVM_BACKENDS = ("nvm-homogeneous", "nvm-prd")

# (fail_at, solver opts): gmres counts restart cycles, not iterations
SOLVER_CASES = {
    "pcg": (10, {}),
    "jacobi": (10, {}),
    "chebyshev": (10, {}),
    "bicgstab": (10, {}),
    "gmres": (3, {"m": 4}),
}
assert set(SOLVER_CASES) == set(SOLVERS)


def _problem(nblocks=4):
    op, b = make_poisson_problem(8, 8, 8, nblocks=nblocks)
    return op, b, JacobiPreconditioner(op)


def _state_fields_close(got, want, rtol=1e-9, atol=1e-9):
    for field in got._fields:
        a, c = getattr(got, field), getattr(want, field)
        if hasattr(a, "shape"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=rtol, atol=atol, err_msg=field)


@pytest.mark.parametrize("solver_name", sorted(SOLVERS))
def test_solver_converges_poisson(solver_name):
    op, b, pre = _problem()
    fail_at, opts = SOLVER_CASES[solver_name]
    solver = make_solver(solver_name, op, pre, **opts)
    state, report, _ = solve(solver, op, b, pre,
                             SolveConfig(tol=1e-10, maxiter=5000))
    assert report.converged, report
    res = float(jnp.linalg.norm(b - op.apply(state.x)) / jnp.linalg.norm(b))
    assert res < 1e-9


@pytest.mark.parametrize("backend_name", NVM_BACKENDS)
@pytest.mark.parametrize("solver_name", sorted(SOLVERS))
def test_multi_block_failure_recovers_exactly(solver_name, backend_name):
    """The acceptance criterion: mid-solve multi-block failure, recovery
    through both NVM architectures, post-recovery state element-wise equal
    to the fault-free run at the same iteration."""
    op, b, pre = _problem()
    fail_at, opts = SOLVER_CASES[solver_name]
    cfg = SolveConfig(tol=1e-10, maxiter=5000)

    ref_solver = make_solver(solver_name, op, pre, **opts)
    _, ref_report, ref_cap = solve(ref_solver, op, b, pre, cfg,
                                   capture_states_at=[fail_at])

    solver = make_solver(solver_name, op, pre, **opts)
    backend = make_backend(backend_name, op, solver=solver)
    state, report, cap = solve(
        solver, op, b, pre, cfg, backend=backend,
        failures=[FailurePlan(fail_at, (1, 2))],
        capture_states_at=[fail_at])

    assert report.failures_recovered == 1
    assert report.converged
    # T=1: the recovery point IS the failure iteration -> exact match
    assert report.wasted_iterations == 0
    _state_fields_close(cap[fail_at], ref_cap[fail_at])
    res = float(jnp.linalg.norm(b - op.apply(state.x)) / jnp.linalg.norm(b))
    assert res < 1e-9


@pytest.mark.parametrize("solver_name", ["jacobi", "bicgstab", "gmres"])
def test_history1_periodic_persistence(solver_name):
    """History-1 solvers under ESRP: persistence every T iterations only,
    failure rolls back to the last persisted iteration (<T wasted)."""
    op, b, pre = _problem()
    _, opts = SOLVER_CASES[solver_name]
    solver = make_solver(solver_name, op, pre, **opts)
    backend = make_backend("nvm-prd", op, solver=solver)
    fail_at = 5 if solver_name == "gmres" else 10
    state, report, _ = solve(
        solver, op, b, pre,
        SolveConfig(tol=1e-10, maxiter=5000, persistence_period=4),
        backend=backend, failures=[FailurePlan(fail_at, (0, 3))])
    assert report.failures_recovered == 1
    assert report.converged
    assert 0 < report.wasted_iterations < 4   # rolled back inside one period
    assert report.persist_events < report.iterations


def test_all_blocks_but_one_fail_nvm():
    """NVM-ESR's defining property holds zoo-wide: any number of
    simultaneous compute failures recovers from one persisted copy."""
    op, b, pre = _problem(nblocks=8)
    solver = make_solver("bicgstab", op, pre)
    backend = make_backend("nvm-prd", op, solver=solver)
    state, report, _ = solve(solver, op, b, pre, SolveConfig(tol=1e-10),
                             backend=backend,
                             failures=[FailurePlan(6, tuple(range(7)))])
    assert report.failures_recovered == 1
    assert report.converged


def test_repeated_failures_across_solvers():
    op, b, pre = _problem(nblocks=8)
    for name in ("chebyshev", "bicgstab"):
        solver = make_solver(name, op, pre)
        backend = make_backend("nvm-homogeneous", op, solver=solver)
        state, report, _ = solve(
            solver, op, b, pre, SolveConfig(tol=1e-10, maxiter=5000),
            backend=backend,
            failures=[FailurePlan(5, (0,)), FailurePlan(9, (2, 3))])
        assert report.failures_recovered == 2, name
        assert report.converged, name


def test_schema_mismatch_rejected():
    """A backend sized for one solver's payload cannot silently persist
    another's: the driver refuses up front."""
    op, b, pre = _problem()
    pcg = make_solver("pcg", op, pre)
    backend = make_backend("nvm-prd", op, solver=pcg)
    bicg = make_solver("bicgstab", op, pre)
    with pytest.raises(ValueError, match="schema"):
        solve(bicg, op, b, pre, SolveConfig(tol=1e-10), backend=backend)


def test_multi_vector_slots_sized_by_schema():
    """BiCGStab persists two vectors + three scalars per slot; the NVM
    footprint follows the schema, not a hard-coded PCG layout."""
    op, b, pre = _problem()
    bicg = make_solver("bicgstab", op, pre)
    be = make_backend("nvm-prd", op, solver=bicg)
    # history=1 -> 2-slot ring; 2 vectors per slot
    assert be.nvm_values() == 2 * 2 * op.n
    pcg_be = make_backend("nvm-prd", op, solver=make_solver("pcg", op, pre))
    assert pcg_be.nvm_values() == 4 * op.n  # the paper's 4-slot pair ring


def test_failure_at_iteration_zero_rejected():
    """A plan that could never fire would silently disarm every later
    plan (injection matches the sorted list head) — the driver refuses."""
    op, b, pre = _problem()
    solver = make_solver("pcg", op, pre)
    backend = make_backend("nvm-prd", op, solver=solver)
    with pytest.raises(ValueError, match="at_iteration"):
        solve(solver, op, b, pre, SolveConfig(tol=1e-10), backend=backend,
              failures=[FailurePlan(0, (1,)), FailurePlan(5, (2,))])


def test_registry_errors():
    op, b, pre = _problem()
    with pytest.raises(KeyError, match="unknown solver"):
        make_solver("sor", op, pre)
    with pytest.raises(KeyError, match="unknown backend"):
        make_backend("tape", op)


def test_spectral_bounds_routes():
    """Closed form (stencil) and dense (generic) bound estimates agree."""
    op, b, pre = _problem()
    lo_cf, hi_cf = spectral_bounds(op, pre)

    class _NotAStencil:
        def __init__(self, op):
            self._op = op
            self.n, self.dtype, self.partition = op.n, op.dtype, op.partition

        def apply(self, v):
            return self._op.apply(v)

    lo_d, hi_d = spectral_bounds(_NotAStencil(op), pre)
    np.testing.assert_allclose([lo_cf, hi_cf], [lo_d, hi_d], rtol=1e-8)


def test_legacy_duck_typed_backend_still_drives_pcg_solve():
    """External backends written against the pre-zoo contract (persist /
    recover / fail only, PCG payloads) keep working through the generic
    driver, and are cleanly rejected for non-PCG schemas."""
    from repro.core.state import RecoveryPayload

    class OldStyleBackend:
        def __init__(self, nblocks, block_size):
            self.nblocks, self.block_size = nblocks, block_size
            self.slots = {}

        def persist(self, k, beta, p_full):
            self.slots[k] = (beta, np.asarray(p_full).copy())
            return 0.0

        def fail(self, blocks):
            pass

        def recover(self, blocks, k):
            def payload(kk, beta):
                shards = [self.slots[kk][1][b * self.block_size:(b + 1) * self.block_size]
                          for b in blocks]
                return RecoveryPayload(kk, beta, np.concatenate(shards))
            return payload(k - 1, 0.0), payload(k, self.slots[k][0])

    op, b, pre = _problem()
    be = OldStyleBackend(op.nblocks, op.partition.block_size)
    solver = make_solver("pcg", op, pre)
    state, report, _ = solve(solver, op, b, pre, SolveConfig(tol=1e-10),
                             backend=be, failures=[FailurePlan(10, (1, 2))])
    assert report.failures_recovered == 1 and report.converged

    with pytest.raises(ValueError, match="legacy"):
        solve(make_solver("bicgstab", op, pre), op, b, pre,
              SolveConfig(tol=1e-10), backend=OldStyleBackend(
                  op.nblocks, op.partition.block_size))


def test_legacy_backend_api_still_serves_pcg():
    """The pre-zoo persist/recover entry points (used by the Fig. 9/10
    benchmarks) stay wire-compatible with the schema-driven path."""
    op, b, pre = _problem()
    be = NVMESRHomogeneous(op.nblocks, op.partition.block_size, np.float64)
    p0 = np.arange(op.n, dtype=np.float64)
    p1 = p0 + 1.0
    be.persist(0, 0.0, p0)
    be.persist(1, 0.25, p1)
    prev, cur = be.recover([1, 2], 1)
    assert prev.k == 0 and cur.k == 1 and cur.beta == 0.25
    bs = op.partition.block_size
    np.testing.assert_array_equal(prev.p, p0[bs:3 * bs])
    np.testing.assert_array_equal(cur.p, p1[bs:3 * bs])


# ----------------------------------------------------------------------
# The convergence norm is reduced on the device (DESIGN.md §10)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("solver_name", sorted(SOLVERS))
def test_device_norm_matches_numpy(solver_name):
    """``device_norm`` of each solver's residual, a few iterations in,
    equals the host numpy 2-norm up to the change of summation order."""
    from repro.solvers.base import device_norm

    op, b, pre = _problem()
    _, opts = SOLVER_CASES[solver_name]
    solver = make_solver(solver_name, op, pre, **opts)
    state = solver.init_state(op, pre, b)
    step = solver.make_step(op, pre)
    for _ in range(3):
        state = step(state)
    for v in (b, state.r):
        want = np.linalg.norm(np.asarray(v))
        assert abs(device_norm(op, v) - want) <= 1e-14 * want


@pytest.fixture
def host_pulls(monkeypatch):
    """Every conversion of a jax array to numpy, through ``np.asarray``,
    ``np.array`` or the array's own ``_value`` (``float``, ``int``,
    ``tolist``): ``(size, names of the functions on the stack)``.  A
    constant that a jitted program closes over, read once as XLA lowers
    the program, is not a pull of the loop's and is left out."""
    import sys

    from jax._src.array import ArrayImpl

    seen = []

    def record(a):
        if isinstance(a, jax.Array):
            names, f = set(), sys._getframe(2)
            while f is not None:
                names.add(f.f_code.co_name)
                f = f.f_back
            if "_array_mlir_constant_handler" not in names:
                seen.append((a.size, names))

    def spying(convert):
        def spy(a, *args, **kwargs):
            record(a)
            return convert(a, *args, **kwargs)
        return spy

    value = ArrayImpl._value

    def value_spy(self):
        record(self)
        return value.fget(self)

    monkeypatch.setattr(np, "asarray", spying(np.asarray))
    monkeypatch.setattr(np, "array", spying(np.array))
    monkeypatch.setattr(ArrayImpl, "_value", property(value_spy))
    return seen


def test_unprotected_solve_pulls_no_vector_to_the_host(host_pulls):
    """bnorm, every loop-top check and ``finalize`` bring back one
    scalar each: no array of more than one element reaches numpy."""
    op, b, pre = _problem()
    solver = make_solver("pcg", op, pre)
    state, report, _ = solve(solver, op, b, pre,
                             SolveConfig(tol=0.0, maxiter=4))
    assert len(report.residual_history) == 4 and report.final_relres > 0
    assert [size for size, _ in host_pulls if size > 1] == []
    np.asarray(state.r)  # and the spy does see a vector pull
    assert host_pulls[-1][0] == op.n


def test_persisting_solve_pulls_only_the_recovery_set(host_pulls):
    """An nvm-prd solve copies p (its recovery set) to the host at each
    persistence point, and no other vector: r stays on the device."""
    op, b, pre = _problem()
    solver = make_solver("pcg", op, pre)
    backend = make_backend("nvm-prd", op, solver=solver)
    _, report, _ = solve(solver, op, b, pre,
                         SolveConfig(tol=0.0, maxiter=4), backend=backend)
    assert report.persist_events == 5
    vectors = [names for size, names in host_pulls if size > 1]
    assert len(vectors) >= report.persist_events
    assert all("recovery_set" in names for names in vectors)
