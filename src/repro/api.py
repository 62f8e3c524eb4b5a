"""``repro.api`` — the front door: problem, solver, resilience, solve.

The rest of the package is deliberately explicit (operators, schemas,
sessions, registries); this façade wires it for the common case so a
recoverable solve is three declarations and one call::

    from repro import api

    result = api.solve(
        api.Problem.poisson(8, nblocks=4),
        api.SolverSpec("pcg"),
        api.ResilienceSpec("replicated(nvm-prd x2)", persist_mode="overlap"),
    )
    assert result.converged

Everything is still the same machinery underneath — `SolverSpec.build`
returns a registry solver, `ResilienceSpec.build` a registry
:class:`~repro.nvm.backend.PersistenceBackend` (spec strings compose:
``"replicated(nvm-prd x2)"``, ``"tiered(nvm-homogeneous)"``), and
:func:`solve` drives :func:`repro.solvers.driver.solve` — so anything
built here interoperates with hand-wired code.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.poisson import PRECONDITIONERS, make_poisson_problem
from repro.nvm.backend import (
    BackendCapabilities,
    PersistenceBackend,
    UnrecoverableFailure,
    backend_names,
)
from repro.obs.trace import NULL_SPAN
from repro.solvers import driver as _driver
from repro.solvers.driver import (
    CampaignPlan,
    FailureCampaign,
    FailureEvent,
    FailurePlan,
    SolveConfig,
    SolveReport,
    SpecAdvice,
    SpecRanking,
    UnsurvivableCampaignError,
    advise_spec,
    plan_campaign,
)
from repro.solvers.registry import SOLVERS, make_backend, make_solver
from repro.serving.solve_service import (
    ServiceConfig,
    ServiceError,
    ServiceTicket,
    SolveService,
)
from repro.serving.trace import ServiceRequest, generate_request_trace

__all__ = [
    "Problem",
    "SolverSpec",
    "ResilienceSpec",
    "SolveResult",
    "solve",
    "advise",
    "default_candidate_specs",
    "solver_names",
    "backend_names",
    "BackendCapabilities",
    "PersistenceBackend",
    "UnrecoverableFailure",
    "CampaignPlan",
    "UnsurvivableCampaignError",
    "plan_campaign",
    "advise_spec",
    "SpecAdvice",
    "SpecRanking",
    "FailureCampaign",
    "FailureEvent",
    "FailurePlan",
    "SolveConfig",
    "SolveReport",
    "SolveService",
    "ServiceConfig",
    "ServiceError",
    "ServiceTicket",
    "ServiceRequest",
    "generate_request_trace",
    "serve",
]

#: the composite spec families — they take arguments, so the default
#: candidate list names one canonical instantiation of each
_COMPOSITE_FAMILIES = ("replicated", "tiered", "erasure")


def default_candidate_specs() -> Tuple[str, ...]:
    """The advisor's default candidate list: every non-composite
    registered backend by name, plus canonical instantiations of each
    composite family across the footprint/distance trade-off."""
    base = tuple(n for n in backend_names() if n not in _COMPOSITE_FAMILIES)
    return base + (
        "tiered(nvm-prd)",
        "replicated(nvm-prd x2)",
        "replicated(nvm-prd x3)",
        "erasure(nvm-prd x4+p)",
        "erasure(nvm-prd x6+2p)",
    )


def advise(
    problem: Problem,
    campaign,
    candidates: Optional[Sequence[str]] = None,
    solver: Union["SolverSpec", str] = "pcg",
    dtype: Any = np.float64,
    tracer=None,
) -> SpecAdvice:
    """Rank candidate resilience specs against a campaign for this
    problem: each spec is built (sized for the problem, persisting the
    solver's schema), filtered through
    :func:`~repro.solvers.driver.plan_campaign`, and the survivors
    ranked by storage footprint with modeled persist cost as
    tie-breaker (:func:`~repro.solvers.driver.advise_spec`).  The
    returned :class:`~repro.solvers.driver.SpecAdvice` renders as a
    table via :func:`repro.launch.report.spec_advice_table`.  A
    ``tracer`` (repro.obs) records per-candidate ``advise.candidate``
    events and the ``advise.chosen`` verdict."""
    if isinstance(solver, str):
        solver = SolverSpec(solver)
    built_solver = solver.build(problem)
    if candidates is None:
        candidates = default_candidate_specs()
    built = [(spec, make_backend(spec, problem.op, dtype=dtype,
                                 solver=built_solver))
             for spec in candidates]
    return advise_spec(campaign, built, probe_values=problem.op.n,
                       tracer=tracer)


def solver_names() -> list:
    """All registered solver names."""
    return sorted(SOLVERS)


@dataclasses.dataclass(frozen=True)
class Problem:
    """A linear system ``A x = b`` with a preconditioner: the operator is
    matrix-free and block-partitioned (the failure/recovery unit)."""

    op: Any
    b: Any
    precond: Any

    @property
    def nshards(self) -> int:
        """Device shards the operator is laid out over (1 = unsharded;
        >1 when the operator is a
        :class:`~repro.distributed.sharding.ShardedOperator`)."""
        layout = getattr(self.op, "layout", None)
        return 1 if layout is None else layout.nshards

    def with_shards(self, nshards: int, mesh=None) -> "Problem":
        """Lay this problem out over ``nshards`` devices on a 1-D
        ``data`` mesh (:func:`repro.distributed.sharding.shard_problem`):
        block-rows map contiguously onto shards, and the driver's
        fail/persist/recover path becomes per-shard addressable
        (``FailureEvent(shard=...)``).  The sharded solve is
        bit-identical to the unsharded one (DESIGN.md §10).  Raises if
        the problem is already sharded or fewer than ``nshards``
        devices are visible."""
        if getattr(self.op, "layout", None) is not None:
            raise ValueError(
                "problem is already sharded; shard the unsharded "
                "problem instead of re-sharding")
        from repro.distributed.sharding import shard_problem

        sop, sb = shard_problem(self.op, self.b, nshards, mesh=mesh)
        return dataclasses.replace(self, op=sop, b=sb)

    @classmethod
    def poisson(cls, nz: int, ny: Optional[int] = None,
                nx: Optional[int] = None, nblocks: int = 4,
                preconditioner: str = "jacobi",
                nshards: int = 1) -> "Problem":
        """The paper's benchmark: a 7-point 3-D Poisson stencil with a
        smooth right-hand side, split into ``nblocks`` z-slabs.  ``ny``
        and ``nx`` default to ``nz`` (a cubic grid).  ``nshards > 1``
        device-shards the problem (see :meth:`with_shards`)."""
        op, b = make_poisson_problem(nz, ny if ny is not None else nz,
                                     nx if nx is not None else nz,
                                     nblocks=nblocks)
        try:
            pre_cls = PRECONDITIONERS[preconditioner]
        except KeyError:
            from repro.nvm.backend import unknown_name_error

            raise unknown_name_error("preconditioner", preconditioner,
                                     PRECONDITIONERS) from None
        problem = cls(op=op, b=b, precond=pre_cls(op))
        if nshards != 1:
            problem = problem.with_shards(nshards)
        return problem

    @classmethod
    def from_parts(cls, op, b, precond=None) -> "Problem":
        """Wrap an existing operator / rhs / preconditioner triple."""
        if precond is None:
            precond = PRECONDITIONERS["identity"](op)
        return cls(op=op, b=b, precond=precond)


@dataclasses.dataclass(frozen=True)
class SolverSpec:
    """Which solver, to what accuracy.

    ``options`` are forwarded to the solver factory (e.g. ``{"m": 8}``
    for restarted GMRES)."""

    name: str = "pcg"
    tol: float = 1e-10
    maxiter: int = 10_000
    options: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def build(self, problem: Problem):
        return make_solver(self.name, problem.op, problem.precond,
                           **dict(self.options))


@dataclasses.dataclass(frozen=True)
class ResilienceSpec:
    """Which persistence backend, and how persistence is scheduled.

    ``backend`` is a registry name or composable spec string
    (``"nvm-prd"``, ``"replicated(nvm-prd x2)"``,
    ``"erasure(nvm-prd x4+p)"``, ``"tiered(nvm-homogeneous)"``), an
    already-built :class:`~repro.nvm.backend.PersistenceBackend`, or
    None for an unprotected run.  ``persist_mode`` picks the pipeline
    ("sync" or "overlap", DESIGN.md §6); ``period`` the ESRP
    persistence period.  ``plan_campaigns`` keeps the pre-flight
    campaign planner on (:func:`plan_campaign`, DESIGN.md §8): a
    campaign the backend's capabilities provably cannot survive is
    rejected with :class:`UnsurvivableCampaignError` before iteration
    0.  ``nshards`` pins the expected device-shard count of the
    problem: ``None`` accepts any layout, an integer makes
    :func:`solve` refuse a problem whose shard axis disagrees (the
    spec was sized/planned for that layout).  ``fused_persist``
    selects the fused persist path (DESIGN.md §13): stripe parity
    encodes run through the Pallas GF(256) kernel and, in overlap
    mode, staging defers into the compute window — slot bytes and
    solve trajectories are bit-identical to the numpy path.
    ``options`` are forwarded to the backend factory."""

    backend: Union[str, PersistenceBackend, None] = "nvm-prd"
    persist_mode: str = "sync"
    period: int = 1
    plan_campaigns: bool = True
    nshards: Optional[int] = None
    fused_persist: bool = False
    dtype: Any = np.float64
    options: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def build(self, problem: Problem, solver) -> Optional[PersistenceBackend]:
        if self.backend is None or isinstance(self.backend, PersistenceBackend):
            return self.backend
        return make_backend(self.backend, problem.op, dtype=self.dtype,
                            solver=solver, **dict(self.options))

    @classmethod
    def advise(cls, problem: Problem, campaign,
               candidates: Optional[Sequence[str]] = None,
               solver: Union["SolverSpec", str] = "pcg",
               **spec_kwargs) -> "ResilienceSpec":
        """The cheapest-spec advisor (DESIGN.md §8): return a
        :class:`ResilienceSpec` for the cheapest candidate whose
        declared capabilities carry ``campaign`` — e.g. a
        double-storage-loss campaign picks ``erasure(nvm-prd x6+2p)``
        (1.33x storage) over ``replicated(nvm-prd x3)`` (3x) on
        footprint grounds.  ``spec_kwargs`` (``persist_mode``,
        ``period``, ...) are forwarded to the spec.  Raises
        :class:`UnsurvivableCampaignError` when no candidate survives;
        use :func:`advise` for the full ranking table."""
        advice = advise(problem, campaign, candidates, solver=solver,
                        dtype=spec_kwargs.get("dtype", np.float64))
        if advice.chosen is None:
            raise UnsurvivableCampaignError(
                "no candidate spec survives the campaign: "
                + "; ".join(f"[{r.spec}] {r.reason}"
                            for r in advice.rejected))
        return cls(advice.chosen, **spec_kwargs)


@dataclasses.dataclass(frozen=True)
class SolveResult:
    """Outcome of :func:`solve`: the final solver state, the full
    :class:`~repro.solvers.driver.SolveReport`, any captured states, and
    the backend (for capability / footprint inspection)."""

    state: Any
    report: SolveReport
    captured: Dict[int, Any]
    backend: Optional[PersistenceBackend]

    @property
    def x(self) -> np.ndarray:
        """The solution iterate as a host array."""
        return np.asarray(self.state.x)

    @property
    def converged(self) -> bool:
        return self.report.converged

    @property
    def relres(self) -> float:
        return self.report.final_relres

    @property
    def iterations(self) -> int:
        return self.report.iterations

    @property
    def capabilities(self) -> Optional[BackendCapabilities]:
        return None if self.backend is None else self.backend.capabilities


def solve(
    problem: Problem,
    solver: Union[SolverSpec, str] = SolverSpec(),
    resilience: Union[ResilienceSpec, str, None] = None,
    failures: Union[FailureCampaign, Sequence, Tuple] = (),
    x0=None,
    capture_states_at: Sequence[int] = (),
    tracer=None,
) -> SolveResult:
    """Build the solver and backend from their specs and run the
    recoverable solve.

    ``solver`` and ``resilience`` accept bare name strings as shorthand
    for default specs (``"pcg"`` == ``SolverSpec("pcg")``,
    ``"replicated(nvm-prd x2)"`` ==
    ``ResilienceSpec("replicated(nvm-prd x2)")``); ``resilience=None``
    runs unprotected (and refuses injected failures, like the driver).
    ``tracer`` (a :class:`repro.obs.Tracer`) records spans and events
    through the driver, the persistence sessions, and the stager —
    export with ``tracer.to_chrome(...)`` for Perfetto
    (docs/observability.md); omitted, the hot path stays a strict no-op.
    """
    if isinstance(solver, str):
        solver = SolverSpec(solver)
    if isinstance(resilience, str):
        resilience = ResilienceSpec(resilience)
    if resilience is None:
        resilience = ResilienceSpec(backend=None)
    if (resilience.nshards is not None
            and resilience.nshards != problem.nshards):
        raise ValueError(
            f"ResilienceSpec.nshards={resilience.nshards} but the "
            f"problem is laid out over nshards={problem.nshards}; "
            f"re-shard with Problem.with_shards({resilience.nshards}) "
            f"or drop the spec's shard pin")

    trace = tracer or None
    # building the backend allocates its stores: host work that the
    # solve pays before its first iteration
    with (trace.span("solve.build", backend=resilience.backend)
          if trace is not None else NULL_SPAN):
        built_solver = solver.build(problem)
        backend = resilience.build(problem, built_solver)
    config = SolveConfig(
        tol=solver.tol,
        maxiter=solver.maxiter,
        persistence_period=resilience.period,
        persist_mode=resilience.persist_mode,
        plan_campaign=resilience.plan_campaigns,
        fused_persist=resilience.fused_persist,
        tracer=trace,
    )
    state, report, captured = _driver.solve(
        built_solver, problem.op, problem.b, problem.precond,
        config=config, backend=backend, failures=failures, x0=x0,
        capture_states_at=capture_states_at,
    )
    return SolveResult(state=state, report=report, captured=captured,
                       backend=backend)


def serve(
    requests: Sequence[ServiceRequest],
    lanes: int = 4,
    max_queue: int = 8,
    tracer=None,
) -> Dict[str, ServiceTicket]:
    """Replay a multi-tenant request trace through a fresh
    :class:`SolveService` (docs/serving.md) and return tenant ->
    ticket; each accepted ticket carries its :class:`SolveResult`.
    For incremental submission use the service object directly::

        svc = api.SolveService(api.ServiceConfig(lanes=8))
        ticket = svc.submit(problem, "pcg", failures=campaign)
        svc.drain()
    """
    svc = SolveService(ServiceConfig(lanes=lanes, max_queue=max_queue,
                                     tracer=tracer))
    return svc.replay(requests)
