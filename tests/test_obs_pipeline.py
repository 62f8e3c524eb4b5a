"""Pipeline-level observability contracts (ISSUE 6, DESIGN.md §9).

Three things only an end-to-end solve can establish:

1. **Enabled coverage** — a traced campaign solve emits the span/event
   taxonomy (docs/observability.md §2) across every layer: driver loop,
   session compositions (mirror/stripe/tier), stager.
2. **The zero-overhead disabled contract** — with no tracer configured
   the driver executes *zero tracer callables* per iteration: a
   counting falsy tracer passed as ``config.tracer`` sees exactly one
   ``__bool__`` normalization and no ``span``/``event`` calls at all.
3. **The acceptance sweep** — every registered solver, run under a
   failure campaign with tracing on, produces a Chrome trace that
   parses as trace-event JSON and agrees with its own report
   (``check_trace_report``).
"""
import json

import pytest

from repro.core import JacobiPreconditioner, make_poisson_problem
from repro.obs import NullTracer, Tracer, check_trace_report
from repro.solvers import (
    SOLVERS,
    FailureCampaign,
    FailureEvent,
    SolveConfig,
    make_backend,
    make_solver,
    solve,
)

# (solver opts, failure iteration): gmres counts restart cycles
SOLVER_CASES = {
    "pcg": ({}, 6),
    "jacobi": ({}, 6),
    "chebyshev": ({}, 6),
    "bicgstab": ({}, 6),
    "gmres": ({"m": 4}, 3),
}
assert set(SOLVER_CASES) == set(SOLVERS)


def _problem(nblocks=4):
    op, b = make_poisson_problem(8, 8, 8, nblocks=nblocks)
    return op, b, JacobiPreconditioner(op)


def _traced_solve(spec, campaign=(), mode="overlap", solver_name="pcg",
                  opts=None, nblocks=4):
    op, b, pre = _problem(nblocks)
    solver = make_solver(solver_name, op, pre, **(opts or {}))
    backend = make_backend(spec, op, solver=solver)
    tracer = Tracer()
    state, report, _ = solve(
        solver, op, b, pre,
        SolveConfig(tol=1e-10, maxiter=5000, persist_mode=mode,
                    tracer=tracer),
        backend=backend, failures=campaign)
    return tracer, report


# ----------------------------------------------------------------------
# 1. Enabled coverage, layer by layer
# ----------------------------------------------------------------------
def test_traced_overlap_solve_emits_driver_and_stager_taxonomy():
    campaign = FailureCampaign((FailureEvent(blocks=(1,), at_iteration=6),))
    tracer, report = _traced_solve("nvm-prd", campaign)
    assert report.converged and report.failures_recovered == 1

    names = set(tracer.names())
    # driver loop
    assert {"solve.begin", "iteration.step", "persist.begin",
            "persist.commit", "failure.inject", "recovery.absorbed",
            "persist.drain", "recovery.fetch", "recovery.reconstruct",
            "recovery.rollback", "solve.end"} <= names
    # stager (the begin/commit cost split of DESIGN.md §6)
    assert {"stage.copy", "stage.flush"} <= names

    counts = tracer.counts()
    assert counts["solve.begin"] == 1 and counts["solve.end"] == 1
    assert counts["iteration.step"] >= report.iterations
    assert counts["persist.commit"] == report.persist_events
    assert counts["recovery.absorbed"] == 1
    # every iteration.step span carries its iteration label
    steps = [r for r in tracer.records if r["name"] == "iteration.step"]
    assert all(isinstance(r["args"]["k"], int) for r in steps)
    # the commit is a span: its iteration label and its wall time
    commit = next(r for r in tracer.records if r["name"] == "persist.commit")
    assert commit["type"] == "span" and "k" in commit["args"]
    assert commit["dur"] >= 0.0


def test_traced_replicated_session_emits_mirror_events():
    campaign = FailureCampaign((
        FailureEvent(blocks=(), at_iteration=4, prd=True),
        FailureEvent(blocks=(1,), at_iteration=7),
    ))
    tracer, report = _traced_solve("replicated(nvm-prd x2)", campaign)
    assert report.converged and report.storage_failures == 1

    counts = tracer.counts()
    # both mirrors commit per persistence event until one dies
    assert counts["mirror.commit"] > report.persist_events
    fetches = [r for r in tracer.records if r["name"] == "mirror.fetch"]
    assert fetches, "the recovery fetch must name its serving mirror"
    assert all({"mirror", "served"} <= set(r["args"]) for r in fetches)
    assert counts["storage.kill"] == 1
    check_trace_report(tracer, report)


def test_traced_erasure_session_emits_stripe_taxonomy():
    campaign = FailureCampaign((
        FailureEvent(blocks=(), at_iteration=4, prd=True),
        FailureEvent(blocks=(1, 2), at_iteration=7),
    ))
    tracer, report = _traced_solve("erasure(nvm-prd x4+p)", campaign)
    assert report.converged and report.failures_recovered == 1

    names = set(tracer.names())
    assert {"gf256.rs_encode", "stripe.write", "stripe.degraded",
            "gf256.rs_decode"} <= names
    # one stripe.write per child per committed stripe: shards labeled
    writes = [r for r in tracer.records if r["name"] == "stripe.write"]
    assert all({"child", "shard", "parity", "rot"} <= set(r["args"])
               for r in writes)
    assert any(r["args"]["parity"] for r in writes), "parity shards traced"
    degraded = next(r for r in tracer.records
                    if r["name"] == "stripe.degraded")
    assert degraded["args"]["missing"] and degraded["args"]["nparity"] == 1
    check_trace_report(tracer, report)


def test_traced_tiered_session_reaches_the_inner_stager():
    campaign = FailureCampaign((FailureEvent(blocks=(2,), at_iteration=5),))
    tracer, report = _traced_solve("tiered(nvm-homogeneous)", campaign)
    assert report.converged
    names = set(tracer.names())
    assert {"stage.copy", "stage.flush", "persist.commit",
            "recovery.fetch"} <= names
    check_trace_report(tracer, report)


def test_sync_mode_is_traced_too():
    tracer, report = _traced_solve("nvm-prd", mode="sync")
    names = set(tracer.names())
    assert {"solve.begin", "iteration.step", "persist.commit",
            "solve.end"} <= names
    # the sync write-through path is the session's persist() call
    assert "backend.write" in names
    # sync bypasses staging: no overlap begin/flush split
    assert "persist.begin" not in names
    check_trace_report(tracer, report)


# ----------------------------------------------------------------------
# 2. The zero-overhead disabled contract
# ----------------------------------------------------------------------
class _CountingNullTracer(NullTracer):
    """Falsy (disabled) tracer that records every callable invocation —
    the probe for the zero-callable guarantee."""

    def __init__(self):
        self.bool_calls = 0
        self.span_calls = 0
        self.event_calls = 0

    def __bool__(self):
        self.bool_calls += 1
        return False

    def span(self, name, **labels):
        self.span_calls += 1
        return super().span(name, **labels)

    def event(self, name, **labels):
        self.event_calls += 1
        return None


def test_disabled_tracer_sees_zero_callables():
    op, b, pre = _problem()
    solver = make_solver("pcg", op, pre)
    backend = make_backend("replicated(nvm-prd x2)", op, solver=solver)
    probe = _CountingNullTracer()
    _, report, _ = solve(
        solver, op, b, pre,
        SolveConfig(tol=1e-10, maxiter=5000, persist_mode="overlap",
                    tracer=probe),
        backend=backend,
        failures=[FailureEvent(blocks=(1,), at_iteration=6)])
    assert report.converged and report.iterations > 10
    # one truthiness normalization (`config.tracer or None`), then the
    # identity guards keep every span/event call off the hot path
    assert probe.span_calls == 0
    assert probe.event_calls == 0
    assert probe.bool_calls == 1


def test_disabled_and_absent_tracer_produce_identical_reports():
    def run(tracer):
        op, b, pre = _problem()
        solver = make_solver("pcg", op, pre)
        backend = make_backend("nvm-prd", op, solver=solver)
        _, report, _ = solve(
            solver, op, b, pre,
            SolveConfig(tol=1e-10, maxiter=5000, persist_mode="overlap",
                        tracer=tracer),
            backend=backend,
            failures=[FailureEvent(blocks=(1,), at_iteration=6)])
        return report

    none_rep = run(None)
    null_rep = run(NullTracer())
    traced_rep = run(Tracer())
    for field in ("iterations", "converged", "persist_events",
                  "persist_aborts", "failures_recovered",
                  "wasted_iterations", "final_relres"):
        assert getattr(null_rep, field) == getattr(none_rep, field), field
        assert getattr(traced_rep, field) == getattr(none_rep, field), field


# ----------------------------------------------------------------------
# 3. The acceptance sweep: every solver, traced, Perfetto-loadable
# ----------------------------------------------------------------------
@pytest.mark.parametrize("solver_name", sorted(SOLVERS))
def test_solver_sweep_produces_valid_chrome_trace(solver_name, tmp_path):
    opts, fail_at = SOLVER_CASES[solver_name]
    campaign = FailureCampaign((
        FailureEvent(blocks=(1,), at_iteration=fail_at),))
    tracer, report = _traced_solve("replicated(nvm-prd x2)", campaign,
                                   solver_name=solver_name, opts=opts)
    assert report.converged and report.failures_recovered == 1
    check_trace_report(tracer, report)

    path = tmp_path / f"trace_{solver_name}.json"
    n = tracer.to_chrome(path)
    doc = json.loads(path.read_text())  # strict JSON: Perfetto-loadable
    events = doc["traceEvents"]
    assert len(events) == n > 0
    assert {e["ph"] for e in events} <= {"X", "i"}
    assert all(e["ts"] >= 0 for e in events)
    spans = [e for e in events if e["ph"] == "X"]
    assert spans and all(e["dur"] >= 0 for e in spans)
    assert {"solve.begin", "iteration.step", "recovery.fetch",
            "solve.end"} <= {e["name"] for e in events}
