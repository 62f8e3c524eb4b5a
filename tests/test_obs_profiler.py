"""Program spans on the profiler's clock, and the span taxonomy of the
host work in every iteration (docs/observability.md §2).

Each span opens a ``jax.profiler.TraceAnnotation`` of its own name, so
a profiled solve shows every span record as a host-plane event of the
same name, on the profiler's clock beside the device's operations.
"""
import glob

import jax
import pytest

from repro.core import JacobiPreconditioner, make_poisson_problem
from repro.obs import Tracer, check_trace_report
from repro.solvers import (FailureCampaign, FailureEvent, SolveConfig,
                           make_backend, make_solver, solve)

HOST_SPANS = ("solve.residual", "persist.pull", "persist.begin",
              "persist.commit", "persist.drain", "stage.copy",
              "stage.flush", "stage.drain")


def _solve(spec="nvm-prd", mode="overlap", campaign=(), tracer=None,
           maxiter=5000):
    op, b = make_poisson_problem(8, 8, 8, nblocks=4)
    pre = JacobiPreconditioner(op)
    solver = make_solver("pcg", op, pre)
    backend = make_backend(spec, op, solver=solver)
    tracer = Tracer() if tracer is None else tracer
    _, report, _ = solve(solver, op, b, pre,
                         SolveConfig(tol=1e-10, maxiter=maxiter,
                                     persist_mode=mode, tracer=tracer),
                         backend=backend, failures=campaign)
    return tracer, report


def _host_events(path):
    """(start ns, duration ns, name) of every host-plane event."""
    data = jax.profiler.ProfileData.from_file(path)
    return [(float(e.start_ns), float(e.duration_ns), e.name)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_every_span_is_on_the_profiler_host_plane(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    campaign = FailureCampaign((FailureEvent(blocks=(1,), at_iteration=6),))
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        tracer, report = _solve(campaign=campaign, maxiter=12)
    assert report.failures_recovered == 1
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)

    spans = sorted((r for r in tracer.records if r["type"] == "span"),
                   key=lambda r: r["ts"])
    names = {r["name"] for r in spans}
    assert set(HOST_SPANS) - {"stage.drain"} <= names
    events = sorted((e for e in _host_events(path) if e[2] in names),
                    key=lambda e: e[0])
    assert [e[2] for e in events] == [r["name"] for r in spans]
    for rec, (_, dur_ns, _) in zip(spans, events):
        diff = abs(dur_ns * 1e-9 - rec["dur"])
        assert diff <= max(0.05 * rec["dur"], 200e-6), (rec, dur_ns)


def test_the_host_work_of_each_iteration_is_spanned():
    campaign = FailureCampaign((FailureEvent(blocks=(2,), at_iteration=5),))
    tracer, report = _solve(campaign=campaign)
    assert report.converged and report.failures_recovered == 1
    counts = tracer.counts()
    # one residual check per loop pass, and one more at exit
    assert counts["solve.residual"] == len(report.residual_history) + 1
    assert counts["persist.pull"] == counts["persist.begin"]
    kinds = {r["name"]: r["type"] for r in tracer.records}
    for name in HOST_SPANS:
        if name in kinds:
            assert kinds[name] == "span", name
    assert {"persist.commit", "stage.copy", "stage.flush",
            "persist.drain"} <= set(kinds)
    # each persist.begin encloses its persist.pull (spans are recorded
    # at close, so the pull's record comes first)
    pulls = [r for r in tracer.records if r["name"] == "persist.pull"]
    begins = [r for r in tracer.records if r["name"] == "persist.begin"]
    for pull, begin in zip(pulls, begins):
        assert pull["args"]["k"] == begin["args"]["k"]
        assert begin["ts"] <= pull["ts"]
        assert pull["ts"] + pull["dur"] <= begin["ts"] + begin["dur"]
        assert pull["depth"] == begin["depth"] + 1
    check_trace_report(tracer, report)


@pytest.mark.parametrize("spec", ["nvm-prd", "erasure(nvm-prd x4+p)",
                                  "replicated(nvm-prd x2)"])
def test_trace_records_carry_wall_time_not_modeled_seconds(spec):
    tracer, report = _solve(spec=spec)
    modeled = {"cost_s", "stage_s", "hidden_s", "exposed_s"}
    assert all(not modeled & set(r["args"]) for r in tracer.records)
    # the modeled seconds stay in the report and its registry
    assert report.persist_cost_s > 0 and report.persist_stage_s > 0
    check_trace_report(tracer, report)


def test_the_sync_persist_pulls_then_commits():
    tracer, report = _solve(mode="sync")
    counts = tracer.counts()
    assert counts["persist.pull"] == counts["persist.commit"] \
        == report.persist_events
    assert "persist.begin" not in counts
    check_trace_report(tracer, report)


def test_without_jax_spans_record_and_annotate_nothing(monkeypatch):
    import sys

    monkeypatch.setitem(sys.modules, "jax.profiler", None)  # import fails
    tracer = Tracer()
    assert tracer._annotation is None
    with tracer.span("persist.commit", k=3, nbytes=8) as span:
        pass
    (rec,) = tracer.records
    assert rec["args"] == {"k": 3, "nbytes": 8} and rec["dur"] >= 0.0
    assert span._ann is None


def test_api_solve_spans_building_the_backend():
    from repro import api

    tracer = Tracer()
    api.solve(api.Problem.poisson(6, nblocks=2),
              api.SolverSpec("pcg", tol=1e-8),
              api.ResilienceSpec("nvm-prd", persist_mode="overlap"),
              tracer=tracer)
    (build,) = [r for r in tracer.records if r["name"] == "solve.build"]
    begin = next(r for r in tracer.records if r["name"] == "solve.begin")
    assert build["type"] == "span" and build["args"]["backend"] == "nvm-prd"
    assert build["ts"] + build["dur"] <= begin["ts"]
