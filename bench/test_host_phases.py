"""The per-iteration host-phase readers on hand-built runs, and the
program's own recovery clock against the harness's reading of it."""
import pytest

from bench import spec
from bench.run import RunView, step_gaps
from bench.trace_reduce import DeviceTrace

READERS = ("residual_check_ms", "persist_pull_ms", "persist_stage_ms",
           "persist_commit_ms", "host_unspanned_ms")
MS = 1e6  # ns


def _span(name, ts, dur):
    return {"type": "span", "name": name, "ts": ts, "dur": dur, "depth": 0,
            "args": {}}


RECORDS = [
    _span("solve.residual", 0.0, 0.002),
    _span("persist.pull", 0.0021, 0.001),
    _span("persist.begin", 0.002, 0.0015),
    _span("persist.commit", 0.004, 0.002),
    {"type": "event", "name": "failure.inject", "ts": 0.0065, "depth": 0,
     "args": {"blocks": [1]}},
    _span("solve.residual", 0.007, 0.003),
    _span("persist.pull", 0.0071, 0.001),
    _span("persist.begin", 0.0070, 0.0015),
]

#: the same spans on the profiler's clock (ns), a pull event inside one,
#: and the last residual spilling past the 10 ms window
HOST = [(0.0, 2 * MS, "solve.residual"),
        (2 * MS, 3.5 * MS, "persist.begin"),
        (2.1 * MS, 3.1 * MS, "persist.pull"),
        (2.2 * MS, 2.9 * MS, "np.asarray_jax.Array_"),
        (4 * MS, 6 * MS, "persist.commit"),
        (6.5 * MS, 6.5 * MS, "failure.inject"),
        (7 * MS, 10.5 * MS, "solve.residual")]


def _run(records=RECORDS, host=HOST, ndevices=1, iterations=2):
    trace = DeviceTrace(window=(0.0, 10 * MS),
                        ops=[[(1 * MS, 1.5 * MS, "fusion")]] * ndevices,
                        modules=[[]] * ndevices, host=list(host))
    return RunView(trace=trace, records=records, config={},
                   iterations=iterations, n=64, nblocks=8, itemsize=8,
                   device_kind="TPU v5 lite", recoveries=0)


def _read(name, run):
    return spec.optional(spec.load_reader(name)(run))


def test_span_readers_sum_wall_time_per_window_iteration():
    run = _run()
    assert _read("residual_check_ms", run) == pytest.approx(2.5)
    assert _read("persist_pull_ms", run) == pytest.approx(1.0)
    assert _read("persist_stage_ms", run) == pytest.approx(0.5)
    assert _read("persist_commit_ms", run) == pytest.approx(1.0)


def test_host_unspanned_is_the_window_less_the_union_of_spans():
    # covered: 0-2, 2-3.5, 4-6, 7-10 ms of the 10 ms window; the pull
    # nests in its begin, the profiler's own pull event and the instant
    # failure.inject count for nothing
    assert _read("host_unspanned_ms", _run()) == pytest.approx(1.5 / 2)


def test_host_unspanned_reads_none_when_no_span_reached_the_host_plane():
    host = [h for h in HOST if h[2] == "np.asarray_jax.Array_"]
    assert _read("host_unspanned_ms", _run(host=host)) is None
    # the span readers still read the program's own records
    assert _read("residual_check_ms", _run(host=host)) == pytest.approx(2.5)


def test_a_program_without_the_spans_reads_nothing():
    # a program whose persist records are instant events and that has
    # no residual span: only iteration.step is a span, off the host plane
    records = [{"type": "event", "name": "persist.begin", "ts": 0.0,
                "depth": 0, "args": {}},
               {"type": "event", "name": "persist.commit", "ts": 0.001,
                "depth": 0, "args": {}},
               _span("iteration.step", 0.002, 0.0001)]
    run = _run(records=records)
    assert all(_read(name, run) is None for name in READERS)


def test_a_host_only_trace_reads_nothing():
    run = _run(ndevices=0)
    assert all(_read(name, run) is None for name in READERS)


def test_the_recovery_wall_clock_matches_the_harness_step_gaps():
    from repro.core import JacobiPreconditioner, make_poisson_problem
    from repro.obs import Tracer
    from repro.solvers import (FailureCampaign, FailureEvent, SolveConfig,
                               make_backend, make_solver, solve)

    op, b = make_poisson_problem(8, 8, 8, nblocks=4)
    pre = JacobiPreconditioner(op)
    solver = make_solver("pcg", op, pre)
    backend = make_backend("replicated(nvm-prd x2)", op, solver=solver)
    tracer = Tracer()
    # a storage-only loss between two block failures: no recovery
    campaign = FailureCampaign((FailureEvent(blocks=(1,), at_iteration=4),
                                FailureEvent(blocks=(), at_iteration=6,
                                             prd=True),
                                FailureEvent(blocks=(2,), at_iteration=9)))
    _, report, _ = solve(solver, op, b, pre,
                         SolveConfig(tol=1e-10, maxiter=5000,
                                     persist_mode="overlap", tracer=tracer),
                         backend=backend, failures=campaign)
    _, recovery = step_gaps(tracer.records)
    assert len(recovery) == 2 and report.failures_recovered == 2
    hist = report.metrics.histogram("recovery.wall_s", phase="recovery")
    assert hist.count == 2
    for mine, harness in zip(hist.values, recovery):
        assert mine == pytest.approx(harness, abs=1e-3)
    assert report.recovery_wall_s == pytest.approx(sum(recovery), abs=2e-3)
    assert report.recovery_wall_s == hist.total
