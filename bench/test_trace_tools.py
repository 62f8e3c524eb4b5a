"""The two trace tools beside the harness: ``span_clock`` (program spans
against their profiler host-plane events) and ``tracer_cost`` (the
tracer's cost per iteration, profiler off)."""
import json
import os
import subprocess
import sys

import pytest

from bench import run, span_clock, spec, tracer_cost
from bench.trace_reduce import DeviceTrace


def _span(name, ts, dur, depth=0, **args):
    return {"type": "span", "name": name, "ts": ts, "dur": dur,
            "depth": depth, "args": args}


def _event(name, ts, **args):
    return {"type": "event", "name": name, "ts": ts, "args": args}


RECORDS = [
    # recorded at close: the child before its parent
    _span("persist.pull", 0.0011, 0.0004, depth=1, k=0),
    _span("persist.begin", 0.0010, 0.0010, k=0),
    _event("failure.inject", 0.0030, k=1),
    _span("solve.residual", 0.0040, 0.0020, k=1),
]
# the same spans on the profiler's host plane (ns), 5 us later, plus an
# event of the runtime that no span names
HOST = [(1_005_000 + 1e9 * r["ts"], 1_005_000 + 1e9 * (r["ts"] + r["dur"]),
         r["name"]) for r in RECORDS if r["type"] == "span"]
HOST.append((1_000_000, 9_000_000, "np.asarray(jax.Array)"))


def test_match_spans_pairs_each_span_with_its_host_event():
    pairs = span_clock.match_spans(RECORDS, HOST)
    assert [r["name"] for r, _ in pairs] == \
        ["persist.begin", "persist.pull", "solve.residual"]
    assert all(r["name"] == h[2] for r, h in pairs)


def test_match_spans_refuses_a_missing_host_event():
    with pytest.raises(ValueError, match="3 span records but 2"):
        span_clock.match_spans(RECORDS, HOST[1:])


def test_summarize_reads_offsets_through_the_harness_clock_map():
    dtrace = DeviceTrace(ops=[], host=HOST, window=(1_000_000, 9_000_000))
    # the tracer started 0 s after the window annotation opened
    pairs = span_clock.match_spans(RECORDS, HOST)
    out = span_clock.summarize(pairs, run._clock_map(dtrace, 7.0, 7.0),
                               rel=0.01, abs_us=50.0)
    assert out["spans"] == 3 and out["outside_limit"] == 0
    assert out["max_dur_diff_us"] < 1e-3
    assert out["clock_map_offset_us"]["median"] == pytest.approx(-5.0)


def test_replay_calls_reopens_spans_in_call_order():
    calls = tracer_cost.replay_calls(RECORDS)
    assert calls == [
        ("open", "persist.begin", {"k": 0}),
        ("open", "persist.pull", {"k": 0}),
        ("close",), ("close",),
        ("event", "failure.inject", {"k": 1}),
        ("open", "solve.residual", {"k": 1}), ("close",)]
    from repro.obs import Tracer

    assert tracer_cost.replay_seconds(calls, 2, Tracer) > 0.0


def test_quartile_spread_matches_statistics_quantiles():
    assert tracer_cost.quartile_spread([5.0]) == (5.0, 5.0)
    q1, q3 = tracer_cost.quartile_spread([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    assert (q1, q3) == (1.75, 5.25)


def test_tracer_cost_rehearses_on_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "bench", "tracer_cost.py"),
         "--workload", "pcg1g-nvmprd.kill", "--rehearse", "--pairs", "1",
         "--iterations", "4", "--seed", str(2**31 + 5)],
        capture_output=True, text=True, env=env, timeout=300, cwd=spec.ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    # the kill lands at the window's middle, as in bench/run.py; it kills
    # a block, not a shard
    (at, blocks, shard, storage), = out["events"]
    assert out["iterations"] == 4 and at == 2 and len(blocks) == 1
    assert shard is None and storage is False
    assert len(out["plain_ms_per_iter"]) == len(out["traced_ms_per_iter"]) \
        == len(out["pair_diff_us_per_iter"]) == 1
    assert out["records_per_iter"] > 1 and out["tracer_calls_us_per_iter"] > 0
    assert out["device"]["platform"] == "cpu"
