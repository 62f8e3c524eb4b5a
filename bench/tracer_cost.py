#!/usr/bin/env python3
"""What the program's tracer costs per iteration with the profiler off.

    python3 bench/tracer_cost.py --workload pcg1g-nvmprd.kill [--pairs 6]
        [--seed N] [--iterations N] [--rehearse-size] [--rehearse]

The cell's window as ``bench/run.py`` runs it (its deployment, its fixed
work of ``run_seconds x iterations_per_second`` iterations, its failure
campaign, the same warm-up), solved ``--pairs`` times with
``tracer=None`` and ``--pairs`` times with a ``repro.obs.Tracer``, in
alternating order within one process.  Each window is timed on the host
clock to ``block_until_ready``, as ``iter_ms`` is.  Reported, per
iteration: each window's time, the traced-minus-untraced difference of
each pair and their median, and the untraced windows' quartile spread
(``statistics.quantiles(n=4)``), which a difference has to exceed to be
resolved.  Besides: the span and event calls of the last traced window
replayed on a fresh ``Tracer`` (profiler annotations included), the
tracer's own time without the work it wraps.

``--iterations`` replaces the fixed work (the failure stays at its
middle); ``--rehearse-size`` runs the cell's block count on the
rehearsal's tiny plane; ``--rehearse`` does that on the host CPU, a
rehearsal of this script that measures nothing.  The last line of
standard output is one JSON object.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def replay_calls(records):
    """The tracer calls that made ``records``, in call order: ("open",
    name, args), ("close",) and ("event", name, args)."""
    marks = []
    for i, r in enumerate(records):
        if r["type"] == "span":
            marks.append((r["ts"], 0, -r["depth"], i,
                          ("open", r["name"], r["args"])))
            marks.append((r["ts"] + r["dur"], 1, r["depth"], i, ("close",)))
        else:
            marks.append((r["ts"], 2, 0, i, ("event", r["name"], r["args"])))
    return [m[-1] for m in sorted(marks)]


def replay_seconds(calls, repeats, tracer_cls):
    """Seconds to make ``calls`` on a fresh tracer, best of ``repeats``."""
    best = float("inf")
    for _ in range(repeats):
        tracer = tracer_cls()
        stack = []
        t0 = time.perf_counter()
        for call in calls:
            if call[0] == "open":
                span = tracer.span(call[1], **call[2])
                span.__enter__()
                stack.append(span)
            elif call[0] == "close":
                stack.pop().__exit__(None, None, None)
            else:
                tracer.event(call[1], **call[2])
        best = min(best, time.perf_counter() - t0)
    return best


def quartile_spread(values):
    """(q1, q3) of ``values`` as ``statistics.quantiles(n=4)`` gives
    them; a single value is its own quartiles."""
    if len(values) < 2:
        return values[0], values[0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q1, q3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--pairs", type=int, default=6)
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    ap.add_argument("--iterations", type=int, default=None)
    ap.add_argument("--rehearse-size", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench import spec
    from bench.run import (MIN_ITERATIONS, REHEARSAL_PLANE,
                           WARMUP_ITERATIONS, build_problem, campaign,
                           data_mesh, draw_rhs, rehearse_on_host)

    cell = spec.load_cell(args.workload)
    cfg = cell.config
    if args.rehearse:
        rehearse_on_host(cell.chips)
    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("tracer_cost: no TPU (--rehearse runs on the host CPU)",
              file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", True)
    from repro import api
    from repro.launch.cache import enable_compile_cache
    from repro.obs import Tracer

    enable_compile_cache()
    small = args.rehearse or args.rehearse_size
    grid = (cfg["nz"],) + (REHEARSAL_PLANE if small
                           else (cfg["ny"], cfg["nx"]))
    n = grid[0] * grid[1] * grid[2]
    rng = np.random.default_rng(args.seed)
    rhs_key = int(rng.integers(2**31))
    blocks = spec.draw_blocks(cell.traffic, cfg["nblocks"], rng, cell.chips)
    mesh = data_mesh(cell.chips)
    problem, resilience = build_problem(
        cfg, grid, draw_rhs(rhs_key, n, mesh), mesh)

    def timed(iterations, events, tracer):
        failures = campaign(events)
        gc.collect()  # each window starts as run.py's does
        t0 = time.perf_counter()
        res = api.solve(problem, api.SolverSpec(cfg["solver"], tol=0.0,
                                                maxiter=iterations),
                        resilience, failures=failures, tracer=tracer)
        jax.block_until_ready(res.state)
        return time.perf_counter() - t0

    warm = spec.warmup_events(cell.traffic, blocks)
    timed(WARMUP_ITERATIONS, warm, None)
    timed(WARMUP_ITERATIONS, warm, Tracer())
    seconds = spec.load_benchmark()["run_seconds"]
    it = args.iterations or max(
        MIN_ITERATIONS, int(round(seconds * cfg["iterations_per_second"])))
    events = spec.failure_events(cell.traffic, it, blocks)
    plain, traced, tracer = [], [], None
    for i in range(args.pairs):
        for mode in ((None, "t") if i % 2 == 0 else ("t", None)):
            if mode is None:
                plain.append(timed(it, events, None))
            else:
                tracer = Tracer()
                traced.append(timed(it, events, tracer))
    calls = replay_calls(tracer.records)
    calls_s = replay_seconds(calls, 20, Tracer)
    diffs_us = [1e6 * (t - p) / it for t, p in zip(traced, plain)]
    plain_ms = [1e3 * t / it for t in plain]
    q1, q3 = quartile_spread(plain_ms)
    median_diff_us = statistics.median(diffs_us)
    out = {
        "workload": args.workload, "grid": grid, "iterations": it,
        "events": [(e.at_iteration, e.blocks, e.shard, e.storage)
                   for e in events],
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "plain_ms_per_iter": plain_ms,
        "traced_ms_per_iter": [1e3 * t / it for t in traced],
        "pair_diff_us_per_iter": diffs_us,
        "median_diff_us_per_iter": median_diff_us,
        "traced_slower_pairs": sum(d > 0 for d in diffs_us),
        "plain_quartile_spread_us_per_iter": 1e3 * (q3 - q1),
        "plain_quartile_spread_share": (q3 - q1) / statistics.median(
            plain_ms),
        "resolved": abs(median_diff_us) > 1e3 * (q3 - q1),
        "records_per_iter": len(tracer.records) / it,
        "tracer_calls_us_per_iter": 1e6 * calls_s / it,
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
