#!/usr/bin/env python3
"""Run one benchmark cell of the recoverable PCG solve on the chip.

    python3 bench/run.py --workload pcg1g-nvmprd.kill --seed 7 \
        --seconds 30 --trace 0

One run is one ``api.solve`` of the cell's deployment with its failure
campaign (the window).  Set-up draws the right-hand side on the device
from the seed, then runs a short warm-up solve with the cell's own
resilience spec and one failure of each kind the window will see, on
the same blocks or shard, so the window compiles nothing.  A cell of
more than one chip lays the problem over that many devices, one shard
each (``Problem.with_shards`` over the configuration's ``chips``, which
``ResilienceSpec(nshards=...)`` pins), and draws the right-hand side
straight into that layout; its traffic may kill a whole shard
(``"unit": "shard"``).  A cell reads the per-layer metrics whose
``workloads`` list names it, a ``<metric>.<variant>`` through
``<metric>``'s reader (:mod:`bench.spec`).  The window's work is
fixed: ``N = --seconds x iterations_per_second`` of the configuration
(its pace on the chip when the cell was defined), and the failures land
at their fractions of ``N``.

After the window the solve's iterate, its report and the persisted
search directions read back from the store are compared with the plain
reference (:mod:`bench.check`).  The last line of standard output is
one JSON object: ``correct``, ``attempted`` (iterations and failures
scheduled), ``failed`` (those not completed), ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, last, ``checks``: each compared number
with its limit, which also close standard error.  ``device`` counts
the cell's devices; ``memory_peak_bytes`` is the fullest one's peak,
``memory_peak_bytes_by_device`` each one's.  Every device reading is
per chip: trace times are averaged over the devices, and the step's
roofline counts one chip's share of the bytes (the stripe encode, which
runs whole on one device, its whole stripe over the kernel's time summed
over the devices).

Without a TPU the run exits 2 and prints no result.  ``--rehearse``
runs the same path on the host CPU at a tiny grid with the same block
count and interpreted kernels, on as many virtual host devices as the
cell has chips; ``--control`` runs the program in float32, one
precision below the configuration's, which the comparison has to find
wrong.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: the rehearsal's grid: the cell's z depth and block count, a tiny plane
REHEARSAL_PLANE = (8, 128)
#: warm-up solve: its failures land at iteration 2, after the first
#: complete recovery point
WARMUP_ITERATIONS = 3
#: the least window: a failure at its middle needs two iterations before
MIN_ITERATIONS = 4
#: fixed directory of the profiler's output inside the checkout
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="host CPU, tiny grid, interpreted kernels")
    ap.add_argument("--control", action="store_true",
                    help="run the program in float32 (must read incorrect)")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the profiler's .xplane.pb here")
    return ap.parse_args(argv)


def say(**fields) -> None:
    print("[bench] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          file=sys.stderr, flush=True)


class CompileCounter:
    """Counts, while ``active``, the programs XLA compiled (each is then
    written to the persistent cache) and those loaded from that cache."""

    MISS = "/jax/compilation_cache/cache_misses"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.active = False
        self.compiled = 0
        self.loaded = 0

    def __call__(self, event, **kwargs):
        if self.active:
            self.compiled += event == self.MISS
            self.loaded += event == self.HIT


class RunView:
    """What a per-layer metric reader sees of one run.  ``n`` and
    ``nblocks`` are the whole problem's, laid over ``chips`` devices;
    the trace's times are per device."""

    def __init__(self, *, trace, records, config, iterations, n, nblocks,
                 itemsize, device_kind, recoveries, chips=1):
        self.trace = trace
        self.records = records
        self.config = config
        self.iterations = iterations
        self.n = n
        self.nblocks = nblocks
        self.itemsize = itemsize
        self.device_kind = device_kind
        self.recoveries = recoveries
        self.chips = chips

    def span_per_recovery(self, name):
        if not self.recoveries:
            return None
        total = sum(r["dur"] for r in self.records
                    if r["type"] == "span" and r["name"] == name)
        return total / self.recoveries


def step_gaps(records):
    """(seconds between consecutive step starts that no failure falls
    in, seconds from each block failure to the next step start), from
    the program's ``iteration.step`` spans and ``failure.inject``
    events."""
    steps = sorted(r["ts"] for r in records
                   if r["type"] == "span" and r["name"] == "iteration.step")
    injects = [r["ts"] for r in records
               if r["name"] == "failure.inject" and r["args"].get("blocks")]
    gaps = [b - a for a, b in zip(steps, steps[1:])
            if not any(a < t < b for t in injects)]
    recovery = [min((s for s in steps if s > t), default=float("nan")) - t
                for t in injects]
    return gaps, recovery


def rehearse_on_host(chips: int) -> None:
    """Point JAX, before it starts, at the host CPU with ``chips``
    virtual devices, so a cell's sharded path runs as it would on its
    chips."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if chips > 1:
        flags = re.sub(r"--xla_force_host_platform_device_count=\S*", "",
                       os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={chips}".strip())


def data_mesh(chips):
    """The 1-D ``data`` mesh a cell of ``chips`` devices lays its
    problem over, or None on one chip."""
    from repro.distributed.sharding import make_data_mesh

    return make_data_mesh(chips) if chips > 1 else None


def draw_rhs(key, n, mesh):
    """The f64 right-hand side of ``n`` unknowns drawn on the device from
    ``key``; on a ``mesh``, straight into the problem's layout, so the
    harness puts no whole vector on one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    layout = ({} if mesh is None else
              {"out_shardings": NamedSharding(mesh, PartitionSpec("data"))})

    @functools.partial(jax.jit, **layout)
    def make_rhs(key):
        return jax.random.normal(jax.random.key(key), (n,), jnp.float64)

    return make_rhs(key)


def build_problem(cfg, grid, b, mesh):
    """The cell's problem with right-hand side ``b``, in ``b``'s
    precision, and its resilience spec.  On a ``mesh`` the problem is
    laid over its devices and the spec pins that layout; without one,
    the unsharded problem and no pin."""
    from repro import api
    from repro.core.poisson import JacobiPreconditioner, StencilOperator

    dtype = b.dtype
    op = StencilOperator(*grid, nblocks=cfg["nblocks"], dtype=dtype)
    problem = api.Problem.from_parts(op, b, JacobiPreconditioner(op))
    nshards = None
    if mesh is not None:
        nshards = mesh.size
        problem = problem.with_shards(nshards, mesh=mesh)
    resilience = api.ResilienceSpec(
        cfg["backend"], persist_mode=cfg["persist_mode"],
        period=cfg["period"], fused_persist=cfg["fused_persist"],
        dtype=dtype, nshards=nshards)
    return problem, resilience


def campaign(events):
    """The program's failure campaign for planned events."""
    from repro import api

    return [api.FailureEvent(blocks=e.blocks, shard=e.shard,
                             at_iteration=e.at_iteration, prd=e.storage)
            for e in events]


def main(argv=None) -> int:
    args = parse_args(argv)
    # libtpu would otherwise log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench import check, spec, trace_reduce

    cell = spec.load_cell(args.workload)
    cfg = cell.config
    if args.rehearse:
        rehearse_on_host(cell.chips)

    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"bench: JAX found no TPU (platform {dev.platform!r}); the "
              f"benchmark measures the chip and has no CPU fallback "
              f"(--rehearse runs a tiny CPU rehearsal)", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2

    jax.config.update("jax_enable_x64", True)
    # every program goes to the persistent cache, so a later run of the
    # cell in this checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro import api
    from repro.launch.cache import enable_compile_cache
    from repro.obs import Tracer

    enable_compile_cache()
    compiles = CompileCounter()
    jax.monitoring.register_event_listener(compiles)

    dtype = np.dtype(np.float32 if args.control else cfg["dtype"])
    grid = (cfg["nz"],) + (REHEARSAL_PLANE if args.rehearse
                           else (cfg["ny"], cfg["nx"]))
    nblocks = cfg["nblocks"]
    n = grid[0] * grid[1] * grid[2]
    rng = np.random.default_rng(args.seed)
    rhs_key = int(rng.integers(2**31))
    blocks = spec.draw_blocks(cell.traffic, nblocks, rng, cell.chips)

    mesh = data_mesh(cell.chips)
    b64 = draw_rhs(rhs_key, n, mesh)
    problem, resilience = build_problem(
        cfg, grid, b64 if dtype == np.float64 else b64.astype(dtype), mesh)

    def solve(iterations, events, tracer):
        return api.solve(problem,
                         api.SolverSpec(cfg["solver"], tol=0.0,
                                        maxiter=iterations),
                         resilience, failures=campaign(events), tracer=tracer)

    # ---- warm-up: every program the window runs, and its pace ----
    warm_tracer = Tracer()
    t0 = time.perf_counter()
    warm = solve(WARMUP_ITERATIONS,
                 spec.warmup_events(cell.traffic, blocks),
                 warm_tracer)
    jax.block_until_ready(warm.state)
    warm_s = time.perf_counter() - t0
    gaps, warm_recovery = step_gaps(warm_tracer.records)
    del warm
    # free the warm-up's buffers now, not in a collection inside the window
    gc.collect()
    iterations = max(MIN_ITERATIONS,
                     int(round(args.seconds * cfg["iterations_per_second"])))
    events = spec.failure_events(cell.traffic, iterations, blocks)
    say(workload=args.workload, seed=args.seed, grid=grid,
        dtype=dtype.name, chips=cell.chips, warmup_s=round(warm_s, 3),
        warmup_step_gaps_s=[round(g, 4) for g in gaps],
        warmup_recovery_s=[round(r, 3) for r in warm_recovery],
        iterations=iterations,
        events=[(e.at_iteration, e.blocks, e.shard, e.storage)
                for e in events])

    # ---- the window ----
    tracer = Tracer()
    tracer_t0 = time.perf_counter()
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    compiles.active = True
    error = None
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        try:
            result = solve(iterations, events, tracer)
            jax.block_until_ready(result.state)
        except Exception:  # the run reports it, incorrect
            error = traceback.format_exc()
            result = None
    window_s = time.perf_counter() - t0
    compiles.active = False
    if args.trace:
        jax.profiler.stop_trace()
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices[:cell.chips]]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(peaks), "memory_peak_bytes": max(peaks),
              "memory_peak_bytes_by_device": peaks}
    block_events = [e for e in events if e.loses_blocks]
    attempted = iterations + len(events)
    say(window_s=round(window_s, 4), compiled_in_window=compiles.compiled,
        loaded_from_cache_in_window=compiles.loaded,
        setup_s=round(setup_s, 3))

    checks = []
    metrics = {}
    breakdown = None
    if error is not None:
        print(error, file=sys.stderr)
        failed = attempted
    else:
        rep = result.report
        x = np.asarray(result.state.x)
        ks = check.durable_pair(rep.iterations, cfg["period"])
        try:
            sets = result.backend.open_session().fetch(
                tuple(range(nblocks)), tuple(ks))
            persisted = {s.k: np.asarray(s.vectors["p"]) for s in sets}
        except Exception:  # an unreadable store is an incorrect run
            print(traceback.format_exc(), file=sys.stderr)
            persisted = {}
        records = tracer.records
        _, recovery = step_gaps(records)
        failed = (max(0, iterations - rep.iterations)
                  + max(0, len(block_events) - rep.failures_recovered))
        del result  # the program's device state, before the reference

        t_ref = time.perf_counter()
        reference = spec.load_reference(cfg["reference"])
        b_host = np.asarray(b64)
        x_ref, p_ref = reference.pcg(b_host, grid, rep.iterations, keep_p=ks)
        true_relres = float(
            np.linalg.norm(b_host - reference.stencil(
                x.astype(np.float64).reshape(grid)).reshape(-1))
            / np.linalg.norm(b_host))
        checks = check.compare(
            cfg["limits"], x=x, x_ref=x_ref, true_relres=true_relres,
            reported_relres=rep.final_relres, persisted=persisted,
            p_ref=p_ref, iterations=rep.iterations,
            planned_iterations=iterations,
            recovered=rep.failures_recovered,
            planned_recoveries=len(block_events),
            storage_losses=rep.storage_failures,
            planned_storage_losses=sum(e.storage for e in events))
        say(reference_s=round(time.perf_counter() - t_ref, 3),
            recovery_s=[round(r, 4) for r in recovery],
            wasted_iterations=rep.wasted_iterations)

        if not args.trace:
            values = {
                "iter_ms": (1e3 * window_s / rep.iterations
                            if rep.iterations else None),
                "recovery_s": (sum(recovery) / len(recovery)
                               if recovery else None),
                "setup_s": setup_s,
            }
            for m in cell.end_to_end:
                v = values.get(m["name"])
                if v is not None and np.isfinite(v):
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            path = trace_reduce.find_xplane(TRACE_DIR)
            dtrace = trace_reduce.load(path) if path else None
            if args.keep_trace and path:
                os.makedirs(args.keep_trace, exist_ok=True)
                stem = os.path.join(args.keep_trace,
                                    f"{args.workload}.{args.seed}")
                shutil.copy(path, stem + ".xplane.pb")
                with open(stem + ".records.json", "w") as f:
                    json.dump({"tracer_t0": tracer_t0, "window_t0": t0,
                               "records": records}, f)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            view = RunView(trace=dtrace, records=records, config=cfg,
                           iterations=rep.iterations, n=n, nblocks=nblocks,
                           itemsize=dtype.itemsize,
                           device_kind=dev.device_kind,
                           recoveries=len(recovery), chips=cell.chips)
            for m in cell.per_layer:
                v = spec.optional(spec.load_reader(m["name"])(view))
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            if dtrace is not None:
                device["busy_s"] = dtrace.busy_s
                device["window_s"] = dtrace.window_s
                to_ns = _clock_map(dtrace, tracer_t0, t0)
                host = dtrace.host + [
                    (to_ns(r["ts"]), to_ns(r["ts"] + r.get("dur", 0.0)),
                     r["name"]) for r in records]
                breakdown = {
                    "device_ops": [[k, v] for k, v in dtrace.top_ops(10)],
                    "idle_gaps": [[k, v] for k, v in trace_reduce.label_gaps(
                        dtrace.idle_gaps(0), host, 10)]
                    if dtrace.ndevices else [],
                }

    correct = error is None and bool(checks) and all(c.ok for c in checks)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = check.as_dict(checks)
    for c in checks:
        print(f"check {c.name}={c.value!r} limit={c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def _clock_map(dtrace, tracer_t0, window_t0):
    """Tracer seconds -> profiler nanoseconds: the window annotation
    opened at ``window_t0`` on the host clock and at ``dtrace.window[0]``
    on the profiler's."""
    offset = dtrace.window[0] + (tracer_t0 - window_t0) * 1e9
    return lambda ts: offset + ts * 1e9


if __name__ == "__main__":
    sys.exit(main())
