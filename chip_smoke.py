#!/usr/bin/env python3
"""Run the recoverable PCG solve on a TPU, through the public entry
points, at the block shape of the repository's own deployment.

``configs/poisson_pcg.py`` ``pcg_1g`` puts a 1024^3 Poisson grid on 512
chips: one z-slab block of 2x1024x1024 unknowns per chip.  This script
puts eight of those blocks on one chip — grid (16, 1024, 1024),
n = 2^24 unknowns in f64, 128 MiB per vector — and checks each phase
against an independent answer::

    python3 chip_smoke.py              # one chip: phases 1-5
    python3 chip_smoke.py --chips 4    # the sharded solve on four chips,
                                       # against the same solve on one
    python3 chip_smoke.py --rehearse   # tiny CPU rehearsal, interpreted
                                       # kernels (no chip needed)

Phases (one chip): 1 device and compile cache; 2 the unprotected
reference solve (``core.pcg.solve_jit``); 3 the recoverable solve with a
block failure and its recovery (``api.solve``); 4 the erasure-coded
persist path with the compiled GF(256) parity kernel, bit-identical to
the numpy encode; 5 the multi-tenant service replaying a seeded trace.

Every phase that fails ends the run with a non-zero exit.  The last
line of standard output is the JSON verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``; everything
else is printed before it.  Without an accelerator (and without
``--rehearse``) the script exits non-zero and prints no verdict.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: the pcg_1g block (2 x 1024 x 1024 unknowns) times eight, on one chip
GRID = (16, 1024, 1024)
NBLOCKS = 8
#: a grid the CPU runs in seconds, same block count
REHEARSAL_GRID = (16, 8, 128)
#: PCG iterations per solve; the failure lands at K // 2
K = 40
#: iterations of each solve on four chips, which bill four times per
#: second: two solves of 20, the shard kill at 10
SHARDED_K = 20
#: phase 3 bounds, set from the chip's emulated f64 (CHANGES.md)
X_REL_BOUND = 1e-8
RELRES_AGREEMENT = 1e-6


def _args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases 1-5; 4: the sharded solve only")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the host CPU at a tiny size")
    return ap.parse_args()


ARGS = _args()
if ARGS.rehearse:
    # must precede the jax import
    os.environ["JAX_PLATFORMS"] = "cpu"
    if ARGS.chips > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={ARGS.chips}")
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_enable_x64", True)

from repro import api  # noqa: E402
from repro.core import reconstruction  # noqa: E402
from repro.core.pcg import solve_jit  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.obs import Tracer  # noqa: E402


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def numpy_stencil(x: np.ndarray, grid) -> np.ndarray:
    """``A x`` for the 7-point Dirichlet stencil, in numpy f64 on the
    host: independent of the code under test."""
    u = x.reshape(grid)
    p = np.pad(u, 1)
    out = 6.0 * u
    out -= p[:-2, 1:-1, 1:-1]
    out -= p[2:, 1:-1, 1:-1]
    out -= p[1:-1, :-2, 1:-1]
    out -= p[1:-1, 2:, 1:-1]
    out -= p[1:-1, 1:-1, :-2]
    out -= p[1:-1, 1:-1, 2:]
    return out.reshape(-1)


def step_timings(tracer: Tracer):
    """(compile s, steady s/iteration, recovery s) from the driver's
    spans: the first ``iteration.step`` holds the trace and compile;
    steady time is the median gap between step starts that no failure
    falls in; recovery runs from ``failure.inject`` to the next step."""
    steps = [r for r in tracer.records
             if r["type"] == "span" and r["name"] == "iteration.step"]
    steps.sort(key=lambda r: r["ts"])
    injects = [r["ts"] for r in tracer.records
               if r["name"] == "failure.inject"]
    gaps = []
    for a, b in zip(steps[1:], steps[2:]):
        if not any(a["ts"] < t < b["ts"] for t in injects):
            gaps.append(b["ts"] - a["ts"])
    recovery = [min(s["ts"] for s in steps if s["ts"] > t) - t
                for t in injects]
    return steps[0]["dur"], statistics.median(gaps), recovery


def phase_device():
    devices = jax.devices()
    dev = devices[0]
    cache = enable_compile_cache()
    say("1 device", jax=jax.__version__, platform=dev.platform,
        kind=repr(dev.device_kind), count=len(devices), cache=cache)
    if dev.platform != "tpu" and not ARGS.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this script measures the chip and has no CPU fallback "
              f"(--rehearse runs a tiny CPU rehearsal)", file=sys.stderr)
        sys.exit(2)
    check(len(devices) >= ARGS.chips,
          f"--chips {ARGS.chips} but JAX sees {len(devices)} device(s)")
    return dev, len(devices)


def phase_reference(problem):
    op, pre, b = problem.op, problem.precond, problem.b
    t0 = time.perf_counter()
    x_ref, k_ref = solve_jit(op.apply, pre.apply, b, tol=0.0, maxiter=K)
    x_ref = np.asarray(x_ref)
    wall = time.perf_counter() - t0
    check(int(k_ref) == K, f"reference ran {int(k_ref)} iterations, not {K}")
    check(bool(np.all(np.isfinite(x_ref))), "reference x is not finite")
    say("2 reference", n=op.n, iterations=int(k_ref),
        seconds_incl_compile=wall)
    return x_ref


def phase_recoverable(problem, x_ref, dev, grid):
    tracer = Tracer()
    t0 = time.perf_counter()
    res = api.solve(
        problem, api.SolverSpec("pcg", tol=0.0, maxiter=K),
        api.ResilienceSpec("nvm-prd", persist_mode="overlap"),
        failures=[api.FailureEvent(blocks=(3,), at_iteration=K // 2)],
        tracer=tracer)
    x = res.x
    wall = time.perf_counter() - t0
    rep = res.report
    check(rep.iterations == K, f"recoverable solve ran {rep.iterations}")
    check(rep.failures_recovered == 1,
          f"failures_recovered={rep.failures_recovered}, expected 1")
    check(bool(np.all(np.isfinite(x))), "recovered x is not finite")
    x_rel = float(np.linalg.norm(x - x_ref) / np.linalg.norm(x_ref))
    b = np.asarray(problem.b)
    true_relres = float(np.linalg.norm(b - numpy_stencil(x, grid))
                        / np.linalg.norm(b))
    agreement = abs(true_relres - rep.final_relres) / rep.final_relres
    compile_s, steady_s, recovery_s = step_timings(tracer)
    local = dict(reconstruction.last_local_cg)
    stats = dev.memory_stats() or {}
    say("3 recoverable", seconds=wall, iterations=rep.iterations,
        failures_recovered=rep.failures_recovered,
        x_vs_reference_rel=x_rel, final_relres=rep.final_relres,
        true_relres_numpy=true_relres, relres_agreement=agreement,
        compile_s=compile_s, steady_s_per_iter=steady_s,
        recovery_s=recovery_s,
        local_cg_iterations=local.get("iterations"),
        local_cg_maxiter=local.get("maxiter"),
        host_pull_bytes_per_iter=2 * problem.op.n * 8,
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"))
    check(x_rel < X_REL_BOUND,
          f"||x - x_ref|| / ||x_ref|| = {x_rel} >= {X_REL_BOUND}")
    check(agreement < RELRES_AGREEMENT,
          f"true relres {true_relres} vs reported {rep.final_relres}")
    check(local.get("iterations", 0) < local.get("maxiter", 0),
          f"replacement-node CG ran to its cap: {local}")


def phase_erasure(problem):
    from repro.kernels import gf256_encode, ops

    camp = api.FailureCampaign((
        api.FailureEvent(blocks=(1,), at_iteration=3, prd=True),
        api.FailureEvent(blocks=(6,), at_iteration=7),
    ))
    runs = {}
    for fused in (False, True):
        tracer = Tracer()
        before = gf256_encode._encode_tiles._cache_size()
        t0 = time.perf_counter()
        res = api.solve(
            problem, api.SolverSpec("pcg", tol=0.0, maxiter=10),
            api.ResilienceSpec("erasure(nvm-prd x6+2p)",
                               persist_mode="overlap", fused_persist=fused),
            failures=camp, tracer=tracer)
        x = res.x
        wall = time.perf_counter() - t0
        encoders = [r["args"]["encoder"] for r in tracer.records
                    if r["name"] == "gf256.rs_encode"]
        runs[fused] = (res, x, encoders,
                       gf256_encode._encode_tiles._cache_size() - before)
        say("4 erasure", fused_persist=fused, seconds=wall,
            iterations=res.report.iterations,
            failures_recovered=res.report.failures_recovered,
            storage_failures=res.report.storage_failures,
            encodes=len(encoders), encoders=sorted(set(encoders)))
    (ref, x_ref, enc_ref, new_ref), (fus, x_fus, enc_fus, new_fus) = \
        runs[False], runs[True]
    for res in (ref, fus):
        check(res.report.failures_recovered == 2,
              f"failures_recovered={res.report.failures_recovered}")
        check(res.report.storage_failures == 1,
              f"storage_failures={res.report.storage_failures}")
    check(x_ref.tobytes() == x_fus.tobytes(),
          "fused-persist solve is not bit-identical to the numpy encode")
    check(np.asarray(ref.state.r).tobytes()
          == np.asarray(fus.state.r).tobytes(), "residuals differ")
    check(enc_ref and set(enc_ref) == {"ref"}, f"numpy run: {enc_ref}")
    check(enc_fus and set(enc_fus) == {"pallas"}, f"fused run: {enc_fus}")
    check(new_ref == 0 and new_fus >= 1,
          f"kernel compiles: numpy run {new_ref}, fused run {new_fus}")
    # the kernel the fused run called, at the shape it called it with,
    # lowers to a Mosaic custom call unless it was interpreted
    be = fus.backend
    nbytes = be.nblocks * be.chunk * 8
    tile = gf256_encode.DEFAULT_BM * gf256_encode.LANES * 4
    rows = max(tile, -(-nbytes // tile) * tile) // (4 * gf256_encode.LANES)
    interpret = not ops._on_tpu()
    hlo = gf256_encode._encode_tiles.lower(
        jax.ShapeDtypeStruct((be.k_data, rows, gf256_encode.LANES),
                             jnp.uint32),
        nparity=2, bm=gf256_encode.DEFAULT_BM,
        interpret=interpret).compile().as_text()
    compiled_kernel = "tpu_custom_call" in hlo
    say("4 erasure", bit_identical=True, kernel_interpret=interpret,
        kernel_is_tpu_custom_call=compiled_kernel)
    if not ARGS.rehearse:
        check(compiled_kernel and not interpret,
              "the GF(256) encode did not run as a compiled TPU kernel")


def phase_service():
    reqs = api.generate_request_trace(0, nrequests=6, failure_rate=0.6,
                                      survivable_only=True)
    svc = api.SolveService(api.ServiceConfig(lanes=4, max_queue=8))
    t0 = time.perf_counter()
    tickets = svc.replay(reqs)
    wall = time.perf_counter() - t0
    accepted = 0
    for req in reqs:
        ticket = tickets[req.tenant]
        if not ticket.accepted:
            say("5 service", tenant=req.tenant, rejected=ticket.reason)
            continue
        accepted += 1
        rep = ticket.result.report
        # a trace event fires unless the tenant converged before it
        fired = [ev for ev in req.failures
                 if ev.at_iteration < rep.iterations]
        want_prd = sum(1 for ev in fired if ev.prd)
        say("5 service", tenant=req.tenant, solver=rep.solver,
            converged=rep.converged, iterations=rep.iterations,
            recovered=rep.failures_recovered, trace_events=len(fired))
        check(rep.converged, f"{req.tenant} did not converge")
        check(rep.failures_recovered == len(fired),
              f"{req.tenant}: recovered {rep.failures_recovered}, trace "
              f"fired {len(fired)}")
        check(rep.storage_failures == want_prd,
              f"{req.tenant}: storage_failures {rep.storage_failures}")
    check(accepted > 0, "the service accepted no tenant")
    say("5 service", accepted=accepted, requests=len(reqs), seconds=wall,
        steps=svc.now)


def phase_sharded(grid):
    """Four chips: the sharded solve with a shard kill, against the
    unsharded solve with the same blocks killed on one device — the
    DESIGN.md §10 bit-identity of x and r."""
    nshards = 4
    spec = api.ResilienceSpec("nvm-prd", persist_mode="overlap")
    solver = api.SolverSpec("pcg", tol=0.0, maxiter=SHARDED_K)
    sharded = api.Problem.poisson(*grid, nblocks=NBLOCKS, nshards=nshards)
    killed = sharded.op.layout.blocks_of(1)
    t0 = time.perf_counter()
    res_s = api.solve(sharded, solver, spec, failures=[
        api.FailureEvent(shard=1, at_iteration=SHARDED_K // 2)])
    xs, rs = np.asarray(res_s.state.x), np.asarray(res_s.state.r)
    wall_s = time.perf_counter() - t0
    shard_devices = {f: {s.device for s in getattr(res_s.state,
                                                   f).addressable_shards}
                     for f in ("x", "r")}
    plain = api.Problem.poisson(*grid, nblocks=NBLOCKS)
    t0 = time.perf_counter()
    res_p = api.solve(plain, solver, spec, failures=[
        api.FailureEvent(blocks=killed, at_iteration=SHARDED_K // 2)])
    xp, rp = np.asarray(res_p.state.x), np.asarray(res_p.state.r)
    wall_p = time.perf_counter() - t0
    x_same = xs.tobytes() == xp.tobytes()
    r_same = rs.tobytes() == rp.tobytes()
    # the first iteration whose residual differs: before the kill points
    # at the step, from it on at the recovery
    hist = zip(res_s.report.residual_history, res_p.report.residual_history)
    diverged = next((k for k, (a, b) in enumerate(hist) if a != b), None)
    say("sharded", nshards=nshards, killed_blocks=killed,
        sharded_seconds=wall_s, unsharded_seconds=wall_p,
        devices_x=len(shard_devices["x"]), devices_r=len(shard_devices["r"]),
        recovered=(res_s.report.failures_recovered,
                   res_p.report.failures_recovered),
        x_bit_identical=x_same, r_bit_identical=r_same,
        first_residual_divergence=diverged,
        x_max_abs_diff=float(np.max(np.abs(xs - xp))),
        r_max_abs_diff=float(np.max(np.abs(rs - rp))))
    check(all(len(d) == nshards for d in shard_devices.values()),
          f"vector shards on {shard_devices}")
    check(res_s.report.failures_recovered == 1
          and res_p.report.failures_recovered == 1, "recovery counts")
    check(x_same and r_same, "sharded solve is not bit-identical")


def main() -> int:
    dev, count = phase_device()
    grid = REHEARSAL_GRID if ARGS.rehearse else GRID
    if ARGS.chips == 4:
        phase_sharded(grid)
    else:
        problem = api.Problem.poisson(*grid, nblocks=NBLOCKS)
        x_ref = phase_reference(problem)
        phase_recoverable(problem, x_ref, dev, grid)
        phase_erasure(problem)
        phase_service()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
