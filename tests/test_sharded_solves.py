"""Sharded-solve lockdown (ISSUE 7): bit-exactness, device-mapped
failures, per-shard recovery traffic.

Three claims from DESIGN.md §10, each asserted against a single
subprocess sweep under 8 faked host devices (the ``multi_device``
fixture; the flag must precede the jax import, so the payload cannot
run in-process):

- **bit-exactness**: every registered solver, in both persist modes,
  against every persistence family, produces a device-sharded
  trajectory bitwise equal to the unsharded one — with and without a
  kill-and-recover in the middle;
- **device-mapped failures**: ``FailureEvent(shard=...)`` kills
  exactly the blocks of that device shard and recovery absorbs it;
- **traffic**: the recovery fetch moves exactly one shard's slot
  bytes — read back from the metrics registry (the same counters
  ``SolveReport`` derives from), never re-derived from the trace — and
  scales with ``blocks_per_shard`` as the shard count varies.
"""
import pytest

_SUB = r"""
import json
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
from repro.core.poisson import make_poisson_problem, PRECONDITIONERS
from repro.distributed.sharding import shard_problem
from repro.obs.metrics import check_report_consistency
from repro.solvers import driver as drv
from repro.solvers.registry import make_solver, make_backend

SOLVERS = ("pcg", "bicgstab", "gmres", "chebyshev", "jacobi")
MODES = ("sync", "overlap")
SPECS = ("nvm-homogeneous", "nvm-prd", "replicated(nvm-prd x2)",
         "erasure(nvm-prd x4+p)")

op, b = make_poisson_problem(8, 8, 8, nblocks=4)
pre = PRECONDITIONERS["jacobi"](op)
sop, sb = shard_problem(op, b, 4)   # 4 shards -> 1 block per shard


def run(name, the_op, the_b, the_pre, spec, mode, failures):
    solver = make_solver(name, the_op, the_pre)
    backend = make_backend(spec, op if the_op.nblocks == 4 else the_op,
                           solver=solver)
    cfg = drv.SolveConfig(tol=0.0, maxiter=8, persistence_period=2,
                          persist_mode=mode)
    st, rep, _ = drv.solve(solver, the_op, the_b, the_pre, config=cfg,
                           backend=backend, failures=failures)
    check_report_consistency(rep)
    return solver, st, rep


out = {"sweep": [], "nofail": [], "scaling": []}
kill_block = [drv.FailureEvent(blocks=(1,), at_iteration=4)]
kill_shard = [drv.FailureEvent(shard=1, at_iteration=4)]

# --- kill-and-recover bit-exactness sweep -----------------------------
for name in SOLVERS:
    for mode in MODES:
        _, st0, _ = run(name, op, b, pre, "nvm-homogeneous", mode,
                        kill_block)
        bx = np.asarray(st0.x).tobytes()
        br = np.asarray(st0.r).tobytes()
        for spec in SPECS:
            solver, st1, rep1 = run(name, sop, sb, pre, spec, mode,
                                    kill_shard)
            slot = solver.schema.slot_nbytes(op.partition.block_size,
                                             np.dtype(b.dtype))
            m = rep1.metrics
            out["sweep"].append({
                "solver": name, "mode": mode, "spec": spec,
                "x_ok": np.asarray(st1.x).tobytes() == bx,
                "r_ok": np.asarray(st1.r).tobytes() == br,
                "recovered": rep1.failures_recovered,
                "nshards": rep1.nshards,
                # registry reads, NOT re-derived from the trace
                "fetch_registry":
                    m.counter_total("recovery.fetch_bytes"),
                "fetch_by_shard": {
                    str(k): v for k, v in m.counter_by_label(
                        "recovery.fetch_bytes", "shard").items()},
                # one shard == one block here
                "want_fetch": solver.schema.history * 1 * slot,
            })

# --- plain sharded solves (no failure) match too ----------------------
for name in SOLVERS:
    _, st0, _ = run(name, op, b, pre, "nvm-homogeneous", "sync", [])
    _, st1, _ = run(name, sop, sb, pre, "nvm-homogeneous", "sync", [])
    out["nofail"].append({
        "solver": name,
        "x_ok": np.asarray(st1.x).tobytes()
                == np.asarray(st0.x).tobytes(),
        "r_ok": np.asarray(st1.r).tobytes()
                == np.asarray(st0.r).tobytes(),
    })

# --- recovery traffic scales with blocks-per-shard --------------------
op8, b8 = make_poisson_problem(8, 8, 8, nblocks=8)
pre8 = PRECONDITIONERS["jacobi"](op8)
for nshards in (2, 4, 8):
    sop8, sb8 = shard_problem(op8, b8, nshards)
    solver, st, rep = run("pcg", sop8, sb8, pre8, "nvm-homogeneous",
                          "sync",
                          [drv.FailureEvent(shard=0, at_iteration=4)])
    slot = solver.schema.slot_nbytes(op8.partition.block_size,
                                     np.dtype(b8.dtype))
    out["scaling"].append({
        "nshards": nshards,
        "fetch": rep.metrics.counter_total("recovery.fetch_bytes"),
        "want": solver.schema.history * (8 // nshards) * slot,
    })

print(json.dumps(out))
"""


@pytest.mark.multi_device
def test_sharded_bit_exactness_failures_and_traffic(multi_device):
    out = multi_device.run(_SUB, ndevices=8, timeout=1800)

    sweep = out["sweep"]
    assert len(sweep) == 5 * 2 * 4
    for case in sweep:
        ctx = (case["solver"], case["mode"], case["spec"])
        assert case["x_ok"] and case["r_ok"], ctx
        assert case["recovered"] == 1, ctx
        assert case["nshards"] == 4, ctx
        # fetched bytes == one shard's slot bytes, from the registry,
        # attributed to the killed shard
        assert case["fetch_registry"] == case["want_fetch"], ctx
        assert case["fetch_by_shard"] == {"1": case["want_fetch"]}, ctx

    assert len(out["nofail"]) == 5
    for case in out["nofail"]:
        assert case["x_ok"] and case["r_ok"], case["solver"]

    scaling = {c["nshards"]: c for c in out["scaling"]}
    assert set(scaling) == {2, 4, 8}
    for nshards, case in scaling.items():
        assert case["fetch"] == case["want"], case
    # halving the shard count doubles the bytes a recovery must move
    assert scaling[2]["fetch"] == 2 * scaling[4]["fetch"]
    assert scaling[4]["fetch"] == 2 * scaling[8]["fetch"]


_NORM_SUB = r"""
import json
import jax
jax.config.update("jax_enable_x64", True)
from repro.core.poisson import make_poisson_problem, PRECONDITIONERS
from repro.distributed.sharding import shard_problem
from repro.solvers import driver as drv
from repro.solvers.registry import make_solver

op, b = make_poisson_problem(8, 8, 8, nblocks=4)
pre = PRECONDITIONERS["jacobi"](op)
sop, sb = shard_problem(op, b, 4)
out = {}
for label, the_op, the_b in (("one", op, b), ("four", sop, sb)):
    _, rep, _ = drv.solve(make_solver("pcg", the_op, pre), the_op, the_b,
                          pre, config=drv.SolveConfig(tol=1e-10))
    out[label] = {"history": [v.hex() for v in rep.residual_history],
                  "final": rep.final_relres.hex(), "nshards": rep.nshards,
                  "converged": rep.converged}
print(json.dumps(out))
"""


@pytest.mark.multi_device
def test_sharded_convergence_norm_is_bitwise_identical(multi_device):
    """The device norm (DESIGN.md §10): a PCG solve on one device and
    on four shards reads the same residual history and final relres,
    bit for bit."""
    out = multi_device.run(_NORM_SUB, ndevices=4, timeout=600)
    one, four = out["one"], out["four"]
    assert (one["nshards"], four["nshards"]) == (1, 4)
    assert one["converged"] and len(one["history"]) > 10
    assert four["history"] == one["history"]
    assert four["final"] == one["final"]
