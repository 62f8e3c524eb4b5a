"""The comparison that decides ``correct`` catches a broken program.

Each case drives a whole rehearsal run (``--rehearse``: no chip, tiny
grid, same block count) with the timed path broken underneath by a
patch applied before the harness starts, and sees ``correct`` come out
false.  ``--control`` runs the program one precision below the
configuration's (float32), which has to fail too."""
import json
import os
import subprocess
import sys

import pytest

from bench import spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]

#: relative size of a planted perturbation: well above what rounding
#: gives and below what would blow the solve up
EPS = 1e-5

FAULTS = {
    # a step that returns its state unchanged (it only counts)
    "unchanged_step": """
import jax
from repro.solvers import pcg
pcg.PCGSolver.make_step = lambda self, op, pre: jax.jit(
    lambda s: s._replace(k=s.k + 1))
""",
    # half of the work left out: the step updates x and r on the first
    # half of the blocks only
    "half_step": """
import jax, jax.numpy as jnp
from repro.solvers import pcg
_make = pcg.PCGSolver.make_step
def make_step(self, op, pre):
    step = _make(self, op, pre)
    keep = jnp.arange(op.n) < op.n // 2
    def half(s):
        new = step(s)
        return new._replace(x=jnp.where(keep, new.x, s.x),
                            r=jnp.where(keep, new.r, s.r))
    return jax.jit(half)
pcg.PCGSolver.make_step = make_step
""",
    # the answer altered where recovery produces it: the rebuilt x_F
    "reconstruct_altered": f"""
from repro.core import reconstruction
_solve = reconstruction.solve_x_from_residual
def solve_x_from_residual(op, b, x_surviving, r_f, failed, local_method="auto"):
    x = _solve(op, b, x_surviving, r_f, failed, local_method)
    part = op.partition
    return part.scatter(x, part.restrict(x, failed) * (1 + {EPS}), failed)
reconstruction.solve_x_from_residual = solve_x_from_residual
""",
    # the persisted payload altered where it is produced
    "persist_altered": f"""
from repro.solvers import pcg
_rset = pcg.PCGSolver.recovery_set
def recovery_set(self, state):
    rs = _rset(self, state)
    rs.vectors["p"] = rs.vectors["p"] * (1 + {EPS})
    return rs
pcg.PCGSolver.recovery_set = recovery_set
""",
    # the degraded decode returns one wrong bit in every rebuilt chunk:
    # mantissa bit 48 of the f64 value in the middle of the chunk
    "decode_altered": """
from repro.nvm import gf256
_rebuild = gf256.rs_reconstruct
def rs_reconstruct(shards, k):
    out = [d.copy() for d in _rebuild(shards, k)]
    for j, s in enumerate(shards[:k]):
        if s is None:
            out[j][len(out[j]) // 16 * 8 + 6] ^= 0x01
    return out
gf256.rs_reconstruct = rs_reconstruct
""",
    # the exchange between chips left out: the stencil of a sharded
    # problem misses the neighbour shard's plane at every slab boundary,
    # as if no halo arrived
    "halo_dropped": """
import jax.numpy as jnp
from repro.distributed import sharding
_apply = sharding.ShardedOperator.apply
def apply(self, x):
    nz, ny, nx = self.base.grid
    u = x.reshape(nz, ny, nx)
    z = jnp.arange(nz)[:, None, None]
    nzl = nz // self.layout.nshards
    lost = (jnp.where((z % nzl == 0) & (z > 0), jnp.roll(u, 1, axis=0), 0)
            + jnp.where((z % nzl == nzl - 1) & (z < nz - 1),
                        jnp.roll(u, -1, axis=0), 0))
    return _apply(self, x) + lost.reshape(-1)
sharding.ShardedOperator.apply = apply
""",
}

CASES = [
    ("pcg1g-nvmprd.kill", "unchanged_step"),
    ("pcg1g-x6p2.kill-prd", "half_step"),
    ("pcg1g-nvmprd.kill", "reconstruct_altered"),
    ("pcg1g-x6p2.kill-prd", "reconstruct_altered"),
    ("pcg1g-nvmprd.kill", "persist_altered"),
    ("pcg1g-x6p2.kill-prd", "decode_altered"),
]


def run_patched(workload, patch, extra, cache_dir, root=spec.ROOT):
    script = (f"import sys; sys.path[:0] = [{root!r} + '/src', "
              f"{root!r}]\n" + patch +
              "\nfrom bench import run\nsys.exit(run.main(sys.argv[1:]))\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", script, "--workload", workload, "--seed",
         "2147483700", "--seconds", "0.5", "--trace", "0", "--rehearse",
         *extra], capture_output=True, text=True, env=env, timeout=300,
        cwd=root)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


@pytest.mark.parametrize("workload, fault", CASES)
def test_a_broken_program_reads_incorrect(workload, fault, cache_dir):
    out = run_patched(workload, FAULTS[fault], [], cache_dir)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_float32_control_reads_incorrect(workload, cache_dir):
    out = run_patched(workload, "", ["--control"], cache_dir)
    assert out["correct"] is False, out["checks"]
    failed = [k for k, c in out["checks"].items()
              if c["value"] is None or c["value"] > c["limit"]]
    assert failed, out["checks"]


def test_a_sharded_solve_without_its_halo_exchange_reads_incorrect(
        candidate_root, cache_dir):
    from bench.conftest import CANDIDATE

    out = run_patched(CANDIDATE, FAULTS["halo_dropped"], [], cache_dir,
                      root=candidate_root)
    assert out["device"]["count"] == 4
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["x_err"]["value"] > out["checks"]["x_err"]["limit"]
