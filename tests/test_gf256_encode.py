"""Fused GF(256) encode + persist staging vs the numpy oracle.

Three layers, all bit-exact (ISSUE 10):

- the tiled encode kernel (`kernels/gf256_encode.py`) against
  ``gf256.rs_encode`` across K/P/ragged-length sweeps (interpret mode);
- the fused update+staging kernel (`fused_cg_update_persist_pallas`)
  against the unfused update plus an ``ErasureSession._shards``-style
  numpy staging pass;
- whole solves: an erasure-backed overlap solve with
  ``fused_persist=True`` is bit-identical to the numpy persist path,
  including under a mid-solve PRD kill, with matching report counts.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.fused_cg import (
    fused_cg_update_pallas,
    fused_cg_update_persist_pallas,
    fused_pass_traffic,
)
from repro.kernels.gf256_encode import gf256_rs_encode_pallas
from repro.nvm import gf256


def _shards(rng, k_data, length):
    return [rng.integers(0, 256, size=length, dtype=np.uint8)
            for _ in range(k_data)]


@pytest.mark.parametrize("k_data", [2, 4, 6])
@pytest.mark.parametrize("nparity", [1, 2])
@pytest.mark.parametrize("length", [1, 100, 8192, 8205])
def test_encode_kernel_bit_identical(k_data, nparity, length):
    """Ragged tails, tile multiples, sub-tile lengths: every parity
    byte equals the numpy reference."""
    rng = np.random.default_rng(k_data * 1000 + nparity * 10 + length)
    shards = _shards(rng, k_data, length)
    want = gf256.rs_encode(shards, nparity)
    got = gf256_rs_encode_pallas(shards, nparity, interpret=True)
    assert len(got) == len(want) == nparity
    for g, w in zip(got, want):
        assert g.dtype == np.uint8 and g.shape == w.shape
        assert np.array_equal(g, w)


def test_encode_kernel_zero_and_saturated_bytes():
    """The gf_mul zero-masking edge: all-zero and all-0xFF shards."""
    shards = [np.zeros(512, np.uint8), np.full(512, 0xFF, np.uint8),
              np.zeros(512, np.uint8), np.full(512, 0x1D, np.uint8)]
    for nparity in (1, 2):
        want = gf256.rs_encode(shards, nparity)
        got = gf256_rs_encode_pallas(shards, nparity, interpret=True)
        for g, w in zip(got, want):
            assert np.array_equal(g, w)


def test_encode_kernel_validation_matches_reference():
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError):
        gf256_rs_encode_pallas(_shards(rng, 4, 64), nparity=3,
                               interpret=True)
    ragged = [np.zeros(64, np.uint8), np.zeros(65, np.uint8)]
    with pytest.raises(ValueError, match="share one shape"):
        gf256_rs_encode_pallas(ragged, nparity=1, interpret=True)


def test_ops_rs_encode_is_the_registered_toggle():
    """Both routes through the dispatch seam agree with the oracle."""
    rng = np.random.default_rng(7)
    shards = _shards(rng, 4, 777)
    want = gf256.rs_encode(shards, 2)
    for mode in ("ref", "pallas"):
        got = ops.rs_encode(shards, 2, mode=mode)
        for g, w in zip(got, want):
            assert np.array_equal(g, w)


# ----------------------------------------------------------------------
# Fused update + persist staging kernel
# ----------------------------------------------------------------------
def _stage_oracle(p, nblocks, k_data, nparity, dtype):
    """ErasureSession._shards, distilled: block-wise chunking on the
    stored dtype, then the numpy parity encode over the raw bytes."""
    bs = p.size // nblocks
    chunk = bs // k_data
    v = np.asarray(p, dtype).reshape(nblocks, bs)
    chunks = [np.ascontiguousarray(v[:, j * chunk:(j + 1) * chunk]
                                   ).reshape(-1)
              for j in range(k_data)]
    parity = gf256.rs_encode([c.view(np.uint8) for c in chunks], nparity)
    return chunks, parity


@pytest.mark.parametrize("nblocks,k_data,nparity",
                         [(8, 4, 1), (8, 6, 2), (4, 2, 2)])
@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_fused_persist_kernel_bit_identical(nblocks, k_data, nparity,
                                            dtype):
    # bs = 1536 = 12 rows of 128: every stripe chunk is whole rows
    # for K = 2, 4 and 6
    n = nblocks * 128 * 12
    rng = np.random.default_rng(nblocks + k_data + nparity)
    x, r, p, ap, inv = (jnp.asarray(rng.standard_normal(n), dtype)
                        for _ in range(5))
    alpha = jnp.asarray(0.37, dtype)
    # same row tile as the persist grid (one partition block per step)
    # so even the fp32 dual-reduction partials group identically
    xo, ro, zo, rz = fused_cg_update_pallas(x, r, p, ap, alpha, inv,
                                            bm=n // nblocks // 128,
                                            interpret=True)
    xf, rf, zf, rzf, chunks, parity = fused_cg_update_persist_pallas(
        x, r, p, ap, alpha, inv, nblocks=nblocks, k_data=k_data,
        nparity=nparity, interpret=True)
    # the update outputs are the SAME bits as the staging-free kernel
    for a, b in zip((xo, ro, zo, rz), (xf, rf, zf, rzf)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    want_chunks, want_parity = _stage_oracle(
        np.asarray(p), nblocks, k_data, nparity, np.dtype(dtype))
    for j in range(k_data):
        got = np.asarray(chunks[:, j, :]).reshape(-1)
        assert np.array_equal(got, want_chunks[j])
    for i in range(nparity):
        got = np.asarray(parity[:, i, :]).reshape(-1)
        assert np.array_equal(got, want_parity[i])


def test_fused_persist_kernel_alignment_fallback_errors():
    """Sizes the fused pass cannot stripe raise — the driver's cue to
    fall back to the unfused staging path."""
    n = 4 * 128
    v = jnp.zeros((n,), jnp.float64)
    a = jnp.asarray(1.0, jnp.float64)
    with pytest.raises(ValueError, match="not divisible by nblocks"):
        fused_cg_update_persist_pallas(v, v, v, v, a, v, nblocks=3,
                                       k_data=2, nparity=1, interpret=True)
    with pytest.raises(ValueError, match="multiple of 128"):
        fused_cg_update_persist_pallas(v, v, v, v, a, v, nblocks=8,
                                       k_data=2, nparity=1, interpret=True)
    with pytest.raises(ValueError, match="not divisible by k_data"):
        fused_cg_update_persist_pallas(v, v, v, v, a, v, nblocks=4,
                                       k_data=5, nparity=1, interpret=True)


def test_fused_pass_traffic_accounting():
    t = fused_pass_traffic(n=1 << 20, itemsize=8, k_data=6, nparity=2)
    n_bytes = (1 << 20) * 8
    assert t["update_read_bytes"] == 5 * n_bytes
    assert t["update_write_bytes"] == 3 * n_bytes
    assert t["staged_write_bytes"] == n_bytes + n_bytes * 2 // 6
    assert t["total_bytes"] == sum(
        t[k] for k in ("update_read_bytes", "update_write_bytes",
                       "staged_write_bytes"))
    assert 0.0 < t["persist_bw_fraction"] < 1.0
    assert t["unfused_extra_read_bytes"] == n_bytes


# ----------------------------------------------------------------------
# Whole-solve exactness: fused persist path == numpy persist path
# ----------------------------------------------------------------------
def _solve_pair(fused, campaign, spec="erasure(nvm-prd x6+2p)"):
    from repro.core import JacobiPreconditioner, make_poisson_problem
    from repro.solvers import SolveConfig, make_backend, make_solver, solve

    op, b = make_poisson_problem(8, 8, 8, nblocks=4)
    pre = JacobiPreconditioner(op)
    solver = make_solver("pcg", op, pre)
    backend = make_backend(spec, op, solver=solver)
    cfg = SolveConfig(tol=1e-10, maxiter=5000, persist_mode="overlap",
                      fused_persist=fused)
    return solve(solver, op, b, pre, config=cfg, backend=backend,
                 failures=campaign)


@pytest.mark.parametrize("spec", ["erasure(nvm-prd x4+p)",
                                  "erasure(nvm-prd x6+2p)"])
def test_fused_solve_bit_identical_clean(spec):
    st_ref, rep_ref, _ = _solve_pair(False, (), spec)
    st_f, rep_f, _ = _solve_pair(True, (), spec)
    assert np.array_equal(np.asarray(st_ref.x), np.asarray(st_f.x))
    assert rep_ref.iterations == rep_f.iterations
    assert rep_ref.persist_events == rep_f.persist_events


def test_fused_solve_bit_identical_under_prd_kill():
    """Mid-solve PRD node kill + block loss: the fused route recovers
    onto the identical trajectory with identical abort accounting."""
    from repro.solvers import FailureCampaign, FailureEvent

    camp = FailureCampaign((
        FailureEvent(blocks=(1,), at_iteration=6, prd=True),
        FailureEvent(blocks=(2, 3), at_iteration=10),
    ))
    st_ref, rep_ref, _ = _solve_pair(False, camp)
    st_f, rep_f, _ = _solve_pair(True, camp)
    assert np.array_equal(np.asarray(st_ref.x), np.asarray(st_f.x))
    assert rep_ref.iterations == rep_f.iterations
    assert rep_f.failures_recovered == 2
    assert rep_ref.persist_events == rep_f.persist_events
    assert rep_ref.persist_aborts == rep_f.persist_aborts


def test_fused_solve_traced_closes_the_triangle():
    """With tracing on, the fused route's span/event stream still
    satisfies check_trace_report — including the staging conservation
    law (stage.copy == stage.flush + stage.abort drops) — and records
    the encoder route on the encode span."""
    from repro.core import JacobiPreconditioner, make_poisson_problem
    from repro.obs import Tracer, check_trace_report
    from repro.solvers import (FailureCampaign, FailureEvent, SolveConfig,
                               make_backend, make_solver, solve)

    op, b = make_poisson_problem(8, 8, 8, nblocks=4)
    pre = JacobiPreconditioner(op)
    solver = make_solver("pcg", op, pre)
    backend = make_backend("erasure(nvm-prd x6+2p)", op, solver=solver)
    tracer = Tracer()
    cfg = SolveConfig(tol=1e-10, maxiter=5000, persist_mode="overlap",
                      fused_persist=True, tracer=tracer)
    camp = FailureCampaign((
        FailureEvent(blocks=(0,), at_iteration=5, prd=True),))
    _, report, _ = solve(solver, op, b, pre, config=cfg, backend=backend,
                         failures=camp)
    check_trace_report(tracer, report)
    encoders = {rec["args"].get("encoder")
                for rec in tracer.records
                if rec.get("name") == "gf256.rs_encode"}
    assert encoders == {"pallas"}


def test_resilience_spec_forwards_fused_persist():
    from repro.api import Problem, ResilienceSpec, SolverSpec
    from repro.api import solve as api_solve

    problem = Problem.poisson(8, 8, 8, nblocks=4)
    spec = ResilienceSpec("erasure(nvm-prd x4+p)", persist_mode="overlap",
                          fused_persist=True)
    res_f = api_solve(problem, SolverSpec("pcg", tol=1e-10), spec)
    res_r = api_solve(problem, SolverSpec("pcg", tol=1e-10),
                      ResilienceSpec("erasure(nvm-prd x4+p)",
                                     persist_mode="overlap"))
    assert res_f.converged and res_r.converged
    assert np.array_equal(res_f.x, res_r.x)


def test_set_encode_mode_validates_and_propagates():
    from repro.core import make_poisson_problem
    from repro.nvm.backend import create_backend
    from repro.solvers import make_solver

    op, b = make_poisson_problem(8, 8, 8, nblocks=4)
    from repro.core import JacobiPreconditioner

    solver = make_solver("pcg", op, JacobiPreconditioner(op))
    be = create_backend("erasure(nvm-prd x4+p)", op.partition.nblocks,
                        op.partition.block_size, schema=solver.schema)
    session = be.open_session(solver.schema, op.partition)
    assert session._encode_mode == "ref"
    session.set_encode_mode("pallas")
    assert session._encode_mode == "pallas"
    with pytest.raises(ValueError, match="unknown parity encode mode"):
        session.set_encode_mode("simd")
    with pytest.raises(ValueError, match="unknown parity encode mode"):
        create_backend("erasure(nvm-prd x4+p)", op.partition.nblocks,
                       op.partition.block_size, schema=solver.schema,
                       encode="simd")
