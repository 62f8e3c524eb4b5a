"""Compile the main path's kernels and the recoverable PCG step for a
TPU v5e chip, at real widths, without a chip attached.

Interpret-mode tests cannot see what Mosaic refuses (tiling rules,
unsupported vector ops, 64-bit operands), so every Pallas kernel on the
solve path is compiled here for a described ``v5e:2x2`` topology: the
TPU compiler is installed even where no chip is.  Nothing runs; each
test asserts that the kernel lowered to a ``tpu_custom_call`` (not an
interpreted body) and that the program fits one chip's memory.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but can never be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return compiled


def test_stencil7_compiles_for_v5e(one_chip, no_compile_cache):
    from repro.kernels.stencil7 import stencil7_pallas

    compiled = _compile(lambda u: stencil7_pallas(u),
                        _spec((64, 256, 256), jnp.float32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_cg_update_compiles_for_v5e(one_chip, no_compile_cache):
    from repro.kernels.fused_cg import fused_cg_update_pallas

    n = 1 << 21
    vec = _spec((n,), jnp.float32, one_chip)
    compiled = _compile(
        lambda x, r, p, ap, inv, a: fused_cg_update_pallas(
            x, r, p, ap, a, inv),
        vec, vec, vec, vec, vec, _spec((), jnp.float32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("nparity", [1, 2])
def test_fused_cg_update_persist_compiles_for_v5e(one_chip, no_compile_cache,
                                                  nparity):
    from repro.kernels.fused_cg import fused_cg_update_persist_pallas

    n = 1 << 21
    vec = _spec((n,), jnp.float32, one_chip)
    compiled = _compile(
        lambda x, r, p, ap, inv, a: fused_cg_update_persist_pallas(
            x, r, p, ap, a, inv, nblocks=64, k_data=4, nparity=nparity),
        vec, vec, vec, vec, vec, _spec((), jnp.float32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("nparity", [1, 2])
def test_gf256_encode_compiles_for_v5e(one_chip, no_compile_cache, nparity):
    from repro.kernels.gf256_encode import DEFAULT_BM, _encode_tiles

    # (6, 4096, 128) bytes = six 512 KiB shards, packed 4 per uint32
    words = _spec((6, 4096 // 4, 128), jnp.uint32, one_chip)
    compiled = _compile(
        lambda w: _encode_tiles(w, nparity=nparity, bm=DEFAULT_BM,
                                interpret=False),
        words)
    assert "tpu_custom_call" in compiled.as_text()


def test_kernels_refuse_f64_before_lowering():
    from repro.kernels.fused_cg import fused_cg_update_pallas
    from repro.kernels.stencil7 import stencil7_pallas

    v = jnp.zeros((1024,), jnp.float64)
    with pytest.raises(ValueError, match="float64 operands cannot be"):
        fused_cg_update_pallas(v, v, v, v, 1.0, v)
    with pytest.raises(ValueError, match="float64 operands cannot be"):
        stencil7_pallas(jnp.zeros((8, 8, 128), jnp.float64))


def test_pcg_step_f64_compiles_for_v5e(one_chip, no_compile_cache):
    """The recoverable PCG step the driver jits — f64, Jacobi, the
    order-pinned dots — at the pcg_1g block shape: 8 blocks of
    2x1024x1024 on one chip.  XLA emulates f64 on the TPU."""
    from repro.core.poisson import JacobiPreconditioner, StencilOperator
    from repro.core.state import PCGState
    from repro.solvers.pcg import PCGSolver

    op = StencilOperator(16, 1024, 1024, nblocks=8)
    step = PCGSolver().make_step(op, JacobiPreconditioner(op))
    vec = _spec((op.n,), jnp.float64, one_chip)
    scalar = _spec((), jnp.float64, one_chip)
    state = PCGState(x=vec, r=vec, z=vec, p=vec, rz=scalar,
                     beta_prev=scalar, k=_spec((), jnp.int32, one_chip))
    compiled = step.lower(state).compile()
    mem = compiled.memory_analysis()
    # x, r and p are read (z is recomputed, so XLA drops it)
    assert mem.argument_size_in_bytes >= 3 * op.n * 8
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM_BYTES


def test_device_norm_compiles_for_v5e(topo, one_chip, no_compile_cache):
    """The convergence norm's program at the pcg_1g block shape, on one
    chip and z-sharded over four: it reads the f64 vector and returns
    one f64 scalar, the only bytes the host pulls."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.solvers.base import _sq_norm

    n = 16 * 1024 * 1024
    mesh = Mesh(topo.devices, ("data",))
    for m, sharding in ((None, one_chip),
                        (mesh, NamedSharding(mesh, P("data")))):
        lowered = _sq_norm(8, m).lower(_spec((n,), jnp.float64, sharding))
        assert (lowered.out_info.shape, lowered.out_info.dtype) == (
            (), jnp.float64)
        mem = lowered.compile().memory_analysis()
        assert mem.argument_size_in_bytes >= n * 8 // (1 if m is None else 4)
        # the scalar's output buffer is one padded tile, not a vector
        assert mem.output_size_in_bytes <= 1024


def test_sharded_pcg_compiles_for_v5e_2x2(topo, no_compile_cache):
    """The same grid z-sharded over four chips: the step (halo
    exchange as collective-permutes) and the stencil alone, as
    ``init_state`` and recovery call it outside the step.  The stencil
    must compile as one program: an eager ``jnp.pad`` of the sharded
    f64 grid aborts the TPU compiler."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.poisson import (JacobiPreconditioner, StencilOperator,
                                    stencil7)
    from repro.core.state import PCGState
    from repro.distributed.sharding import ShardedOperator, ShardLayout
    from repro.solvers.pcg import PCGSolver

    mesh = Mesh(topo.devices, ("data",))
    shard, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    op = StencilOperator(16, 1024, 1024, nblocks=8)
    sop = ShardedOperator(op, ShardLayout(8, 4), mesh)
    step = PCGSolver().make_step(sop, JacobiPreconditioner(op))
    vec = _spec((op.n,), jnp.float64, shard)
    scalar = _spec((), jnp.float64, rep)
    state = PCGState(x=vec, r=vec, z=vec, p=vec, rz=scalar,
                     beta_prev=scalar, k=_spec((), jnp.int32, rep))
    hlo = step.lower(state).compile().as_text()
    assert "collective-permute" in hlo
    stencil7.lower(_spec(op.grid, jnp.float64, shard)).compile()
