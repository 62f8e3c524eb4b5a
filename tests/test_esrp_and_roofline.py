"""Regression tests for bugs found during the build, plus roofline-parser
units and a true multi-device elastic-restore test."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FailurePlan,
    InMemoryESR,
    JacobiPreconditioner,
    NVMESRHomogeneous,
    NVMESRPRD,
    PCGConfig,
    make_poisson_problem,
    solve,
)


# ----------------------------------------------------------------------
# REGRESSION: ESRP mid-burst failure (k%S slot rings overwrite the last
# complete pair when persistence has gaps — found by examples/, fixed with
# event-addressed slots + content-matched recovery)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend_cls", [InMemoryESR, NVMESRHomogeneous, NVMESRPRD])
@pytest.mark.parametrize("fail_at", [30, 31, 32])
def test_esrp_mid_burst_failure_recovers(backend_cls, fail_at):
    """Period-5 bursts persist k=25,26 then k=30,31...  A failure at k=30
    (right after the FIRST write of the new burst) must still recover
    from the (25,26) pair; at k=31 from (30,31)."""
    op, b = make_poisson_problem(32, 16, 16, nblocks=8)
    pre = JacobiPreconditioner(op)
    be = backend_cls(op.nblocks, op.partition.block_size, np.float64)
    st, rep, _ = solve(op, b, pre,
                       PCGConfig(tol=1e-10, persistence_period=5),
                       backend=be, failures=[FailurePlan(fail_at, (1, 2))])
    assert rep.failures_recovered == 1
    assert rep.converged
    res = float(jnp.linalg.norm(b - op.apply(st.x)) / jnp.linalg.norm(b))
    assert res < 1e-9


# ----------------------------------------------------------------------
# roofline collective parser units
# ----------------------------------------------------------------------
def test_collective_bytes_parser():
    from repro.launch.roofline import collective_bytes

    hlo = """
  %all-gather.8 = f32[16,4096,4096]{2,0,1} all-gather(%x), replica_groups=[16,16]<=[256]
  %ar = bf16[256]{0} all-reduce(%y), to_apply=%sum
  %cp = f32[5,1026,1026]{2,1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %aa = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-to-all(%a, %b)
  %unrelated = f32[2,2]{1,0} add(%p, %q)
"""
    got = collective_bytes(hlo)
    assert got["all-gather"] == 16 * 4096 * 4096 * 4
    assert got["all-reduce"] == 256 * 2
    assert got["collective-permute"] == 5 * 1026 * 1026 * 4
    assert got["all-to-all"] == 2 * 8 * 8 * 4
    assert "add" not in got


def test_corrected_collectives_model():
    from repro.launch.report import corrected_coll_bytes

    row = {"coll_by_kind": {"all-gather": 100, "all-reduce": 80,
                            "collective-permute": 20}}
    # bf16 model: 0.5*(AG+CP) + 0.25*AR
    assert corrected_coll_bytes(row, bf16=True) == 0.5 * 120 + 0.25 * 80
    assert corrected_coll_bytes(row, bf16=False) == 200


def test_roofline_terms_and_bottleneck():
    from repro.launch.roofline import Roofline

    r = Roofline(flops=197e12, hbm_bytes=819e9 / 2, coll_bytes=50e9 * 2,
                 coll_by_kind={}, chips=256, device_kind="TPU v5 lite")
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 0.5) < 1e-9
    assert abs(r.t_collective - 2.0) < 1e-9
    assert r.bottleneck == "collective"
    assert r.step_time_lb == r.t_collective


def test_roofline_refuses_unknown_device_kind():
    """Peaks are keyed by device_kind; a chip with no published entry
    (or the host CPU) is an error, never a silent v5e default."""
    from repro.launch.mesh import chip_peaks
    from repro.launch.roofline import Roofline

    assert chip_peaks("TPU v5 lite").hbm_bytes_per_s == 819e9
    for kind in ("cpu", "TPU v4"):
        with pytest.raises(ValueError, match="no published peaks"):
            Roofline(flops=1.0, hbm_bytes=1.0, coll_bytes=0.0,
                     coll_by_kind={}, chips=1, device_kind=kind)


# ----------------------------------------------------------------------
# elastic restore: checkpoint saved on 1 device restored across 8
# ----------------------------------------------------------------------
_SUB = r"""
import json, sys, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.ft.checkpoint import CheckpointConfig, NVMCheckpointManager
from repro.launch.mesh import compat_make_mesh

ckpt_dir = sys.argv[1]
mgr = NVMCheckpointManager(CheckpointConfig(ckpt_dir))
like = {"w": jnp.zeros((32, 16)), "b": jnp.zeros((8,))}
mesh = compat_make_mesh((8,), ("data",))
sh = {"w": NamedSharding(mesh, P("data", None)), "b": NamedSharding(mesh, P())}
got = mgr.restore(like, shardings=sh)
assert got is not None
tree, step, _ = got
ndev = len(tree["w"].sharding.device_set)
print(json.dumps({"step": step, "ndev": ndev,
                  "sum": float(tree["w"].sum())}))
"""


@pytest.mark.multi_device
def test_elastic_restore_across_device_counts(tmp_path, multi_device):
    from repro.ft.checkpoint import CheckpointConfig, NVMCheckpointManager

    # save on THIS process (1 device)
    mgr = NVMCheckpointManager(CheckpointConfig(str(tmp_path)))
    w = jnp.arange(32 * 16, dtype=jnp.float32).reshape(32, 16)
    tree = {"w": w, "b": jnp.ones((8,))}
    mgr.save(tree, step=42)

    out = multi_device.run(_SUB, ndevices=8, argv=[str(tmp_path)],
                           timeout=240)
    assert out["step"] == 42
    assert out["ndev"] == 8                      # resharded onto 8 devices
    assert abs(out["sum"] - float(w.sum())) < 1e-3
