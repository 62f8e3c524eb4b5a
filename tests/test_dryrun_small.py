"""Dry-run machinery regression test on a small (2,2,2) host-device mesh.

Runs in a SUBPROCESS (the ``multi_device`` fixture) so the 8-device XLA
flag never touches this test process (smoke tests must keep seeing 1
device).
"""
import pytest

_SUB = r"""
import json, jax
import dataclasses as dc
from repro.distributed.sharding import set_rules
from repro.models import registry as R
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, compat_make_mesh
from repro.launch.roofline import analyze

mesh = compat_make_mesh((2, 2, 2), ("pod", "data", "model"))
rules = set_rules(mesh)
out = {}

# one SMOKE arch cell per kind through the full build_cell -> compile path
for arch, shape in (("llama3_8b", "train_4k"), ("llama3_8b", "decode_32k")):
    cfg = dc.replace(R.get_config(arch, smoke=True), name=f"{arch}-dry")
    # shrink the shape for test speed
    sh = dc.replace(R.SHAPES[shape], seq=128, batch=8)
    R.SHAPES["_test"] = sh
    cell = R.build_cell(cfg, arch, "_test", rules)
    with mesh:
        compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                           donate_argnums=cell.donate).lower(*cell.in_structs).compile()
    r = analyze(compiled, 8, PRODUCTION_DEVICE_KIND)
    ma = compiled.memory_analysis()
    out[f"{arch}/{shape}"] = {
        "flops": r.flops,
        "peak": int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes),
        "colls": r.coll_by_kind,
    }

# the solver step, both variants
from repro.core.spmv import lower_pcg_step
for variant in ("auto", "shardmap"):
    c = lower_pcg_step(mesh, 64, 32, 32, esr_mode="nvm", variant=variant).compile()
    out[f"pcg/{variant}"] = {"colls": analyze(c, 8, PRODUCTION_DEVICE_KIND).coll_by_kind}

print(json.dumps(out))
"""


@pytest.mark.multi_device
def test_dryrun_small_mesh(multi_device):
    out = multi_device.run(_SUB, ndevices=8, timeout=480)
    # train cell compiled, has compute and collectives
    tr = out["llama3_8b/train_4k"]
    assert tr["flops"] > 0 and tr["peak"] > 0
    assert any(k in tr["colls"] for k in ("all-reduce", "all-gather"))
    # decode cell compiled
    assert out["llama3_8b/decode_32k"]["peak"] > 0
    # the hillclimbed solver variant moves (far) fewer halo bytes
    auto_cp = out["pcg/auto"]["colls"].get("collective-permute", 0)
    opt_cp = out["pcg/shardmap"]["colls"].get("collective-permute", 0)
    assert 0 < opt_cp < auto_cp
