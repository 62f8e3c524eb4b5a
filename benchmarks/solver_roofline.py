"""ESR vs NVM-ESR on the production mesh: collective bytes + device-RAM
footprint from the compiled solver step (the structural version of the
paper's memory/time claims, per DESIGN.md §5).

Reads results/dryrun.jsonl when the full sweep has run; otherwise spawns
a subprocess with a small 8-device host mesh (this process must keep
seeing 1 device).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, jax
from repro.core.spmv import lower_pcg_step
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, compat_make_mesh
from repro.launch.roofline import analyze
mesh = compat_make_mesh((2,2,2), ("pod","data","model"))
out = {}
for mode in ("nvm", "inmemory"):
    compiled = lower_pcg_step(mesh, 64, 64, 64, esr_mode=mode).compile()
    r = analyze(compiled, 8, PRODUCTION_DEVICE_KIND)
    ma = compiled.memory_analysis()
    out[mode] = {
        "coll_bytes": r.coll_bytes,
        "coll_by_kind": r.coll_by_kind,
        "dev_bytes": int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                         + ma.temp_size_in_bytes),
    }
print(json.dumps(out))
"""


def _from_dryrun():
    path = "results/dryrun.jsonl"
    if not os.path.exists(path):
        return None
    rows = {}
    for line in open(path):
        r = json.loads(line)
        if r.get("arch") == "poisson_pcg" and r["mesh"] == "16x16":
            rows[r["shape"]] = r
    if {"pcg_1g", "pcg_1g_esr"} <= set(rows):
        return rows
    return None


def _persist_bandwidth_rows():
    """The fused persist-bandwidth term (DESIGN.md §13): what share of
    the fused update+staging pass's HBM traffic is persist staging, and
    how many re-read bytes the fusion removes, on the paper's x6+2p
    stripe at the bench solve size."""
    from repro.kernels.fused_cg import fused_pass_traffic

    t = fused_pass_traffic(n=64 * 64 * 64, itemsize=8, k_data=6, nparity=2)
    return [
        ("solver_fused_pass_total_bytes", t["total_bytes"],
         "fused update+staging HBM bytes per pass (x6+2p)"),
        ("solver_persist_bw_fraction", t["persist_bw_fraction"],
         "share of the fused pass spent on persist staging"),
        ("solver_fused_saved_read_bytes", t["unfused_extra_read_bytes"],
         "vector re-read a standalone staging pass would add"),
    ]


def rows():
    out = _persist_bandwidth_rows()
    dr = _from_dryrun()
    if dr is not None:
        nvm, esr = dr["pcg_1g"], dr["pcg_1g_esr"]
        out.append(("solver_nvm_coll_bytes_per_chip",
                    nvm["roofline"]["coll_bytes_per_chip"], "production mesh"))
        out.append(("solver_esr_coll_bytes_per_chip",
                    esr["roofline"]["coll_bytes_per_chip"], "production mesh"))
        out.append(("solver_esr_extra_allgather_bytes",
                    esr["coll_by_kind"].get("all-gather", 0)
                    - nvm["coll_by_kind"].get("all-gather", 0),
                    "the redundancy all-to-all of Algorithm 2"))
        out.append(("solver_esr_dev_ram_x",
                    esr["memory"]["peak_bytes"] / max(nvm["memory"]["peak_bytes"], 1),
                    "peak device RAM blow-up of in-memory ESR"))
        return out
    env = dict(os.environ)
    # prepend, never overwrite: the tier-1 command exports
    # PYTHONPATH=src:$PYTHONPATH and the subprocess must still see the
    # caller's entries (site-installed deps, sitecustomize, ...)
    env["PYTHONPATH"] = os.pathsep.join(
        ["src"] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.pop("XLA_FLAGS", None)
    # placeholder host devices only: never reach for an accelerator the
    # parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _SUB], capture_output=True,
                         text=True, env=env, check=True)
    data = json.loads(res.stdout.strip().splitlines()[-1])
    out.append(("solver_nvm_coll_bytes", data["nvm"]["coll_bytes"], "8-dev mesh"))
    out.append(("solver_esr_coll_bytes", data["inmemory"]["coll_bytes"], "8-dev mesh"))
    out.append(("solver_esr_dev_ram_x",
                data["inmemory"]["dev_bytes"] / max(data["nvm"]["dev_bytes"], 1),
                "peak device RAM blow-up of in-memory ESR"))
    return out
