"""Benchmark harness: one module per paper figure/table.

Prints ``name,value,derived`` CSV rows.  Values are microseconds for
time-like rows (modeled with paper-cluster calibration constants where
the real hardware is simulated — see repro/nvm/store.py), bytes/ratios
otherwise (stated per row).

Usage: ``python benchmarks/run.py [module] [--smoke] [--seed N]
[--json [--out PATH]]``.

- ``--smoke`` shrinks problem sizes (exported as ``REPRO_BENCH_SMOKE=1``
  for modules that honor it) — the CI dry-run path.
- ``--seed N`` threads an explicit seed through every module whose
  ``rows()`` accepts one (also exported as ``REPRO_BENCH_SEED``), so
  two identical invocations produce identical rows.
- ``--json`` emits the BENCH_solver.json perf trajectory
  (``bench_trajectory.build``) instead of CSV rows; ``--out PATH``
  overrides the default location (the repo root).  The document is
  deterministic for a fixed seed modulo its ``wall`` subtrees —
  ``tools/check_bench.py`` validates schema and determinism.

Modules:
  memory_overhead     — paper Fig. 2 + Fig. 8 (RAM/NVRAM utilization)
  persist_homogeneous — paper Fig. 9 (homogeneous persistence tiers)
  persist_prd         — paper Fig. 10 (PRD sub-cluster over RDMA)
  iteration_overhead  — wall-clock per-iteration overhead + recovery
  solver_roofline     — ESR vs NVM-ESR collective bytes on the mesh
  solver_zoo          — per-solver persist overhead across backends
  overlap_campaign    — sync vs overlapped persistence + failure campaigns
  bench_trajectory    — the BENCH_solver.json trajectory (headline CSV view)
"""
from __future__ import annotations

import inspect
import json
import os
import sys
import time
import traceback

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BENCH_JSON = os.path.join(REPO_ROOT, "BENCH_solver.json")


def _parse_args(argv):
    args = list(argv)
    opts = {"smoke": False, "json": False, "seed": 0,
            "out": DEFAULT_BENCH_JSON}
    while "--smoke" in args:
        args.remove("--smoke")
        opts["smoke"] = True
    while "--json" in args:
        args.remove("--json")
        opts["json"] = True
    for flag, key, cast in (("--seed", "seed", int), ("--out", "out", str)):
        while flag in args:
            i = args.index(flag)
            try:
                opts[key] = cast(args[i + 1])
            except (IndexError, ValueError):
                raise SystemExit(f"{flag} needs a {cast.__name__} argument")
            del args[i:i + 2]
    if len(args) > 1:
        raise SystemExit(f"at most one module may be selected, got {args}")
    opts["only"] = args[0] if args else None
    return opts


def _call_rows(mod, seed: int):
    """Call ``mod.rows()``, threading the seed when the module takes
    one — the determinism contract of ``--seed``."""
    if "seed" in inspect.signature(mod.rows).parameters:
        return mod.rows(seed=seed)
    return mod.rows()


def main() -> None:
    opts = _parse_args(sys.argv[1:])
    if opts["smoke"]:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    os.environ["REPRO_BENCH_SEED"] = str(opts["seed"])
    if opts["json"]:
        # The trajectory's sharded rows (DESIGN.md §10) need a device
        # mesh; fake 8 host devices BEFORE jax imports (flag is inert
        # after).  CSV module runs keep the real 1-device view.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    if opts["json"]:
        # The JSON trajectory path: one deterministic document, written
        # where future PRs can diff it (tools/check_bench.py gates it).
        from benchmarks import bench_trajectory

        doc = bench_trajectory.build(seed=opts["seed"])
        with open(opts["out"], "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True, allow_nan=False)
            f.write("\n")
        print(f"wrote {opts['out']} ({len(doc['specs'])} specs, "
              f"seed={opts['seed']}, smoke={doc['smoke']})")
        return

    from benchmarks import (
        bench_trajectory,
        iteration_overhead,
        memory_overhead,
        overlap_campaign,
        persist_homogeneous,
        persist_prd,
        solver_roofline,
        solver_zoo,
    )

    modules = [
        ("memory_overhead", memory_overhead),
        ("persist_homogeneous", persist_homogeneous),
        ("persist_prd", persist_prd),
        ("iteration_overhead", iteration_overhead),
        ("solver_roofline", solver_roofline),
        ("solver_zoo", solver_zoo),
        ("overlap_campaign", overlap_campaign),
        ("bench_trajectory", bench_trajectory),
    ]
    only = opts["only"]
    if only is not None and only not in {name for name, _ in modules}:
        raise SystemExit(f"unknown module {only!r}; have "
                         f"{sorted(name for name, _ in modules)}")
    print("name,value,derived")
    failed = []
    for name, mod in modules:
        if only and name != only:
            continue
        t0 = time.perf_counter()
        try:
            for row_name, value, derived in _call_rows(mod, opts["seed"]):
                print(f"{row_name},{value:.6g},{derived}")
        except Exception as e:  # noqa: BLE001
            failed.append((name, repr(e)))
            traceback.print_exc()
        print(f"_bench_{name}_wall_s,{time.perf_counter()-t0:.2f},harness timing")
    if failed:
        for name, err in failed:
            print(f"_bench_{name}_FAILED,0,{err}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
