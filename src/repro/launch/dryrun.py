import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the device count on first init.
#   512 placeholder host devices back the (2,16,16) production mesh; the
#   dry-run never touches an accelerator, even on a machine that has one.

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x applicable input shape) cell — and the paper's
own PCG solver cells — this lowers and compiles the jitted step on the
production mesh (single-pod 16x16 and multi-pod 2x16x16) of host
placeholder devices, prices the roofline against the production chip
(:data:`~repro.launch.mesh.PRODUCTION_DEVICE_KIND`, named in every row),
prints
``memory_analysis()`` (fits/doesn't fit) and ``cost_analysis()`` (FLOPs,
bytes), extracts collective bytes from the partitioned HLO, and appends
one JSON row per cell to ``results/dryrun.jsonl`` for EXPERIMENTS.md.

Usage::

    python -m repro.launch.dryrun                         # all cells
    python -m repro.launch.dryrun --arch llama3_8b --shape train_4k
    python -m repro.launch.dryrun --mesh multi            # 2x16x16 only
    python -m repro.launch.dryrun --solver                # PCG cells only
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax

from repro.distributed.sharding import set_rules, use_rules
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.launch import roofline as RL
from repro.models import registry as R


def _memory_row(compiled) -> dict:
    ma = compiled.memory_analysis()
    try:
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "peak_bytes": int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                              + ma.temp_size_in_bytes),
        }
    except AttributeError:
        return {"raw": str(ma)}


def _compile_cell(cfg, arch, shape_name, rules, mesh):
    cell = R.build_cell(cfg, arch, shape_name, rules)
    with mesh:
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.in_structs)
        return lowered.compile(), cell


def _depth_variant(cfg, groups: int):
    """Same architecture at reduced UNROLLED depth (scan calibration):
    rolled scan bodies are counted once by cost_analysis regardless of
    trip count, so the calibration variants unroll their (short) scans."""
    import dataclasses as dc
    period = cfg.group_size
    kw = {"n_layers": period * groups, "name": f"{cfg.name}@g{groups}",
          "unroll_groups": True}
    if cfg.family == "encdec":
        kw["enc_layers"] = groups
    return dc.replace(cfg, **kw)


OPT_LEVERS = {
    # §Perf hillclimb levers, applied via --opt (see EXPERIMENTS.md §Perf)
    "logit_bf16": {"logit_dtype": "bfloat16"},
    "explicit_sp": {"explicit_sp": True},
    "bf16_gather": {"bf16_gather": True},
    "remat_dots": {"remat_policy": "dots"},
    "serve_resident": {"serve_resident": True},
    "micro2": {"microbatches": 2},
    "micro4": {"microbatches": 4},
}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             results_path: Optional[str] = "results/dryrun.jsonl",
             verbose: bool = True, calibrate: Optional[bool] = None,
             opt: Optional[str] = None) -> dict:
    """Compile one (arch x shape x mesh) cell.

    XLA's ``cost_analysis`` counts a ``scan`` body ONCE regardless of trip
    count, so FLOPs/bytes/collective-bytes are calibrated by compiling
    1-group and 2-group depth variants and extrapolating the per-group
    delta across the full depth.  Memory analysis always comes from the
    full-depth compile.  Calibration runs on the single-pod mesh (the
    roofline table is single-pod); the multi-pod pass proves compilation.
    """
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = len(mesh.devices.reshape(-1))
    rules = set_rules(mesh)
    cfg = R.get_config(arch)
    label = arch
    if opt:
        import dataclasses as dc
        kw = {}
        for lever in opt.split(","):
            kw.update(OPT_LEVERS[lever])
        cfg = dc.replace(cfg, **kw)
        label = f"{arch}+{opt}"
    if calibrate is None:
        calibrate = not multi_pod

    t0 = time.monotonic()
    compiled, cell = _compile_cell(cfg, arch, shape_name, rules, mesh)
    dt = time.monotonic() - t0
    mem = _memory_row(compiled)

    row = {
        "arch": label, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "device_kind": PRODUCTION_DEVICE_KIND,
        "compile_s": round(dt, 1),
        "memory": mem,
        "ok": True,
    }

    if calibrate:
        c1, _ = _compile_cell(_depth_variant(cfg, 1), arch, shape_name, rules, mesh)
        c2, _ = _compile_cell(_depth_variant(cfg, 2), arch, shape_name, rules, mesh)
        r1 = RL.analyze(c1, chips, PRODUCTION_DEVICE_KIND)
        r2 = RL.analyze(c2, chips, PRODUCTION_DEVICE_KIND)
        period = cfg.group_size
        groups_eff = cfg.n_groups + cfg.n_tail / period
        if cfg.family == "encdec":
            groups_eff = cfg.n_layers  # enc+dec scale together per group

        # the microbatch accumulation loop is ALSO a scan (counted once):
        # per-layer work sits inside it, so totals scale by cfg.microbatches
        mb = cfg.microbatches if shape_name == "train_4k" else 1

        def extrap(a, b):
            return (a + (b - a) * (groups_eff - 1)) * mb

        coll_kinds = set(r1.coll_by_kind) | set(r2.coll_by_kind)
        colls = {k: int(extrap(r1.coll_by_kind.get(k, 0), r2.coll_by_kind.get(k, 0)))
                 for k in coll_kinds}
        roof = RL.Roofline(
            flops=extrap(r1.flops, r2.flops),
            hbm_bytes=extrap(r1.hbm_bytes, r2.hbm_bytes),
            coll_bytes=float(sum(colls.values())),
            coll_by_kind=colls,
            chips=chips,
            device_kind=PRODUCTION_DEVICE_KIND,
        )
        mflops = RL.model_flops(cfg, cell.shape, cell.shape.kind)
        row.update({
            "roofline": roof.as_row(),
            "coll_by_kind": roof.coll_by_kind,
            "model_flops_global": mflops,
            "model_flops_per_chip": mflops / chips,
            "useful_flop_ratio": (mflops / chips) / roof.flops if roof.flops else None,
            "calibration": {"groups_eff": groups_eff,
                            "flops_g1": r1.flops, "flops_g2": r2.flops},
        })
        if verbose:
            print(f"[{arch} x {shape_name} x {row['mesh']}] compile {dt:.1f}s | "
                  f"peak {mem.get('peak_bytes', 0)/2**30:.2f} GiB/dev | "
                  f"flops/chip {roof.flops:.3e} (useful {row['useful_flop_ratio']:.2f}) | "
                  f"bottleneck {roof.bottleneck} "
                  f"(c={roof.t_compute*1e3:.1f} m={roof.t_memory*1e3:.1f} "
                  f"x={roof.t_collective*1e3:.1f} ms)")
    elif verbose:
        print(f"[{label} x {shape_name} x {row['mesh']}] compile {dt:.1f}s | "
              f"peak {mem.get('peak_bytes', 0)/2**30:.2f} GiB/dev | multi-pod pass OK")

    if results_path:
        os.makedirs(os.path.dirname(results_path), exist_ok=True)
        with open(results_path, "a") as f:
            f.write(json.dumps(row) + "\n")
    return row


def run_solver_cell(grid_name: str, multi_pod: bool,
                    results_path: Optional[str] = "results/dryrun.jsonl",
                    verbose: bool = True) -> dict:
    from repro.configs.poisson_pcg import GRIDS
    from repro.core.spmv import lower_pcg_step
    sc = GRIDS[grid_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = len(mesh.devices.reshape(-1))
    nz, ny, nx = sc.grid
    t0 = time.monotonic()
    lowered = lower_pcg_step(mesh, nz, ny, nx, esr_mode=sc.esr_mode,
                             variant=sc.variant)
    compiled = lowered.compile()
    dt = time.monotonic() - t0
    mem = _memory_row(compiled)
    roof = RL.analyze(compiled, chips, PRODUCTION_DEVICE_KIND)
    n = nz * ny * nx
    # PCG iteration useful flops: SpMV(7pt: 7 mul+6 add ~ 13/pt... count 2*nnz
    # = 14n) + 2 dots (4n) + 3 axpy (6n) + precond (n)  => ~25n flops global
    useful = 25.0 * n / chips
    row = {
        "arch": "poisson_pcg", "shape": grid_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "device_kind": PRODUCTION_DEVICE_KIND,
        "esr_mode": sc.esr_mode,
        "compile_s": round(dt, 1),
        "memory": mem,
        "roofline": roof.as_row(),
        "coll_by_kind": roof.coll_by_kind,
        "model_flops_per_chip": useful,
        "useful_flop_ratio": useful / roof.flops if roof.flops else None,
        "ok": True,
    }
    if verbose:
        print(f"[pcg {grid_name} ({sc.esr_mode}) x {row['mesh']}] compile {dt:.1f}s | "
              f"peak {mem.get('peak_bytes',0)/2**30:.3f} GiB/dev | "
              f"bottleneck {roof.bottleneck} colls {roof.coll_by_kind}")
    if results_path:
        os.makedirs(os.path.dirname(results_path), exist_ok=True)
        with open(results_path, "a") as f:
            f.write(json.dumps(row) + "\n")
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all applicable)")
    ap.add_argument("--mesh", default="both", choices=("single", "multi", "both"))
    ap.add_argument("--solver", action="store_true", help="run PCG solver cells only")
    ap.add_argument("--with-solver", action="store_true", help="include PCG cells")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already present in --out")
    ap.add_argument("--opt", default=None,
                    help="comma-separated §Perf levers (see OPT_LEVERS)")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = []

    done = set()
    if args.resume and os.path.exists(args.out):
        for line in open(args.out):
            try:
                r = json.loads(line)
                if r.get("ok"):
                    done.add((r["arch"], r["shape"], r["mesh"]))
            except json.JSONDecodeError:
                pass

    def _skip(arch, shape, mp):
        return (arch, shape, "2x16x16" if mp else "16x16") in done

    if args.solver or args.with_solver:
        from repro.configs.poisson_pcg import GRIDS
        for g in GRIDS:
            for mp in meshes:
                if _skip("poisson_pcg", g, mp):
                    continue
                try:
                    run_solver_cell(g, mp, args.out)
                except Exception as e:  # noqa: BLE001
                    failures.append((f"pcg/{g}", mp, repr(e)))
                    traceback.print_exc()
        if args.solver:
            _finish(failures)
            return

    archs = [args.arch] if args.arch else R.ARCH_IDS
    for arch in archs:
        cfg = R.get_config(arch)
        shapes = [args.shape] if args.shape else R.cells_for(cfg)
        for shape in shapes:
            for mp in meshes:
                if _skip(arch, shape, mp):
                    continue
                try:
                    run_cell(arch, shape, mp, args.out, opt=args.opt)
                except Exception as e:  # noqa: BLE001
                    failures.append((f"{arch}/{shape}", mp, repr(e)))
                    traceback.print_exc()
    _finish(failures)


def _finish(failures) -> None:
    if failures:
        print(f"\nDRY-RUN FAILURES ({len(failures)}):")
        for name, mp, err in failures:
            print(f"  {name} multi_pod={mp}: {err}")
        raise SystemExit(1)
    print("\nDRY-RUN: all requested cells lowered + compiled successfully.")


if __name__ == "__main__":
    main()
