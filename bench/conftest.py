"""A candidate four-chip cell for the benchmark's tests, added to a copy
of the checkout the way a later cell is added: new data files, one new
``workloads`` entry and new ``per_layer`` entries, with no existing file
or entry edited."""
import json
import os
import shutil

import pytest

from bench import spec

#: pcg_1g's 2x1024x1024 blocks, 8 per chip, z-sharded over four chips;
#: one whole shard (8 blocks) killed at mid-window
CANDIDATE = "pcg4c-nvmprd.shardkill"
#: the existing per-layer metrics the candidate reads, each through a
#: new entry ``<metric>.<cell>`` that the metric's own reader serves
CANDIDATE_METRICS = ["idle_share", "step_device_ms", "step_hbm_roofline",
                     "recovery_fetch_s", "recovery_reconstruct_s",
                     "residual_check_ms", "persist_pull_ms",
                     "persist_stage_ms", "persist_commit_ms",
                     "host_unspanned_ms"]


def candidate_files():
    """path under bench/ -> JSON document of each new data file."""
    base = spec.load_config("pcg1g-nvmprd")
    config = dict(base, name="pcg4c-nvmprd", nz=64, nblocks=32, chips=4,
                  iterations_per_second=0.0784)
    traffic = {"name": "shardkill",
               "why": "one chip lost at mid-window: every block of one "
                      "shard, the seed picks the shard",
               "failures": [{"at": 0.5, "unit": "shard", "storage": False}]}
    return {"configs/pcg4c-nvmprd.json": config,
            "traffic/shardkill.json": traffic}


def candidate_entry():
    return {"name": CANDIDATE, "config": "pcg4c-nvmprd",
            "traffic": "shardkill", "chips": 4,
            "why": "n=2^26 f64 over 4 chips, 8 blocks each: halo exchange, "
                   "cross-shard dots, 4 shares persisted per step; one chip's "
                   "8 blocks killed at mid-window"}


def candidate_metrics(bench):
    """The candidate's per-layer entries: each metric it reads, as the
    existing entry has it, under its own name and ``workloads``."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    return [dict(by_name[m], name=f"{m}.{CANDIDATE}", workloads=[CANDIDATE])
            for m in CANDIDATE_METRICS]


def add_candidate(root: str) -> None:
    """Add the candidate cell to the checkout at ``root``."""
    for rel, doc in candidate_files().items():
        path = os.path.join(root, "bench", rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f, indent=2)
    bench = spec.load_benchmark(root)
    bench["workloads"].append(candidate_entry())
    bench["per_layer"] += candidate_metrics(bench)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=2)


@pytest.fixture
def candidate_root(tmp_path):
    """A checkout of the benchmark as it stands (the program's ``src``
    linked in) with the candidate cell added."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "test*",
                                                  "conftest.py"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(spec.ROOT, "src"), root / "src")
    add_candidate(str(root))
    return str(root)
