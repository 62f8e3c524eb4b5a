"""The harness command end to end on the host CPU: ``--rehearse`` runs a
tiny grid with the cell's block count, on as many virtual devices as the
cell has chips, and prints a well-formed result line; without it, a CPU
run exits non-zero and prints no result."""
import json
import os
import subprocess
import sys

import pytest

from bench import spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def run_bench(args, cache_dir, timeout=300, root=spec.ROOT):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         *map(str, args)], capture_output=True, text=True, env=env,
        timeout=timeout, cwd=root)


def last_json(res):
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_prints_a_correct_result_line(workload, cache_dir):
    out = last_json(run_bench(["--workload", workload, "--seed", 2**31 + 3,
                               "--seconds", 0.5, "--trace", 0, "--rehearse"],
                              cache_dir))
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 4
    cell = spec.load_cell(workload)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    for name, m in out["metrics"].items():
        assert m["value"] > 0 and m["unit"]
    assert out["device"]["platform"] == "cpu"


def test_traced_rehearsal_reads_the_program_spans(cache_dir):
    out = last_json(run_bench(["--workload", "pcg1g-x6p2.kill-prd",
                               "--seed", 8, "--seconds", 0.5, "--trace", 1,
                               "--rehearse"], cache_dir))
    assert out["correct"] is True
    # the CPU trace has no TPU plane: the device readers stay silent
    assert set(out["metrics"]) == {"recovery_fetch_s",
                                   "recovery_reconstruct_s"}
    assert {"busy_s", "window_s"} <= set(out["device"])


def test_a_cpu_run_without_the_rehearsal_switch_fails(cache_dir):
    res = run_bench(["--workload", CELLS[0], "--seed", 1, "--seconds", 1,
                     "--trace", 0], cache_dir)
    assert res.returncode != 0
    assert "{" not in res.stdout


@pytest.mark.parametrize("trace", [0, 1])
def test_a_sharded_rehearsal_kills_a_shard_and_recovers(candidate_root,
                                                       cache_dir, trace):
    from bench.conftest import CANDIDATE

    out = last_json(run_bench(["--workload", CANDIDATE, "--seed",
                               2**31 + 17, "--seconds", 0.5, "--trace",
                               trace, "--rehearse"], cache_dir,
                              root=candidate_root))
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["checks"]["recovered"]["value"] == 0
    assert out["device"]["count"] == 4
    assert len(out["device"]["memory_peak_bytes_by_device"]) == 4
    assert out["device"]["memory_peak_bytes"] == max(
        out["device"]["memory_peak_bytes_by_device"])
    # the CPU trace has no TPU plane: of the metrics the cell reads, the
    # recovery spans read, the device and host-phase readers stay silent
    spans = {"recovery_fetch_s", "recovery_reconstruct_s"}
    assert set(out["metrics"]) == ({"iter_ms", "setup_s"} if trace == 0
                                   else {f"{m}.{CANDIDATE}" for m in spans})
