"""Every configuration, traffic mix, reference and per-layer metric
reader that ``BENCHMARK.json`` names loads from its own file, and a new
one is a new file plus a new entry."""
import json
import os
import re
import shutil

import numpy as np
import pytest

from bench import check, costs, spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_loads_what_it_names(workload):
    cell = spec.load_cell(workload)
    cfg = cell.config
    assert NAME.match(cell.name)
    assert cfg["nz"] % cfg["nblocks"] == 0
    assert cfg["nz"] // cfg["nblocks"] == cfg["block_planes"]
    assert {"x_err", "relres_gap", "persist_err"} <= set(cfg["limits"])
    assert hasattr(spec.load_reference(cfg["reference"]), "pcg")
    for ev in cell.traffic["failures"]:
        assert 0.0 < float(ev["at"]) < 1.0
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.load_reader(m["name"]))


@pytest.mark.parametrize("kind", ["configs", "traffic"])
def test_every_data_file_loads_and_names_itself(kind):
    folder = os.path.join(spec.ROOT, "bench", kind)
    names = sorted(f[:-5] for f in os.listdir(folder) if f.endswith(".json"))
    assert names
    load = spec.load_config if kind == "configs" else spec.load_traffic
    for name in names:
        assert load(name)["name"] == name


def test_benchmark_entries_are_well_formed():
    names = set()
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        doc = spec.load_config(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert doc["source"] == c["source"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert doc["deployment"][key] != doc[key], key
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= {w["name"] for w in BENCH["workloads"]}


def test_a_new_cell_is_a_new_file_and_an_entry(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "test*"))
    doc = json.loads(json.dumps(BENCH))
    (root / "bench" / "traffic" / "twice.json").write_text(json.dumps({
        "name": "twice", "failures": [{"at": 0.3, "blocks": 2},
                                      {"at": 0.7, "blocks": 1}]}))
    (root / "bench" / "metrics" / "window_steps.py").write_text(
        "def read(run):\n    return float(run.iterations)\n")
    doc["workloads"].append({"name": "pcg1g-nvmprd.twice",
                             "config": "pcg1g-nvmprd", "traffic": "twice",
                             "chips": 1, "why": "two failures"})
    doc["per_layer"].append({"name": "window_steps", "unit": "steps",
                             "better": "higher", "source": "program_counter",
                             "layer": "solve loop", "moves": "iter_ms",
                             "workloads": ["pcg1g-nvmprd.twice"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    cell = spec.load_cell("pcg1g-nvmprd.twice", root=str(root))
    assert [m["name"] for m in cell.per_layer] == ["window_steps"]
    read = spec.load_reader("window_steps", root=str(root))
    assert read(type("Run", (), {"iterations": 12})()) == 12.0
    blocks = spec.draw_blocks(cell.traffic, 8, np.random.default_rng(5))
    events = spec.failure_events(cell.traffic, 20, blocks)
    assert [(e.at_iteration, len(e.blocks)) for e in events] == [(6, 2),
                                                                (14, 1)]
    assert len(spec.warmup_events(cell.traffic, blocks)) == 2


def test_events_draw_blocks_from_the_seed_alone():
    traffic = spec.load_traffic("kill-prd")
    draws = [spec.draw_blocks(traffic, 8, np.random.default_rng(2**31 + 9))
             for _ in range(2)]
    assert draws[0] == draws[1]
    (ev,) = spec.failure_events(traffic, 13, draws[0])
    assert ev.at_iteration == 6 and ev.storage and len(ev.blocks) == 1
    assert spec.failure_events(traffic, 1, draws[0])[0].at_iteration == 2


@pytest.mark.parametrize("period, iterations, pair", [
    (1, 13, [12, 13]), (20, 13, [0, 1]), (20, 21, [20, 21]),
    (20, 40, [20, 21]), (20, 41, [40, 41])])
def test_durable_pair_follows_the_esrp_schedule(period, iterations, pair):
    assert check.durable_pair(iterations, period) == pair


def test_least_bytes_by_hand():
    # one PCG iteration on 2x3x4 f64 unknowns: x, r, p read and written
    assert costs.pcg_step_least_bytes(24, 8) == 6 * 24 * 8
    # 2 blocks of 10 values, 6+2 stripe: chunks of ceil(10/6) = 2 values
    assert costs.gf256_encode_least_bytes(2, 10, 6, 2, 8) == 8 * 2 * 2 * 8
    peaks = costs.chip_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.chip_peaks("TPU v9 imaginary")


def test_reference_stencil_by_hand():
    ref = spec.load_reference("pcg_stencil7")
    u = np.zeros((3, 3, 3))
    u[1, 1, 1] = 1.0
    out = ref.stencil(u)
    assert out[1, 1, 1] == 6.0
    assert out[0, 1, 1] == out[1, 0, 1] == out[1, 1, 2] == -1.0
    assert np.count_nonzero(out) == 7
    # PCG on a 1x1x1 grid solves 6 x = b in one step
    x, kept = ref.pcg(np.array([3.0]), (1, 1, 1), 1, keep_p=(0,))
    assert x[0] == 0.5 and kept[0][0] == 0.5
