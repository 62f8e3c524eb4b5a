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


def _write(root, rel, doc):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)


def _today(cell):
    """The per-layer metrics whose own ``workloads`` list names ``cell``:
    what each accepted cell read before this harness ran sharded cells."""
    return [m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", [cell])]


@pytest.mark.parametrize("change, error", [
    # a one-chip cell of the four-chip configuration
    ({"workload": {"chips": 1}}, ValueError),
    # a four-chip cell of a one-chip configuration
    ({"workload": {"config": "pcg1g-nvmprd"}}, ValueError),
    # a shard failure on a one-chip cell
    ({"workload": {"config": "pcg1g-nvmprd", "chips": 1}}, ValueError),
    # a failure of a unit the generator does not know
    ({"traffic": {"unit": "rack"}}, ValueError),
    # a per-layer metric of the cell that no reader serves
    ({"metric": "no_such_metric.sharded"}, FileNotFoundError),
])
def test_load_cell_refuses_a_cell_its_layout_cannot_run(candidate_root,
                                                       change, error):
    from bench.conftest import CANDIDATE

    spec.load_cell(CANDIDATE, root=candidate_root)
    bench = spec.load_benchmark(candidate_root)
    bench["workloads"][-1].update(change.get("workload", {}))
    if "traffic" in change:
        traffic = spec.load_traffic("shardkill", candidate_root)
        traffic["failures"][0].update(change["traffic"])
        _write(candidate_root, "bench/traffic/shardkill.json", traffic)
    if "metric" in change:
        bench["per_layer"].append(dict(bench["per_layer"][0],
                                       name=change["metric"],
                                       workloads=[CANDIDATE]))
    _write(candidate_root, "BENCHMARK.json", bench)
    with pytest.raises(error):
        spec.load_cell(CANDIDATE, root=candidate_root)


def test_a_shard_failure_draws_one_shard_from_the_seed(candidate_root):
    from bench.conftest import CANDIDATE

    cell = spec.load_cell(CANDIDATE, root=candidate_root)
    assert cell.chips == cell.config["chips"] == 4

    def draw(seed):
        rng = np.random.default_rng(seed)
        rhs_key = int(rng.integers(2**31))
        return rhs_key, spec.draw_blocks(cell.traffic, 32, rng, cell.chips)

    draws = [draw(2**31 + 9 + s) for s in range(40)]
    assert draws == [draw(2**31 + 9 + s) for s in range(40)]
    assert {d[1][0] for d in draws} == {0, 1, 2, 3}
    (ev,) = spec.failure_events(cell.traffic, 13, draws[0][1])
    assert ev.at_iteration == 6 and ev.shard == draws[0][1][0]
    assert ev.blocks == () and not ev.storage and ev.loses_blocks
    (warm,) = spec.warmup_events(cell.traffic, draws[0][1])
    assert warm.at_iteration == 2 and warm.shard == ev.shard


#: (rhs_key, draws) of the accepted cells, as the generator gave them
#: before a failure could kill a shard
ACCEPTED_DRAWS = {1: (1016164991, [(4,)]),
                  2**31 + 3: (985173613, [(5,)]),
                  2147484003: (1690033565, [(3,)]),
                  2**33 + 5: (1702598247, [(1,)])}


@pytest.mark.parametrize("seed", sorted(ACCEPTED_DRAWS))
@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_the_accepted_cells_draw_what_they_drew(workload, seed):
    cell = spec.load_cell(workload)
    rng = np.random.default_rng(seed)
    rhs_key = int(rng.integers(2**31))
    draws = spec.draw_blocks(cell.traffic, cell.config["nblocks"], rng,
                             cell.chips)
    key, blocks = ACCEPTED_DRAWS[seed]
    assert rhs_key == key
    assert draws == blocks[:len(cell.traffic["failures"])]
    for ev in spec.failure_events(cell.traffic, 17, draws):
        assert ev.shard is None and ev.blocks in blocks


#: each roofline on the recorded TPU trace at one chip's share of the
#: step (65536 f64 unknowns, 8 blocks, one 6+2 encode), as the readers
#: computed it before they knew the chip count
ROOFLINE_ONE_CHIP = {"step_hbm_roofline": 89.01362060110611,
                     "gf256_encode_roofline": 33.78000340025656}


@pytest.mark.parametrize("metric", sorted(ROOFLINE_ONE_CHIP))
def test_rooflines_count_one_chips_share(metric):
    from bench import trace_reduce
    from bench.run import RunView
    from bench.test_trace_reduce import TPU_TRACE

    trace = trace_reduce.load(TPU_TRACE)
    encode = {"type": "span", "name": "gf256.rs_encode", "ts": 0.0,
              "dur": 1e-3, "depth": 0, "args": {}}

    def read(n, nblocks, chips):
        return spec.load_reader(metric)(RunView(
            trace=trace, records=[encode],
            config={"stripe_data": 6, "stripe_parity": 2}, iterations=2,
            n=n, nblocks=nblocks, itemsize=8, device_kind="TPU v5 lite",
            recoveries=0, chips=chips))

    assert read(65536, 8, 1) == ROOFLINE_ONE_CHIP[metric]
    four = read(4 * 65536, 32, 4)
    if metric == "step_hbm_roofline":
        # four chips, each stepping the same share in the recorded time:
        # the same reading, a quarter of the whole problem's bytes over
        # one chip's time
        assert four == pytest.approx(ROOFLINE_ONE_CHIP[metric], rel=1e-12)
        assert four == pytest.approx(read(4 * 65536, 32, 1) / 4, rel=1e-12)
    else:
        # the encode takes the whole stripe on the one device that ran
        # it: four times the bytes in the recorded kernel time
        assert four == pytest.approx(4 * ROOFLINE_ONE_CHIP[metric],
                                     rel=1e-12)


def test_accepted_cells_read_the_metrics_they_read():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert [m["name"] for m in cell.per_layer] == _today(w["name"])
        for m in cell.per_layer:
            assert spec.reader_of(m["name"]) == m["name"]


def test_a_new_cell_names_existing_metrics_without_editing_them(
        candidate_root):
    from bench.conftest import CANDIDATE, CANDIDATE_METRICS, candidate_entry

    doc = spec.load_benchmark(candidate_root)
    # one new workloads entry and new per-layer entries after the
    # existing ones; every existing entry as it was
    assert doc["workloads"][:-1] == BENCH["workloads"]
    assert doc["workloads"][-1] == candidate_entry()
    old = len(BENCH["per_layer"])
    assert doc["per_layer"][:old] == BENCH["per_layer"]
    assert {k: v for k, v in doc.items()
            if k not in ("workloads", "per_layer")} == {
        k: v for k, v in BENCH.items() if k not in ("workloads", "per_layer")}
    cell = spec.load_cell(CANDIDATE, root=candidate_root)
    assert cell.per_layer == doc["per_layer"][old:]
    # each new entry is an existing metric, read by that metric's reader
    assert [spec.reader_of(m["name"], candidate_root)
            for m in cell.per_layer] == CANDIDATE_METRICS
    assert {"step_hbm_roofline", "idle_share"} <= set(CANDIDATE_METRICS)
    assert [m["name"] for m in cell.end_to_end] == ["iter_ms", "setup_s"]
    for w in BENCH["workloads"]:
        old = spec.load_cell(w["name"], root=candidate_root)
        assert [m["name"] for m in old.per_layer] == _today(w["name"])
