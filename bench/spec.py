"""Find a cell's configuration, traffic, reference and metric readers
by the names ``BENCHMARK.json`` gives them.

Each kind lives in a directory of its own beside this file:

- ``configs/<config>.json`` — the deployment: grid, blocks, precision,
  solver, resilience spec, the guarantee, the reference's name and the
  limits of the comparison that decides ``correct``.  Its ``chips`` is
  the number of devices the problem is laid over, one shard of whole
  blocks each (1: the unsharded solve), and a cell's ``chips`` has to
  equal it;
- ``traffic/<traffic>.json`` — the failure campaign as fractions of the
  window's iterations (:func:`failure_events` turns it into events).
  A failure kills ``blocks`` blocks drawn from the seed, or, with
  ``"unit": "shard"``, every block of one shard drawn from the seed
  (only on a cell of more than one chip);
- ``references/<reference>.py`` — the plain reference, numpy only;
- ``metrics/<metric>.py`` — one per-layer metric reader, a ``read(run)``
  that returns a number or None when it finds nothing to read.  A
  metric named ``<metric>.<variant>`` with no reader of its own is read
  by ``<metric>``'s: a new cell reads an existing metric through a new
  ``per_layer`` entry of that name whose ``workloads`` list names it,
  and no existing entry changes.

Adding a cell, a configuration, a mix or a metric takes a new file and
a new ``BENCHMARK.json`` entry; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Union

import numpy as np

#: the checkout: ``BENCHMARK.json`` and this ``bench/`` directory
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(kind: str, name: str, root: str = ROOT) -> Dict[str, Any]:
    path = os.path.join(root, "bench", kind, f"{name}.json")
    with open(path) as f:
        doc = json.load(f)
    if doc.get("name") != name:
        raise ValueError(f"{path} names itself {doc.get('name')!r}, "
                         f"not {name!r}")
    return doc


def load_config(name: str, root: str = ROOT) -> Dict[str, Any]:
    return _load_json("configs", name, root)


def load_traffic(name: str, root: str = ROOT) -> Dict[str, Any]:
    return _load_json("traffic", name, root)


def _module_path(kind: str, name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", kind, f"{name}.py")


def _load_module(kind: str, name: str, root: str = ROOT):
    path = _module_path(kind, name, root)
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    if mod_spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reference(name: str, root: str = ROOT):
    return _load_module("references", name, root)


def reader_of(name: str, root: str = ROOT) -> str:
    """The reader of per-layer metric ``name``: its own, or, for a
    ``<metric>.<variant>`` without one, ``<metric>``'s."""
    if os.path.exists(_module_path("metrics", name, root)):
        return name
    return name.split(".")[0]


def load_reader(name: str, root: str = ROOT):
    """The ``read(run)`` function of per-layer metric ``name``."""
    return _load_module("metrics", reader_of(name, root), root).read


@dataclass(frozen=True)
class Cell:
    """One ``workloads`` entry with what it names, loaded.  ``chips`` is
    also the number of shards the problem is laid over."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    names = metric.get("workloads")
    return names is None or cell in names


#: what one traffic failure kills: ``blocks`` drawn blocks, or one shard
UNITS = ("block", "shard")


def _unit(failure: Dict[str, Any]) -> str:
    return failure.get("unit", "block")


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Cell ``name`` with its configuration, traffic and metrics.
    Refuses a cell whose ``chips`` differs from its configuration's, a
    traffic failure of an unknown unit, a shard failure on a cell of
    one chip, and a per-layer metric of the cell that has no reader."""
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    chips = int(w["chips"])
    config = load_config(w["config"], root)
    traffic = load_traffic(w["traffic"], root)
    if chips != int(config["chips"]):
        raise ValueError(f"{name}: chips {chips} but its configuration "
                         f"{w['config']!r} lays the problem over "
                         f"{config['chips']} chips")
    for ev in traffic["failures"]:
        if _unit(ev) not in UNITS:
            raise ValueError(f"{name}: traffic {w['traffic']!r} has a "
                             f"failure of unit {_unit(ev)!r}, not one "
                             f"of {UNITS}")
        if _unit(ev) == "shard" and chips == 1:
            raise ValueError(f"{name}: traffic {w['traffic']!r} kills a "
                             f"shard, but the cell runs on one chip")
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    for m in per_layer:
        if not os.path.exists(
                _module_path("metrics", reader_of(m["name"], root), root)):
            raise FileNotFoundError(f"{name}: per-layer metric "
                                    f"{m['name']!r} has no reader")
    return Cell(
        name=name,
        chips=chips,
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=per_layer,
    )


#: one failure's draw: the blocks a block failure kills, or the index of
#: the shard a shard failure kills
Draw = Union[tuple, int]


@dataclass(frozen=True)
class PlannedEvent:
    """One failure of the window: at which iteration, which blocks or
    which shard (every block it owns), and whether a storage child dies
    with them."""

    at_iteration: int
    blocks: tuple
    storage: bool
    shard: Optional[int] = None

    @property
    def loses_blocks(self) -> bool:
        """Whether the event kills blocks, which the solve recovers: a
        shard event counts as one block event."""
        return bool(self.blocks) or self.shard is not None


def draw_blocks(traffic: Dict[str, Any], nblocks: int,
                rng: np.random.Generator, nshards: int = 1) -> List[Draw]:
    """For each of the traffic's failures, in order, what it kills,
    drawn by the seed's generator: the ``blocks`` distinct blocks of a
    block failure as a sorted tuple, or the index of one of ``nshards``
    shards for a failure of ``"unit": "shard"``."""
    return [int(rng.integers(nshards)) if _unit(ev) == "shard"
            else tuple(sorted(int(b) for b in rng.choice(
                nblocks, size=int(ev.get("blocks", 1)), replace=False)))
            for ev in traffic["failures"]]


def _event(at: int, failure: Dict[str, Any], draw: Draw) -> PlannedEvent:
    storage = bool(failure.get("storage", False))
    if _unit(failure) == "shard":
        return PlannedEvent(at, (), storage, shard=draw)
    return PlannedEvent(at, draw, storage)


def failure_events(traffic: Dict[str, Any], iterations: int,
                   blocks: List[Draw]) -> List[PlannedEvent]:
    """The traffic's failures for a window of ``iterations``: each lands
    at its fraction of the window, never before iteration 2 (the first
    complete recovery point), on its drawn blocks or shard."""
    return [_event(max(2, int(round(float(ev["at"]) * iterations))), ev,
                   blocks[i])
            for i, ev in enumerate(traffic["failures"])]


def warmup_events(traffic: Dict[str, Any],
                  blocks: List[Draw]) -> List[PlannedEvent]:
    """One failure of each kind the traffic holds, at iteration 2 of the
    warm-up solve, on the blocks or the shard the window will kill, so
    the window meets no program it has not compiled."""
    seen = {}
    for i, ev in enumerate(traffic["failures"]):
        kind = (_unit(ev), int(ev.get("blocks", 1)),
                bool(ev.get("storage", False)))
        seen.setdefault(kind, _event(2, ev, blocks[i]))
    return list(seen.values())


def optional(value: Optional[float]) -> Optional[float]:
    """A reader's value, or None where it found nothing: never NaN."""
    if value is None or not np.isfinite(value):
        return None
    return float(value)
