"""Find a cell's configuration, traffic, reference and metric readers
by the names ``BENCHMARK.json`` gives them.

Each kind lives in a directory of its own beside this file:

- ``configs/<config>.json`` — the deployment: grid, blocks, precision,
  solver, resilience spec, the guarantee, the reference's name and the
  limits of the comparison that decides ``correct``;
- ``traffic/<traffic>.json`` — the failure campaign as fractions of the
  window's iterations (:func:`failure_events` turns it into events);
- ``references/<reference>.py`` — the plain reference, numpy only;
- ``metrics/<metric>.py`` — one per-layer metric reader, a ``read(run)``
  that returns a number or None when it finds nothing to read.

Adding a cell, a configuration, a mix or a metric takes a new file and
a new ``BENCHMARK.json`` entry; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

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


def _load_module(kind: str, name: str, root: str = ROOT):
    path = os.path.join(root, "bench", kind, f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    if mod_spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reference(name: str, root: str = ROOT):
    return _load_module("references", name, root)


def load_reader(name: str, root: str = ROOT):
    """The ``read(run)`` function of per-layer metric ``name``."""
    return _load_module("metrics", name, root).read


@dataclass(frozen=True)
class Cell:
    """One ``workloads`` entry with what it names, loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    names = metric.get("workloads")
    return names is None or cell in names


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_config(w["config"], root),
        traffic=load_traffic(w["traffic"], root),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


@dataclass(frozen=True)
class PlannedEvent:
    """One failure of the window: at which iteration, which blocks, and
    whether a storage child dies with them."""

    at_iteration: int
    blocks: tuple
    storage: bool


def draw_blocks(traffic: Dict[str, Any], nblocks: int,
                rng: np.random.Generator) -> List[tuple]:
    """For each of the traffic's failures, the ``blocks`` distinct
    blocks it kills, drawn by the seed's generator."""
    return [tuple(sorted(int(b) for b in rng.choice(
        nblocks, size=int(ev.get("blocks", 1)), replace=False)))
        for ev in traffic["failures"]]


def failure_events(traffic: Dict[str, Any], iterations: int,
                   blocks: List[tuple]) -> List[PlannedEvent]:
    """The traffic's failures for a window of ``iterations``: each lands
    at its fraction of the window, never before iteration 2 (the first
    complete recovery point), on its drawn ``blocks``."""
    return [PlannedEvent(max(2, int(round(float(ev["at"]) * iterations))),
                         blocks[i], bool(ev.get("storage", False)))
            for i, ev in enumerate(traffic["failures"])]


def warmup_events(traffic: Dict[str, Any],
                  blocks: List[tuple]) -> List[PlannedEvent]:
    """One failure of each kind the traffic holds, at iteration 2 of the
    warm-up solve, on the blocks the window will kill, so the window
    meets no program it has not compiled."""
    seen = {}
    for i, ev in enumerate(traffic["failures"]):
        kind = (int(ev.get("blocks", 1)), bool(ev.get("storage", False)))
        seen.setdefault(kind, PlannedEvent(2, blocks[i], kind[1]))
    return list(seen.values())


def optional(value: Optional[float]) -> Optional[float]:
    """A reader's value, or None where it found nothing: never NaN."""
    if value is None or not np.isfinite(value):
        return None
    return float(value)
