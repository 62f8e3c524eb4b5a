#!/usr/bin/env python3
"""Compare the program's span records with the same spans on the
profiler's host plane, for a run kept with ``bench/run.py --keep-trace
DIR`` (``<stem>.xplane.pb`` and ``<stem>.records.json``).

    python3 bench/span_clock.py DIR/pcg1g-nvmprd.kill.7 [--rel 0.01 --abs-us 50]

Each span opens a profiler annotation of its own name, so the host
plane holds one event per span record, in the same order.  Prints one
JSON object: how many spans matched, the largest difference of their
durations, how many differ by more than ``--rel`` and ``--abs-us``
together, and how far the harness's offset map of tracer seconds onto
the profiler's clock (``run._clock_map``, from the window annotation)
places each span's start from its native start.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from typing import Dict, List, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

Interval = Tuple[float, float, str]


def match_spans(records: Sequence[Dict],
                host: Sequence[Interval]) -> List[Tuple[Dict, Interval]]:
    """Pair every span record with its host-plane event: both sorted by
    start, the events filtered to the span names.  Raises ValueError
    where the two sequences differ in length or in a name."""
    spans = sorted((r for r in records if r["type"] == "span"),
                   key=lambda r: r["ts"])
    names = {r["name"] for r in spans}
    events = sorted((h for h in host if h[2] in names), key=lambda h: h[0])
    if len(spans) != len(events):
        raise ValueError(f"{len(spans)} span records but {len(events)} "
                         f"host-plane events of their names")
    for i, (r, h) in enumerate(zip(spans, events)):
        if r["name"] != h[2]:
            raise ValueError(f"span {i}: record {r['name']!r} but host "
                             f"event {h[2]!r}")
    return list(zip(spans, events))


def summarize(pairs, map_ns, rel: float, abs_us: float) -> Dict:
    diffs = [abs((h[1] - h[0]) * 1e-9 - r["dur"]) for r, h in pairs]
    off = [(map_ns(r["ts"]) - h[0]) * 1e-3 for r, h in pairs]
    over = sum(1 for (r, _), d in zip(pairs, diffs)
               if d > rel * r["dur"] and d > abs_us * 1e-6)
    return {
        "spans": len(pairs),
        "max_dur_diff_us": 1e6 * max(diffs, default=0.0),
        "outside_limit": over,
        "limit": {"rel": rel, "abs_us": abs_us},
        "clock_map_offset_us": {
            "median": statistics.median(off) if off else None,
            "min": min(off, default=None), "max": max(off, default=None)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("stem", help="DIR/<workload>.<seed> of --keep-trace")
    ap.add_argument("--rel", type=float, default=0.01)
    ap.add_argument("--abs-us", type=float, default=50.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import run, trace_reduce

    with open(args.stem + ".records.json") as f:
        kept = json.load(f)
    dtrace = trace_reduce.load(args.stem + ".xplane.pb")
    pairs = match_spans(kept["records"], dtrace.host)
    out = summarize(pairs, run._clock_map(dtrace, kept["tracer_t0"],
                                          kept["window_t0"]),
                    args.rel, args.abs_us)
    print(json.dumps(out))
    return 0 if out["outside_limit"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
