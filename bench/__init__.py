"""The on-chip benchmark of the recoverable solve (``BENCHMARK.json``).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell.  Everything that belongs to one
configuration, traffic mix or per-layer metric is a file of its own
under ``configs/``, ``traffic/``, ``metrics/`` and ``references/``,
found by the name ``BENCHMARK.json`` gives it (:mod:`bench.spec`).
"""
