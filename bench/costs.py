"""The yardstick's arithmetic: chip peaks and the least bytes a call
must move, from its shapes.

Least bytes count what any implementation has to read and write, so a
share of the roofline built on them stays under 100% however the
program fuses its work.  A reader sets them against the device time of
the same work: a sharded step's time is per chip and its bytes are one
chip's share (``n / chips`` unknowns).
"""
from __future__ import annotations

import json
import os
from typing import Dict

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind`` (as JAX names
    it).  A kind missing from the table is an error, not a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table knows {sorted(table)}")
    return table[device_kind]


def pcg_step_least_bytes(n: int, itemsize: int) -> int:
    """One PCG iteration: x, r and p each read once and written once
    (the stencil reads p, and z = r / 6 needs no vector of its own).
    The constant Jacobi diagonal counts nothing."""
    return 6 * n * itemsize


def gf256_encode_least_bytes(nblocks: int, block_size: int, k_data: int,
                             nparity: int, itemsize: int) -> int:
    """One stripe encode of one vector: the K data chunks read and the P
    parity chunks written.  Each block's slice is split into K chunks of
    ``ceil(block_size / K)`` values."""
    chunk = -(-block_size // k_data)
    return (k_data + nparity) * nblocks * chunk * itemsize
