"""Pallas TPU kernel: tiled GF(2^8) Reed-Solomon P/Q parity encode.

The erasure backend's stripe write (DESIGN.md §8) splits every slot
vector into K data chunks and derives P parity chunks (P ∈ {1, 2}) with
:func:`repro.nvm.gf256.rs_encode` — a numpy table-lookup pass that runs
entirely outside the compute stream, reading the K chunks once per
parity row.  This kernel fuses both parity rows into **one read of the
data**: each grid step pulls a ``(K, bm, 128)`` tile of packed bytes
into VMEM and emits the matching P and Q tiles together.

Bytes travel packed four to a ``uint32`` word (the TPU's vector unit
has no byte lanes worth using, and no 1-D table gathers), and every
operation is bytewise — no carry ever crosses a byte — so byte order
inside a word never matters:

- P parity is the plain XOR of the K shards (Vandermonde row 0 is all
  ones);
- Q parity is ``sum_j g^j d_j`` with ``g = 2``, evaluated by Horner's
  rule from the last shard, ``q = xtime(q) ^ d_j``, where ``xtime`` is
  multiplication by 2 modulo the field polynomial 0x11D on four packed
  bytes at once (the Linux RAID-6 construction): shift every byte left
  and XOR 0x1D into the bytes whose top bit fell off.

Multiplying by 2 under 0x11D is exactly ``gf256.gf_mul(2, .)``, so the
parity bytes are **bit-identical** to :func:`repro.nvm.gf256.rs_encode`,
which stays the test oracle (``tests/test_gf256_encode.py`` sweeps
K ∈ {2,..,6}, P ∈ {1,2} and ragged tails).

Backends never call this module directly: dispatch goes through
:func:`repro.kernels.ops.rs_encode` (the registered fused-persist
toggle), which repro-lint rule RL204 enforces.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.tiling import LANES, block_at
from repro.nvm import gf256

#: default packed-word rows per grid step ((bm, 128) uint32 = 128 KB per
#: shard)
DEFAULT_BM = 256

_HIGH_BITS = 0x80808080
_LOW_SEVEN = 0x7F7F7F7F


def gf_xtime_packed(w: jax.Array) -> jax.Array:
    """Multiply each of the four bytes packed in every uint32 of ``w``
    by 2 in GF(2^8) (polynomial 0x11D): shift left within the byte and
    fold 0x1D = x^4 + x^3 + x^2 + 1 into bytes whose top bit overflowed."""
    top = (w & jnp.uint32(_HIGH_BITS)) >> 7           # 0 or 1 per byte
    shifted = (w & jnp.uint32(_LOW_SEVEN)) << 1
    return shifted ^ (top << 4) ^ (top << 3) ^ (top << 2) ^ top


def _make_encode_kernel(k_data: int, nparity: int):
    """Build the tile kernel for a static (K, P) stripe shape."""

    def kernel(d_ref, *out_refs):
        p = d_ref[0]                         # (bm, LANES) packed bytes
        for j in range(1, k_data):
            p = p ^ d_ref[j]
        out_refs[0][...] = p
        if nparity == 2:
            q = d_ref[k_data - 1]
            for j in range(k_data - 2, -1, -1):
                q = gf_xtime_packed(q) ^ d_ref[j]
            out_refs[1][...] = q

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("nparity", "bm", "interpret"))
def _encode_tiles(words: jax.Array, nparity: int, bm: int, interpret: bool):
    """P/Q parity words of ``words``, a ``(K, m, 128)`` uint32 array of
    packed shard bytes, tiled ``bm`` rows per grid step."""
    k_data, m, _ = words.shape
    tile = pl.BlockSpec((k_data, bm, LANES), block_at(3, axis=1))
    out_spec = pl.BlockSpec((bm, LANES), block_at(2))
    return pl.pallas_call(
        _make_encode_kernel(k_data, nparity),
        grid=(m // bm,),
        in_specs=[tile],
        out_specs=[out_spec] * nparity,
        out_shape=[jax.ShapeDtypeStruct((m, LANES), jnp.uint32)] * nparity,
        name="gf256_rs_encode",
        interpret=interpret,
    )(words)


def gf256_rs_encode_pallas(data: Sequence[np.ndarray], nparity: int,
                           bm: int = DEFAULT_BM,
                           interpret: bool = False) -> List[np.ndarray]:
    """Drop-in for :func:`repro.nvm.gf256.rs_encode`: ``nparity``
    parity shards over equal-length uint8 data shards, both parities
    emitted from a single tiled read of the data.

    Ragged lengths are zero-padded up to the tile grid internally
    (parity of zero bytes is zero on both rows) and sliced back, so the
    returned shards are bit-identical to the numpy reference for any
    length.
    """
    shards = [np.ascontiguousarray(d, dtype=np.uint8).reshape(-1)
              for d in data]
    if len({s.shape for s in shards}) != 1:
        raise ValueError(
            f"data shards must share one shape, got "
            f"{[s.shape for s in shards]}")
    # same arity validation (and error text) as the numpy reference
    gf256.vandermonde(nparity, len(shards))
    n = shards[0].size
    tile_bytes = bm * LANES * 4
    padded = max(tile_bytes, -(-n // tile_bytes) * tile_bytes)
    arr = np.zeros((len(shards), padded), dtype=np.uint8)
    for j, s in enumerate(shards):
        arr[j, :n] = s
    words = arr.view(np.uint32).reshape(len(shards), -1, LANES)
    out = _encode_tiles(jnp.asarray(words), nparity=nparity, bm=bm,
                        interpret=interpret)
    return [np.asarray(o).view(np.uint8).reshape(-1)[:n].copy()
            for o in out]
