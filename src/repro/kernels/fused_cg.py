"""Pallas TPU kernel: fused PCG vector update (lines 4-7a of Algorithm 1).

CG's per-iteration vector work is HBM-bandwidth-bound (arithmetic
intensity < 1 flop/byte).  Executed as separate XLA ops, the update
reads/writes each of ``x, r, z`` plus ``p, ap`` several times:

    x' = x + a p; r' = r - a ap; z' = M^{-1} r'; rz' = <r', z'>
    (>= 9n reads + 3n writes as 4 standalone ops)

This kernel performs all four in **one pass over VMEM tiles**: 5n reads +
3n writes (the theoretical minimum with a fused reduction), a ~1.5x cut
of HBM traffic on the dominant term of the solver roofline.  The dual
reduction is accumulated per-tile into one lane-wide (1, 128) partials
row (hierarchical reduction: VREG -> VMEM partial row -> tiny jnp.sum
epilogue); ``alpha`` rides in SMEM.

Layout: inputs are viewed as ``(m, 128)`` — lane-aligned for the VPU;
``bm`` rows per tile (sublane-multiple).  ``inv_diag`` supports any
diagonal preconditioner (Jacobi); pass ones for plain CG.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gf256_encode import gf_xtime_packed
from repro.kernels.tiling import LANES, block_at, reject_f64

#: default row-tile cap: tiles never exceed this many (·, 128) rows
DEFAULT_BM = 256


def largest_divisor_bm(m: int, cap: int = DEFAULT_BM) -> int:
    """The largest divisor of ``m`` that is <= ``cap`` (>= 1 always):
    the auto block-rows choice, so every lane-aligned ``n`` gets a
    legal tiling instead of a divisibility error."""
    bm = min(cap, m)
    while m % bm:
        bm -= 1
    return bm


def _fused_cg_kernel(x_ref, r_ref, p_ref, ap_ref, inv_ref, alpha_ref,
                     xo_ref, ro_ref, zo_ref, partial_ref):
    """The update body both kernels share: ``x' = x + a p``, ``r' = r - a ap``,
    ``z' = r' * inv``, and this tile's lane-wise ``<r', z'>`` partials
    (one (1, 128) row; the ``jnp.sum`` epilogue finishes the dot)."""
    alpha = alpha_ref[0, 0]
    p = p_ref[...]
    ap = ap_ref[...]
    xn = x_ref[...] + alpha * p
    rn = r_ref[...] - alpha * ap
    zn = rn * inv_ref[...]
    xo_ref[...] = xn
    ro_ref[...] = rn
    zo_ref[...] = zn
    # fp32 accumulation for the dual reduction (bf16 partial sums of
    # near-cancelling terms would destroy CG's beta)
    prod = rn.astype(jnp.float32) * zn.astype(jnp.float32)
    partial_ref[0] = jnp.sum(prod, axis=0, keepdims=True)


def _alpha_operand(alpha, dtype):
    """``alpha`` as the (1, 1) SMEM scalar both kernels read."""
    return jnp.broadcast_to(jnp.asarray(alpha, dtype), (1, 1))


_ALPHA_SPEC = pl.BlockSpec((1, 1), block_at(2, axis=None),
                           memory_space=pltpu.SMEM)


def fused_cg_update_pallas(
    x: jax.Array,
    r: jax.Array,
    p: jax.Array,
    ap: jax.Array,
    alpha: jax.Array,
    inv_diag: jax.Array,
    bm: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-pass fused CG update; returns (x', r', z', rz').

    ``bm=None`` (the default) picks the largest divisor of the row
    count ``m = n // 128`` not exceeding :data:`DEFAULT_BM`, so any
    lane-aligned ``n`` tiles legally (e.g. ``n = 384*128`` -> bm=192).
    An explicit ``bm`` that does not divide ``m`` still raises — that
    is a caller bug, not a size to silently repair.
    """
    if not interpret:
        reject_f64("fused_cg_update_pallas", x, r, p, ap, inv_diag)
    n = x.shape[0]
    if n % LANES != 0:
        raise ValueError(f"n={n} must be a multiple of {LANES}")
    m = n // LANES
    if bm is None:
        bm = largest_divisor_bm(m)
    else:
        bm = min(bm, m)
        if m % bm != 0:
            raise ValueError(
                f"rows m={m} not divisible by block rows bm={bm}")
    grid = m // bm

    def as2d(v):
        return v.reshape(m, LANES)

    vec_spec = pl.BlockSpec((bm, LANES), block_at(2))

    xo, ro, zo, partials = pl.pallas_call(
        _fused_cg_kernel,
        grid=(grid,),
        in_specs=[vec_spec] * 5 + [_ALPHA_SPEC],
        out_specs=[
            vec_spec, vec_spec, vec_spec,
            pl.BlockSpec((1, 1, LANES), block_at(3)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, LANES), x.dtype),
            jax.ShapeDtypeStruct((m, LANES), x.dtype),
            jax.ShapeDtypeStruct((m, LANES), x.dtype),
            jax.ShapeDtypeStruct((grid, 1, LANES), jnp.float32),
        ],
        name="fused_cg_update",
        interpret=interpret,
    )(as2d(x), as2d(r), as2d(p), as2d(ap), as2d(inv_diag),
      _alpha_operand(alpha, x.dtype))

    rz = jnp.sum(partials).astype(x.dtype)  # tiny fp32 epilogue
    return xo.reshape(n), ro.reshape(n), zo.reshape(n), rz


# ----------------------------------------------------------------------
# Fused persist staging (DESIGN.md §13): the update pass already holds
# every vector the PCG recovery schema needs (the search direction ``p``
# is one of its five reads), so the erasure stripe's staging work —
# chunking ``p`` block-wise into K shards and deriving the P/Q parity
# bytes — can ride the same tile pass instead of a separate host-side
# numpy pass.  The emitted chunk and parity layouts are byte-identical
# to ``ErasureSession._shards`` + ``gf256.rs_encode``.
# ----------------------------------------------------------------------
def _make_persist_kernel(k_data: int, nparity: int, chunk_rows: int):
    def kernel(x_ref, r_ref, p_ref, ap_ref, inv_ref, alpha_ref,
               xo_ref, ro_ref, zo_ref, partial_ref, ch_ref, par_ref):
        _fused_cg_kernel(x_ref, r_ref, p_ref, ap_ref, inv_ref, alpha_ref,
                         xo_ref, ro_ref, zo_ref, partial_ref)
        # --- staging free rider: this tile IS one partition block of
        # p, and stripe chunk j is its row band j (whole 128-lane rows)
        words = []
        for j in range(k_data):
            cj = p_ref[j * chunk_rows:(j + 1) * chunk_rows, :]
            ch_ref[0, j] = cj
            # the chunk's bytes as packed uint32 words; bytewise XOR and
            # xtime never cross a byte, so byte order is preserved
            w = jax.lax.bitcast_convert_type(cj, jnp.uint32)
            words.append(w.reshape(chunk_rows, -1))
        pp = words[0]
        for w in words[1:]:
            pp = pp ^ w
        par_ref[0, 0] = pp
        if nparity == 2:
            # Q = sum_j g^j d_j by Horner's rule from the last shard
            q = words[-1]
            for w in reversed(words[:-1]):
                q = gf_xtime_packed(q) ^ w
            par_ref[0, 1] = q

    return kernel


def fused_cg_update_persist_pallas(
    x: jax.Array,
    r: jax.Array,
    p: jax.Array,
    ap: jax.Array,
    alpha: jax.Array,
    inv_diag: jax.Array,
    *,
    nblocks: int,
    k_data: int,
    nparity: int,
    interpret: bool = False,
):
    """Fused CG update + erasure persist staging in one tile pass.

    Returns ``(x', r', z', rz', chunks, parity)`` where ``chunks`` is a
    ``(nblocks, k_data, chunk)`` array of ``p``'s stripe chunks (chunk
    ``j`` of the full vector is ``chunks[:, j, :].reshape(-1)``) and
    ``parity`` a ``(nblocks, nparity, chunk*itemsize)`` uint8 array of
    the P/Q parity bytes, both byte-identical to what
    ``ErasureSession._shards`` computes from the same ``p``.

    The grid runs one partition block per step (tile rows =
    ``block_size // 128``), so the stripe chunking aligns with the
    update tiling; sizes that break that alignment (``128 ∤
    block_size`` or chunks that are not whole 128-lane rows) raise and
    callers fall back to the unfused path (DESIGN.md §13).
    """
    from repro.nvm import gf256

    if not interpret:
        reject_f64("fused_cg_update_persist_pallas", x, r, p, ap, inv_diag)
    n = x.shape[0]
    if n % nblocks != 0:
        raise ValueError(f"n={n} not divisible by nblocks={nblocks}")
    bs = n // nblocks
    if bs % LANES != 0:
        raise ValueError(
            f"block_size={bs} must be a multiple of {LANES} for the "
            f"fused persist pass")
    rb = bs // LANES
    if rb % k_data != 0:
        raise ValueError(
            f"block rows {rb} (block_size={bs}) not divisible by "
            f"k_data={k_data}: the fused pass stages whole 128-lane "
            f"rows per stripe chunk")
    itemsize = jnp.dtype(x.dtype).itemsize
    if itemsize not in (4, 8):
        raise ValueError(
            f"the fused persist pass packs 4- or 8-byte values into "
            f"uint32 words, got {jnp.dtype(x.dtype).name}")
    gf256.vandermonde(nparity, k_data)
    chunk_rows = rb // k_data
    words_per_row = LANES * itemsize // 4
    m = n // LANES

    def as2d(v):
        return v.reshape(m, LANES)

    vec_spec = pl.BlockSpec((rb, LANES), block_at(2))

    xo, ro, zo, partials, chunks, parity = pl.pallas_call(
        _make_persist_kernel(k_data, nparity, chunk_rows),
        grid=(nblocks,),
        in_specs=[vec_spec] * 5 + [_ALPHA_SPEC],
        out_specs=[
            vec_spec, vec_spec, vec_spec,
            pl.BlockSpec((1, 1, LANES), block_at(3)),
            pl.BlockSpec((1, k_data, chunk_rows, LANES),
                         block_at(4)),
            pl.BlockSpec((1, nparity, chunk_rows, words_per_row),
                         block_at(4)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, LANES), x.dtype),
            jax.ShapeDtypeStruct((m, LANES), x.dtype),
            jax.ShapeDtypeStruct((m, LANES), x.dtype),
            jax.ShapeDtypeStruct((nblocks, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, k_data, chunk_rows, LANES),
                                 x.dtype),
            jax.ShapeDtypeStruct((nblocks, nparity, chunk_rows,
                                  words_per_row), jnp.uint32),
        ],
        name="fused_cg_update_persist",
        interpret=interpret,
    )(as2d(x), as2d(r), as2d(p), as2d(ap), as2d(inv_diag),
      _alpha_operand(alpha, x.dtype))

    rz = jnp.sum(partials).astype(x.dtype)
    chunk = bs // k_data
    parity = jax.lax.bitcast_convert_type(parity, jnp.uint8).reshape(
        nblocks, nparity, chunk * itemsize)
    return (xo.reshape(n), ro.reshape(n), zo.reshape(n), rz,
            chunks.reshape(nblocks, k_data, chunk), parity)


def fused_pass_traffic(n: int, itemsize: int, k_data: int,
                       nparity: int) -> dict:
    """HBM traffic accounting of the fused update+staging pass (the
    roofline's persist-bandwidth term): the bare update moves 5n reads
    + 3n writes; fused staging adds the chunk emission (n values) and
    the parity emission (n * P/K values) as extra writes — the encode
    *reads* ride for free on the p read the update already does."""
    update_read = 5 * n * itemsize
    update_write = 3 * n * itemsize
    staged_write = n * itemsize + (n * itemsize * nparity) // k_data
    total = update_read + update_write + staged_write
    return {
        "update_read_bytes": update_read,
        "update_write_bytes": update_write,
        "staged_write_bytes": staged_write,
        "total_bytes": total,
        # share of the fused pass's HBM traffic that is persist staging
        "persist_bw_fraction": staged_write / total,
        # what a standalone staging pass would add: re-read the vector
        # (n) plus the same writes — the traffic the fusion removes
        "unfused_extra_read_bytes": n * itemsize,
    }
