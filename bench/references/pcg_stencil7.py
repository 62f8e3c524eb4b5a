"""Plain reference of the pcg_1g deployments: unprotected Jacobi PCG on
the 7-point Dirichlet Poisson stencil, in numpy on the host.

It imports nothing of the program and takes nothing the program made:
only the right-hand side, which the benchmark itself draws from the
seed.  No failure, no persistence, no recovery: exact state
reconstruction promises the same iterates as this uninterrupted solve.

Every vector operation runs slab by slab (ranges of z planes) on a few
threads, since numpy releases the interpreter lock inside its loops; the
dots add the slabs' partial sums in slab order.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, Tuple

import numpy as np

THREADS = 8


def _slabs(nz: int):
    step = -(-nz // THREADS)
    return [(z, min(z + step, nz)) for z in range(0, nz, step)]


def _stencil_slab(u: np.ndarray, out: np.ndarray, z0: int, z1: int) -> None:
    nz = u.shape[0]
    o, us = out[z0:z1], u[z0:z1]
    np.multiply(us, 6.0, out=o)
    lo, hi = max(z0, 1), min(z1, nz - 1)
    out[lo:z1] -= u[lo - 1:z1 - 1]
    out[z0:hi] -= u[z0 + 1:hi + 1]
    o[:, 1:] -= us[:, :-1]
    o[:, :-1] -= us[:, 1:]
    o[:, :, 1:] -= us[:, :, :-1]
    o[:, :, :-1] -= us[:, :, 1:]


def stencil(u: np.ndarray) -> np.ndarray:
    """``A u`` on an ``(nz, ny, nx)`` grid: ``6 u`` minus the six face
    neighbours, out-of-domain neighbours zero."""
    out = np.empty_like(u)
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(lambda s: _stencil_slab(u, out, *s), _slabs(len(u))))
    return out


def pcg(b: np.ndarray, grid: Tuple[int, int, int], iterations: int,
        keep_p: Iterable[int] = ()) -> Tuple[np.ndarray, Dict[int, np.ndarray]]:
    """``iterations`` steps of Jacobi PCG from ``x = 0`` (paper
    Algorithm 1, ``alpha = r'z / p'Ap``).  Returns ``x`` and the search
    direction ``p`` after each iteration in ``keep_p`` (``p`` after 0
    iterations is ``z0``), in ``b``'s precision."""
    keep = set(keep_p)
    inv_diag = b.dtype.type(1.0) / b.dtype.type(6.0)
    x = np.zeros(grid, b.dtype)
    r = b.reshape(grid).copy()
    z = r * inv_diag
    p = z.copy()
    ap = np.empty_like(p)
    slabs = _slabs(grid[0])
    kept = {0: p.reshape(-1).copy()} if 0 in keep else {}

    with ThreadPoolExecutor(THREADS) as pool:
        def each(fn):
            return list(pool.map(lambda s: fn(slice(*s)), slabs))

        def dot(a, c):
            parts = each(lambda s: np.vdot(a[s], c[s]))
            return sum(parts[1:], parts[0])

        rz = dot(r, z)
        for k in range(1, iterations + 1):
            each(lambda s: _stencil_slab(p, ap, s.start, s.stop))
            alpha = rz / dot(p, ap)

            def update(s):
                x[s] += alpha * p[s]
                r[s] -= alpha * ap[s]
                np.multiply(r[s], inv_diag, out=z[s])

            each(update)
            rz_new = dot(r, z)
            beta = rz_new / rz

            def direction(s):
                p[s] *= beta
                p[s] += z[s]

            each(direction)
            rz = rz_new
            if k in keep:
                kept[k] = p.reshape(-1).copy()
    return x.reshape(-1), kept
