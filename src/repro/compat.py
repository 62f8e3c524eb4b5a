"""The jax API seam: every ``shard_map`` call site routes through
:func:`shard_map` (repro-lint RL101), so a jax API change is absorbed
here once.  Written against the installed jax (0.9)."""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
