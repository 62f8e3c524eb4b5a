"""Logical-axis sharding: model code names *logical* dimensions; a rules
table maps them onto physical mesh axes per deployment.

Parallelism realized through the rules (DESIGN.md §5):

- **DP**   batch        -> ("pod", "data")
- **FSDP** fsdp         -> "data"   (ZeRO-3 parameter/optimizer sharding)
- **TP**   heads/mlp/vocab -> "model" (Megatron tensor parallelism)
- **EP**   experts      -> "model"  (expert parallelism, aligned with TP)
- **SP**   seq          -> "model"  (Megatron sequence parallelism of the
  residual stream between blocks; GSPMD inserts the all-gather /
  reduce-scatter transitions at block boundaries)
- **KV-seq** kv_seq     -> "model"  (sequence-sharded decode caches ->
  flash-decode style distributed softmax)

Model code calls ``shard(x, "batch", "seq", "embed")`` etc.; with no mesh
configured (CPU smoke tests) this is the identity.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, Tuple[str, ...], None]


@dataclass(frozen=True)
class AxisRules:
    """Mapping of logical axis names to physical mesh axes."""

    mesh: Optional[Mesh]
    rules: Dict[str, Axis]

    def physical(self, logical: Optional[str]) -> Axis:
        if logical is None:
            return None
        axis = self.rules.get(logical)
        if axis is None or self.mesh is None:
            return None
        # keep only axes present in this mesh (e.g. no "pod" single-pod)
        if isinstance(axis, tuple):
            kept = tuple(a for a in axis if a in self.mesh.axis_names)
            return kept if kept else None
        return axis if axis in self.mesh.axis_names else None

    def spec(self, *logical: Optional[str]) -> P:
        return P(*(self.physical(l) for l in logical))


DEFAULT_RULES: Dict[str, Axis] = {
    "batch": ("pod", "data"),
    "fsdp": "data",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "seq": "model",      # sequence parallelism of the residual stream
    "kv_seq": "model",   # sequence-sharded decode caches
    "embed": None,
    "layers": None,
    "state": None,       # SSM state dim
}

_ctx = threading.local()


def set_rules(mesh: Optional[Mesh], rules: Optional[Dict[str, Axis]] = None) -> AxisRules:
    r = AxisRules(mesh, dict(DEFAULT_RULES if rules is None else rules))
    _ctx.rules = r
    return r


def current_rules() -> AxisRules:
    r = getattr(_ctx, "rules", None)
    if r is None:
        r = AxisRules(None, dict(DEFAULT_RULES))
        _ctx.rules = r
    return r


@contextlib.contextmanager
def use_rules(mesh: Optional[Mesh], rules: Optional[Dict[str, Axis]] = None):
    prev = getattr(_ctx, "rules", None)
    set_rules(mesh, rules)
    try:
        yield current_rules()
    finally:
        _ctx.rules = prev


def logical_spec(*logical: Optional[str]) -> P:
    return current_rules().spec(*logical)


def spec_for_shape(rules: AxisRules, shape: Sequence[int],
                   logical: Sequence[Optional[str]]) -> P:
    """Physical spec with per-dimension divisibility degradation.

    A logical axis whose mapped mesh extent does not divide the tensor
    dimension is dropped (for tuple mappings, the longest divisible prefix
    is kept) — e.g. kv_heads=8 on a model=16 axis falls back to
    replication while q-heads=32 shard fully.
    """
    phys = []
    mesh = rules.mesh
    for dim, l in zip(shape, logical):
        ax = rules.physical(l)
        if ax is None or mesh is None:
            phys.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        kept = []
        prod = 1
        for a in axes:
            ext = mesh.shape[a]
            if dim % (prod * ext) == 0:
                kept.append(a)
                prod *= ext
        if not kept:
            phys.append(None)
        elif len(kept) == 1:
            phys.append(kept[0])
        else:
            phys.append(tuple(kept))
    return P(*phys)


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names (identity w/o mesh)."""
    r = current_rules()
    if r.mesh is None:
        return x
    spec = spec_for_shape(r, x.shape, logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(r.mesh, spec))


# ======================================================================
# Sharded solves (ISSUE 7): block-rows of a solver problem mapped onto a
# 1-D ``data`` mesh axis.  The paper's failure unit is a *node*: one
# device shard owning a contiguous run of partition blocks.  A
# ``ShardLayout`` is that mapping; ``shard_problem`` wraps an operator /
# rhs pair so the five zoo solvers run with device-sharded vectors and
# ``FailureEvent(shard=...)`` kills exactly one device's blocks
# (DESIGN.md §10).
# ======================================================================
@dataclass(frozen=True)
class ShardLayout:
    """Block-rows -> device shards, contiguously: shard ``s`` owns blocks
    ``[s*bps, (s+1)*bps)`` with ``bps = nblocks // nshards`` (z-slab
    locality: a device's blocks are its slab of the grid)."""

    nblocks: int
    nshards: int

    def __post_init__(self):
        if not (1 <= self.nshards <= self.nblocks):
            raise ValueError(
                f"need 1 <= nshards <= nblocks, got nshards={self.nshards} "
                f"with nblocks={self.nblocks}")
        if self.nblocks % self.nshards != 0:
            raise ValueError(
                f"nblocks={self.nblocks} not divisible by "
                f"nshards={self.nshards}")

    @property
    def blocks_per_shard(self) -> int:
        return self.nblocks // self.nshards

    def blocks_of(self, shard: int) -> Tuple[int, ...]:
        """The partition blocks owned by device shard ``shard``."""
        if not (0 <= shard < self.nshards):
            raise ValueError(
                f"shard {shard} out of range for nshards={self.nshards}")
        bps = self.blocks_per_shard
        return tuple(range(shard * bps, (shard + 1) * bps))

    def shard_of_block(self, block: int) -> int:
        if not (0 <= block < self.nblocks):
            raise ValueError(
                f"block {block} out of range for nblocks={self.nblocks}")
        return block // self.blocks_per_shard

    def shard_of_block_map(self) -> Dict[int, int]:
        """The full block -> owning-shard map (per-shard session
        addressing: :meth:`repro.nvm.backend.PersistSession.bind_shards`)."""
        return {b: self.shard_of_block(b) for b in range(self.nblocks)}


def make_data_mesh(nshards: int) -> Mesh:
    """A 1-D ``data`` mesh of ``nshards`` devices (built by the jax API
    seam, ``compat_make_mesh``).  Raises ``ValueError`` when the runtime
    has fewer devices — callers (tests) turn that into a clean skip."""
    from repro.launch.mesh import compat_make_mesh

    have = jax.device_count()
    if have < nshards:
        raise ValueError(
            f"cannot build a {nshards}-shard data mesh on {have} "
            f"device(s); fake host devices with "
            f"--xla_force_host_platform_device_count")
    return compat_make_mesh((nshards,), ("data",))


class ShardedOperator:
    """An operator whose vectors live block-sharded on a ``data`` mesh.

    Wraps any block-partitioned operator: ``apply`` keeps outputs pinned
    to the canonical layout (``P("data")`` over the flat index space —
    legal because ``nblocks % nshards == 0``); every other attribute
    (``partition``, ``nblocks``, ``n``, ``diag``, ``inblock_apply``,
    ``offblock_apply``, ...) delegates to the base operator, so
    preconditioners and reconstruction code run unchanged.  The wrapper
    adds ``layout`` and ``mesh`` — the driver and the solvers' deterministic
    reductions key off both (``getattr(op, "mesh", None)``)."""

    def __init__(self, base, layout: ShardLayout, mesh: Mesh):
        if "data" not in mesh.axis_names:
            raise ValueError("ShardedOperator needs a mesh with a 'data' axis")
        if int(mesh.shape["data"]) != layout.nshards:
            raise ValueError(
                f"mesh data axis has {mesh.shape['data']} device(s) but the "
                f"layout declares nshards={layout.nshards}")
        if base.nblocks != layout.nblocks:
            raise ValueError(
                f"operator has {base.nblocks} blocks but the layout "
                f"declares nblocks={layout.nblocks}")
        self.base = base
        self.layout = layout
        self.mesh = mesh
        self.vector_sharding = NamedSharding(mesh, P("data"))

    def __getattr__(self, name):
        return getattr(self.base, name)

    def apply(self, x: jax.Array) -> jax.Array:
        y = self.base.apply(x)
        return jax.lax.with_sharding_constraint(y, self.vector_sharding)

    def device_put(self, x: jax.Array) -> jax.Array:
        """Place a full-length vector into the canonical block sharding."""
        return jax.device_put(x, self.vector_sharding)


def shard_problem(op, b, nshards: int, mesh: Optional[Mesh] = None):
    """Shard a block-partitioned problem across ``nshards`` devices.

    Returns ``(sharded_op, sharded_b)``: the operator wrapped in a
    :class:`ShardedOperator` over a 1-D ``data`` mesh and the rhs placed
    into the canonical block sharding.  ``nshards`` must divide the
    operator's block count (blocks are the failure unit; shards are
    whole groups of them)."""
    layout = ShardLayout(nblocks=op.nblocks, nshards=nshards)
    if mesh is None:
        mesh = make_data_mesh(nshards)
    sharded = ShardedOperator(op, layout, mesh)
    return sharded, sharded.device_put(b)


def place_state(state, mesh: Mesh, vector_fields: Sequence[str]):
    """Re-pin a solver state NamedTuple to the canonical placement:
    vector fields block-sharded on ``data``, everything else replicated.

    The driver applies this after ``init_state``/``reconstruct`` so the
    jitted step always sees one placement — recovery must not silently
    recompile the step for a different layout (a different layout could
    legally reassociate reductions and break bit-exactness)."""
    vspec = NamedSharding(mesh, P("data"))
    rspec = NamedSharding(mesh, P())
    vfields = set(vector_fields)
    placed = {
        f: jax.device_put(getattr(state, f),
                          vspec if f in vfields else rspec)
        for f in state._fields
    }
    return type(state)(**placed)
