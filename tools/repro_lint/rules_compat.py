"""RL1xx — jax version-compat isolation.

``jax.shard_map``, ``jax.sharding.AxisType`` and ``jax.make_mesh``'s
``axis_types`` kwarg are the jax surfaces that have moved between
releases.  The repo's answer is a single compat seam —
``repro/compat.py`` (:func:`shard_map`) and ``repro/launch/mesh.py`` (:func:`compat_make_mesh`) — and these rules
keep every other file off the raw surfaces, so a jax upgrade or
downgrade is a two-file change instead of a tree-wide audit.
"""
from __future__ import annotations

import ast
from typing import Iterable, List, Set, Tuple

from .core import FileContext, Finding, Rule

#: the only files allowed to touch the raw version-dependent surfaces
COMPAT_FILES = ("repro/compat.py", "repro/launch/mesh.py")


def _jax_imports(ctx: FileContext) -> Tuple[Set[str], Set[str]]:
    """Names bound in this file by ``from jax... import`` — returns
    ({names bound to Mesh}, {names bound to make_mesh})."""
    mesh_names: Set[str] = set()
    make_mesh_names: Set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.split(".")[0] == "jax":
            for alias in node.names:
                bound = alias.asname or alias.name
                if alias.name == "Mesh":
                    mesh_names.add(bound)
                if alias.name == "make_mesh":
                    make_mesh_names.add(bound)
    return mesh_names, make_mesh_names


class RawShardMapRule(Rule):
    rule_id = "RL101"
    title = "direct jax.shard_map outside the compat seam"
    hint = "call repro.compat.shard_map (the API seam) instead"
    invariant = "jax.shard_map has moved between jax releases; all " \
                "call sites route through repro.compat"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.path_endswith(*COMPAT_FILES):
            return
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Attribute)
                    and node.attr == "shard_map"
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "jax"):
                yield self.finding(
                    ctx, node, "direct jax.shard_map reference — "
                    "version-dependent surface")
            if isinstance(node, ast.ImportFrom) and node.module in (
                    "jax", "jax.experimental.shard_map"):
                for alias in node.names:
                    if alias.name == "shard_map":
                        yield self.finding(
                            ctx, node, f"shard_map imported from "
                            f"{node.module!r} — version-dependent surface")


class RawAxisTypeRule(Rule):
    rule_id = "RL102"
    title = "jax.sharding.AxisType outside the compat seam"
    hint = "use repro.launch.mesh.compat_make_mesh, the one place " \
           "axis types are chosen"
    invariant = "jax.sharding.AxisType does not exist before jax 0.5; " \
                "meshes are built in the compat seam only"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.path_endswith(*COMPAT_FILES):
            return
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Attribute) and node.attr == "AxisType"
                    and ast.unparse(node.value) == "jax.sharding"):
                yield self.finding(
                    ctx, node, "jax.sharding.AxisType reference — "
                    "version-dependent surface")
            if isinstance(node, ast.ImportFrom) \
                    and node.module == "jax.sharding":
                for alias in node.names:
                    if alias.name == "AxisType":
                        yield self.finding(
                            ctx, node, "AxisType imported from "
                            "jax.sharding — version-dependent surface")


class RawMeshConstructionRule(Rule):
    rule_id = "RL103"
    title = "raw Mesh construction outside the compat seam"
    hint = "build meshes with repro.launch.mesh.compat_make_mesh (or " \
           "make_mesh_for); importing Mesh for type annotations is fine"
    invariant = "DESIGN.md §10: every mesh is built by compat_make_mesh " \
                "so axis-type semantics match across jax versions"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.path_endswith(*COMPAT_FILES):
            return
        mesh_names, make_mesh_names = _jax_imports(ctx)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Name) and (
                    func.id in mesh_names or func.id in make_mesh_names):
                yield self.finding(
                    ctx, node, f"raw {func.id}(...) construction — mesh "
                    f"geometry must go through the compat seam")
            elif isinstance(func, ast.Attribute):
                dotted = ast.unparse(func)
                if dotted in ("jax.sharding.Mesh", "jax.make_mesh",
                              "jax.experimental.maps.Mesh"):
                    yield self.finding(
                        ctx, node, f"raw {dotted}(...) construction — "
                        f"mesh geometry must go through the compat seam")


RULES: List[Rule] = [RawShardMapRule(), RawAxisTypeRule(),
                     RawMeshConstructionRule()]
