"""Rule ``no-raw-shard-map-import``: the mesh shim is the one door.

`repro.launch.mesh` wraps ``jax.shard_map`` (and mesh construction)
in the one place that sets how every shard_map in the repo checks
replication (``check_vma=False``) and which axis types a mesh has.  A
direct ``jax.experimental.shard_map`` import bypasses that door."""
from __future__ import annotations

import ast

from repro.analysis.lint import dotted, not_in, rule

_MESH = "src/repro/launch/mesh.py"


@rule("no-raw-shard-map-import",
      summary="shard_map is imported only via repro.launch.mesh",
      rationale="launch/mesh.py is the one place that sets shard_map's "
                "replication checking and the mesh axis types; a raw "
                "import bypasses it",
      fix_hint="from repro.launch.mesh import shard_map",
      applies=not_in(_MESH))
def check(ctx):
    """Flag imports of (or attribute chains into)
    ``jax.experimental.shard_map`` anywhere but the shim module."""
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom):
            if node.module == "jax.experimental.shard_map":
                yield node.lineno, ("raw jax.experimental.shard_map "
                                    "import bypasses the launch/mesh "
                                    "shard_map door")
            elif node.module == "jax.experimental" and any(
                    a.name == "shard_map" for a in node.names):
                yield node.lineno, ("raw jax.experimental shard_map "
                                    "import bypasses the launch/mesh "
                                    "shard_map door")
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax.experimental.shard_map":
                    yield node.lineno, ("raw jax.experimental."
                                        "shard_map import bypasses "
                                        "the launch/mesh shard_map door")
        elif isinstance(node, ast.Attribute):
            if dotted(node) == "jax.experimental.shard_map.shard_map":
                yield node.lineno, ("raw jax.experimental.shard_map "
                                    "use bypasses the launch/mesh "
                                    "shard_map door")
