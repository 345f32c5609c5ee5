"""Sharding helpers: logical-axis constraints + spec-tree -> NamedSharding.

`constrain` is the boundary-hint primitive model code calls between
blocks (`constrain(x, "batch", None, "tp")`).  It is a no-op unless a
`use_mesh_rules(mesh, rules)` context is active — smoke tests and the
single-host serve engine run the very same model code with zero SPMD
overhead, while the dry-run/pjit path gets real with_sharding_constraint
hints.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .axes import MeshRules, _axis_size, sanitize_pspec

_ctx = threading.local()


def serve_mesh(tp: int, devices=None) -> Mesh:
    """1-D ("model",) mesh over `devices` (default: the first `tp` local
    devices) — the mesh one TP-sharded serve engine runs on.  The
    launcher gives each replica its own devices when there are enough.
    Raises with the host-mesh escape hatch when the platform exposes
    fewer devices than `tp`."""
    import numpy as np
    devs = list(devices) if devices is not None else jax.devices()
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if len(devs) < tp:
        raise ValueError(
            f"tp={tp} needs {tp} devices but the {devs[0].platform} "
            f"backend exposes {len(devs)}; on CPU force a host mesh "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={tp}")
    return Mesh(np.asarray(devs[:tp]), ("model",))


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated NamedSharding (host-fed tokens/tables/lengths
    and the gathered logits)."""
    return NamedSharding(mesh, P())


def current_mesh_rules() -> Optional[Tuple[Mesh, MeshRules]]:
    """The (mesh, rules) of the innermost `use_mesh_rules`, or None."""
    return getattr(_ctx, "mesh_rules", None)


@contextlib.contextmanager
def use_mesh_rules(mesh: Mesh, rules: MeshRules):
    prev = current_mesh_rules()
    _ctx.mesh_rules = (mesh, rules)
    try:
        yield
    finally:
        _ctx.mesh_rules = prev


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Sharding-constrain `x` by logical axis names (no-op w/o context)."""
    cur = current_mesh_rules()
    if cur is None:
        return x
    mesh, rules = cur
    spec = sanitize_pspec(rules.pspec(tuple(axes)), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ----------------------------------------------------------------------------
# spec trees -> sharding trees
# ----------------------------------------------------------------------------
def _leaf_sharding(axes, shape, mesh, rules) -> NamedSharding:
    return NamedSharding(mesh, sanitize_pspec(rules.pspec(axes), shape, mesh))


def tree_shardings(spec_tree: Any, mesh: Mesh, rules: MeshRules) -> Any:
    """ParamSpec pytree -> NamedSharding pytree (same structure)."""
    from repro.models.common import is_spec
    return jax.tree_util.tree_map(
        lambda s: _leaf_sharding(s.axes, s.shape, mesh, rules),
        spec_tree, is_leaf=is_spec)


def qtree_shardings(spec_tree: Any, qtree: Any, mesh: Mesh,
                    rules: MeshRules) -> Any:
    """Shardings for a (possibly quantized) param tree.

    `qtree` mirrors `spec_tree` except eligible weights are QTensor
    nodes (packed data + scales).  Both QTensor fields shard by the
    dense weight's logical axes, but a dim is sharded only when the
    mesh axis divides it in EVERY materialization — orig_shape, the
    packed data (int4 halves the quant axis), and the group-scale array
    (quant-axis dim is K/group).  Sanitizing data and scales
    independently against the dense axes could shard the data while
    replicating (or raggedly splitting) its scales, silently
    misaligning the per-group dequant — so one pspec is computed across
    all three shapes and applied to both fields."""
    from repro.models.common import is_spec
    from repro.quant.qarray import QTensor

    def per_leaf(spec, q):
        if isinstance(q, QTensor):
            entries = tuple(rules.pspec(spec.axes)) + (None,) * len(
                q.orig_shape)
            out = []
            for i, entry in enumerate(entries[:len(q.orig_shape)]):
                n = _axis_size(mesh, entry)
                if entry is not None and any(
                        shape[i] % n != 0 for shape in
                        (q.orig_shape, q.data.shape, q.scales.shape)):
                    entry = None
                out.append(entry)
            spec_p = P(*out)
            return QTensor(
                data=NamedSharding(mesh, spec_p),
                scales=NamedSharding(mesh, spec_p),
                bits=q.bits, group=q.group, axis=q.axis,
                orig_shape=q.orig_shape)
        return _leaf_sharding(spec.axes, q.shape, mesh, rules)

    return jax.tree_util.tree_map(
        per_leaf, spec_tree, qtree,
        is_leaf=lambda x: is_spec(x))
