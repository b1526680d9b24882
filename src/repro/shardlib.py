"""Logical-axis sharding utilities (MaxText-style axis rules, minimal).

Model code never names mesh axes directly.  It annotates tensors with
*logical* axis names (``shard(x, "batch", "seq", "embed")``) and the active
:class:`AxisRules` context maps logical names to mesh axes.  Outside any
context every helper is a no-op, so the same model code runs on a single
CPU device in tests and under a 512-chip mesh in the dry-run.

``maybe_shard_map`` wraps a per-shard function in ``jax.shard_map`` when a
mesh is active and calls it directly (world size 1) otherwise; model code
that needs *manual* collectives (MoE dispatch, split-KV decode attention,
row-sharded embedding lookup) uses it together with the ``psum``/``axis_size``
helpers below, which likewise degrade to identities without a mesh.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "make_mesh", "AxisRules", "axis_rules", "current_rules", "current_mesh",
    "logical_to_spec", "shard", "sharding_for", "maybe_shard_map",
    "psum", "pmax", "pmin", "psum_scatter", "all_gather", "axis_size",
    "axis_index",
]

_state = threading.local()


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    :func:`shard` constrains arrays with ``with_sharding_constraint``,
    which accepts only ``Auto`` mesh axes; ``jax.make_mesh`` alone gives
    ``Explicit`` ones.  ``devices`` defaults to ``jax.devices()``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


class AxisRules:
    """Mapping from logical axis names to mesh axis names (or tuples)."""

    def __init__(self, mesh: Mesh, rules: Dict[str, Union[str, Tuple[str, ...], None]]):
        self.mesh = mesh
        self.rules = dict(rules)
        # A mesh axis may back at most one logical axis within a single
        # PartitionSpec; the resolver below drops duplicate uses per-tensor.

    def resolve(self, name: Optional[str]):
        if name is None:
            return None
        return self.rules.get(name, None)


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Dict[str, Any]):
    prev = getattr(_state, "rules", None)
    _state.rules = AxisRules(mesh, rules)
    try:
        with mesh:
            yield _state.rules
    finally:
        _state.rules = prev


def current_rules() -> Optional[AxisRules]:
    return getattr(_state, "rules", None)


def current_mesh() -> Optional[Mesh]:
    r = current_rules()
    return r.mesh if r is not None else None


def logical_to_spec(*names: Optional[str]) -> P:
    """Resolve logical axis names to a PartitionSpec under current rules."""
    r = current_rules()
    if r is None:
        return P()
    used: set = set()
    parts = []
    for nm in names:
        ax = r.resolve(nm)
        if ax is None:
            parts.append(None)
            continue
        ax_t = (ax,) if isinstance(ax, str) else tuple(ax)
        ax_t = tuple(a for a in ax_t if a not in used and a in r.mesh.axis_names)
        used.update(ax_t)
        if not ax_t:
            parts.append(None)
        elif isinstance(ax, str):
            parts.append(ax_t[0])
        else:
            # Preserve tuple form for tuple-valued rules: PartitionSpec
            # does not normalize ('data',) == 'data' on every JAX version.
            parts.append(ax_t)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def sharding_for(*names: Optional[str]) -> Optional[NamedSharding]:
    r = current_rules()
    if r is None:
        return None
    return NamedSharding(r.mesh, logical_to_spec(*names))


def shard(x: jnp.ndarray, *names: Optional[str]) -> jnp.ndarray:
    """Apply a logical sharding constraint (no-op without an active mesh)."""
    s = sharding_for(*names)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)


# ---------------------------------------------------------------------------
# Manual-SPMD helpers: real collectives inside shard_map, identity outside.
# ---------------------------------------------------------------------------

def _axes_tuple(ax) -> Tuple[str, ...]:
    if ax is None:
        return ()
    return (ax,) if isinstance(ax, str) else tuple(ax)


def _live_axes(logical: str) -> Tuple[str, ...]:
    """Mesh axes backing `logical` under the current rules (may be ())."""
    r = current_rules()
    if r is None:
        return ()
    return tuple(a for a in _axes_tuple(r.resolve(logical))
                 if a in r.mesh.axis_names)


def psum(x, axes: Sequence[str]):
    axes = tuple(axes)
    return jax.lax.psum(x, axes) if axes else x


def pmax(x, axes: Sequence[str]):
    axes = tuple(axes)
    return jax.lax.pmax(x, axes) if axes else x


def pmin(x, axes: Sequence[str]):
    """Cross-shard min — the (min, +) semiring's reduction, i.e. how a
    fleet merges per-shard distance rows when the batch axis is sharded
    (DESIGN.md §13)."""
    axes = tuple(axes)
    return jax.lax.pmin(x, axes) if axes else x


def psum_scatter(x, axes: Sequence[str], scatter_dimension: int = 0):
    axes = tuple(axes)
    if not axes:
        return x
    return jax.lax.psum_scatter(x, axes, scatter_dimension=scatter_dimension,
                                tiled=True)


def all_gather(x, axes: Sequence[str], axis: int = 0):
    axes = tuple(axes)
    if not axes:
        return x
    return jax.lax.all_gather(x, axes, axis=axis, tiled=True)


def axis_size(axes: Sequence[str], mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or current_mesh()
    if mesh is None:
        return 1
    out = 1
    for a in _axes_tuple(tuple(axes)):
        if a in mesh.axis_names:
            out *= mesh.shape[a]
    return out


def axis_index(axes: Sequence[str]):
    axes = tuple(axes)
    if not axes:
        return jnp.int32(0)
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def maybe_shard_map(fn: Callable, in_specs, out_specs) -> Callable:
    """``jax.shard_map`` under an active mesh; plain call otherwise.

    in_specs/out_specs are pytrees of PartitionSpec built with
    :func:`logical_to_spec` (already resolved). Without a mesh the function
    runs unmapped — every collective helper above degrades to identity, so
    the math is unchanged at world size 1.
    """
    mesh = current_mesh()
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
