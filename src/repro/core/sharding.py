"""Logical-axis sharding rules for the simulator's arrays -> PartitionSpecs.

Event-batch arrays are described by *logical* dim names (``events``,
``depos``, ``wires``, ``ticks``); the rule table maps each name to mesh
axes. ``build_spec`` drops any mapping whose axis size does not divide the
dim, so a batch that does not split over a mesh axis stays replicated on it.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisName = Union[str, Tuple[str, ...], None]

#: logical dim -> mesh axes
ACT_RULES: Dict[str, AxisName] = {
    "depos": ("pod", "data", "model"),
    "events": ("pod", "data"),   # event axis of a multi-event batch (DP)
    "wires": "model",
    "ticks": None,
}


# ---------------------------------------------------------------------------
# Mesh context (our own tracker; avoids depending on jax internals)
# ---------------------------------------------------------------------------

_state = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def current_act_rules() -> Dict[str, AxisName]:
    return getattr(_state, "act_rules", None) or ACT_RULES


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], act_rules: Optional[Dict] = None):
    prev = current_mesh()
    prev_rules = getattr(_state, "act_rules", None)
    _state.mesh = mesh
    _state.act_rules = act_rules
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _state.mesh = prev
        _state.act_rules = prev_rules


# ---------------------------------------------------------------------------
# Spec construction
# ---------------------------------------------------------------------------


def build_spec(shape: Sequence[int], names: Sequence[Optional[str]],
               mesh: Optional[Mesh], rules: Dict[str, AxisName]) -> P:
    """PartitionSpec for `shape` given logical `names`, with divisibility
    fallback (drop axes that don't divide, trailing-first for tuples)."""
    if mesh is None:
        return P()
    assert len(shape) == len(names), (shape, names)
    used: set = set()
    entries = []
    for dim, name in zip(shape, names):
        axis = rules.get(name) if name else None
        if axis is None:
            entries.append(None)
            continue
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        # keep only axes present in the mesh and unused so far
        axes = tuple(a for a in axes if a in mesh.shape and a not in used)
        # drop axes (from the right) until the product divides the dim
        while axes:
            prod = 1
            for a in axes:
                prod *= mesh.shape[a]
            if prod and dim % prod == 0:
                break
            axes = axes[:-1]
        if not axes:
            entries.append(None)
        else:
            used.update(axes)
            entries.append(axes if len(axes) > 1 else axes[0])
    return P(*entries)


def named_sharding(shape, names, rules=None, mesh=None) -> Optional[NamedSharding]:
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    spec = build_spec(shape, names, mesh, rules or ACT_RULES)
    return NamedSharding(mesh, spec)


def logical(x: jax.Array, names: Sequence[Optional[str]],
            rules: Optional[Dict[str, AxisName]] = None) -> jax.Array:
    """with_sharding_constraint by logical names (no-op without a mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = build_spec(x.shape, names, mesh, rules or current_act_rules())
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
