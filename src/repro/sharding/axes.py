"""Logical-axis sharding context.

Model code calls ``constrain(x, 'batch', 'seq', 'heads', None)`` with
*logical* axis names; the active :class:`AxisRules` maps those to mesh
axes. With no active rules (CPU tests) ``constrain`` is a no-op, so the
model code is mesh-agnostic.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxis = Union[None, str, Tuple[str, ...]]

_TLS = threading.local()


@dataclass(frozen=True)
class AxisRules:
    mesh: Mesh
    rules: dict  # logical name -> mesh axis (str | tuple | None)

    def to_mesh_axes(self, names) -> P:
        axes = []
        for n in names:
            axes.append(None if n is None else self.rules.get(n))
        return P(*axes)


# Production logical->mesh mapping. "clients" is the DRACO agent axis.
def default_rules(mesh: Mesh) -> AxisRules:
    multi_pod = "pod" in mesh.axis_names
    client_axes = ("pod", "data") if multi_pod else ("data",)
    return AxisRules(
        mesh=mesh,
        rules={
            "clients": client_axes if multi_pod else "data",
            "batch": client_axes if multi_pod else "data",
            "seq": None,
            "cache_seq": None,
            "heads": "model",
            "kv_heads": "model",
            "ff": "model",
            "experts": "model",
            "vocab": "model",
            "embed": None,
            "state": None,
            "ssm_heads": "model",
        },
    )


def train_rules(mesh: Mesh) -> AxisRules:
    """Rules for code running *inside* the per-client vmap: the client axis
    is handled by vmap(spmd_axis_name=...), so logical batch stays
    unsharded and only model-parallel axes constrain."""
    base = default_rules(mesh)
    rules = dict(base.rules)
    rules["batch"] = None
    rules["clients"] = None
    return AxisRules(mesh=mesh, rules=rules)


def current_rules() -> Optional[AxisRules]:
    return getattr(_TLS, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[AxisRules]):
    prev = getattr(_TLS, "rules", None)
    _TLS.rules = rules
    try:
        yield rules
    finally:
        _TLS.rules = prev


def constrain(x: jax.Array, *names):
    """Apply a sharding constraint by logical axis names (no-op w/o rules).

    Axes mapped to None and axes whose dim isn't divisible by the mesh-axis
    size become UNCONSTRAINED (partitioner's choice) — NOT replicated: an
    explicit None would force an all-gather of already-sharded operands
    (measured: a full f32 KV-cache all-gather per layer at decode)."""
    from repro.sharding.specs import filter_divisible

    rules = current_rules()
    if rules is None:
        return x
    assert len(names) == x.ndim, (names, x.shape)
    spec = filter_divisible(rules.to_mesh_axes(names), x.shape, rules.mesh)
    # dedup: a mesh axis may appear once; later duplicates -> UNCONSTRAINED
    seen = set()
    axes_out = []
    for a in spec:
        key = tuple(a) if isinstance(a, tuple) else a
        if a is not None and key in seen:
            a = None
        elif a is not None:
            seen.add(key)
        axes_out.append(a)
    U = jax.sharding.PartitionSpec.UNCONSTRAINED
    spec = jax.sharding.PartitionSpec(*[a if a is not None else U for a in axes_out])
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))
