"""Production mesh construction — every mesh of the repo is built here.

Single pod: (16, 16) = 256 chips, axes ("data", "model") — 16 DRACO
clients x 16-way tensor parallel. Multi-pod: (2, 16, 16) = 512 chips,
axes ("pod", "data", "model") — 32 clients spanning 2 pods; the gossip
graph's client axis is the flattened ("pod", "data") product, so gossip
edges cross the inter-pod links (DCN/optical) exactly where the paper's
protocol tolerates delay.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with every axis Auto-typed.

    `jax.make_mesh` defaults to Explicit axes, under which the sharded
    gathers and contractions of the train, unify and sweep steps raise
    `ShardingTypeError`; the steps are written for partitioner-propagated
    (Auto) sharding. `devices` defaults to all visible devices."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Tiny mesh for CPU integration tests (requires >= prod(shape) devices)."""
    return make_mesh(shape, axes)


def make_sweep_mesh(num_data: int | None = None):
    """Mesh for `repro.api.sweep.simulate_sweep(..., mesh=...)`: every
    device on the "data" axis (the `sharding/axes.py` "clients" rule maps
    the DRACO client axis onto it), trivial "model" axis — protocol
    sweeps are client-parallel, not tensor-parallel. `num_data` defaults
    to all visible devices; the client count N must be divisible by it
    for the axis to actually shard (`specs.filter_divisible` falls back
    to replicated otherwise)."""
    n = num_data if num_data is not None else len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def client_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def num_clients(mesh) -> int:
    n = 1
    for a in client_axes(mesh):
        n *= mesh.shape[a]
    return n
