"""Mesh construction — every mesh of the repo is built here.

Axes ("data", "model"): the DRACO client axis on "data" (one client per
device group), tensor parallel on "model". `launch.train.client_mesh`
sizes it from the visible devices; the sweep and the tests use the
builders below.

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
