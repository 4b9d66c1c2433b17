"""Production mesh construction.

Single pod:  (16, 16)      axes ("data", "model")   = 256 chips
Multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips
Population: (shards,)      axis  ("pop",)  — co-search population axis

A FUNCTION, not a module constant — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import functools

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many (host) devices exist — used by the
    multi-device subprocess tests."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((1, n // model, model),
                         ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)


@functools.lru_cache(maxsize=None)
def make_pop_mesh(shards: int):
    """1-D mesh over the first `shards` local devices, axis "pop" — the
    co-search engines shard their population / fleet-member axis over
    it (`search.make_fused_runner(..., shards=...)`).  Cached per shard
    count so every engine trace for the same count closes over ONE mesh
    object.  Built with `jax.sharding.Mesh` directly: the population
    axis may legitimately cover a strict subset of the devices (shards
    is a divisor of the population, not of the device count)."""
    devices = jax.devices()
    if shards < 1 or shards > len(devices):
        raise ValueError(f"shards={shards} outside 1..{len(devices)} "
                         "available devices")
    return jax.sharding.Mesh(np.asarray(devices[:shards]), ("pop",))


def auto_pop_shards(members: int, requested: int | None = None) -> int:
    """Resolve the population shard count: the member axis must divide
    evenly, so `None` picks the largest divisor of `members` that fits
    the local device count (1 on a single-device host — the unsharded
    engine path).  An explicit request is validated, not adjusted."""
    n_dev = len(jax.devices())
    if requested is not None:
        if requested < 1 or requested > n_dev:
            raise ValueError(f"shards={requested} outside 1..{n_dev} "
                             "available devices")
        if members % requested:
            raise ValueError(f"shards={requested} does not divide the "
                             f"{members}-member population/chunk evenly")
        return requested
    return max(s for s in range(1, min(members, n_dev) + 1)
               if members % s == 0)
