"""Production mesh definitions.

Single pod: (data=16, model=16) — 256 v5e chips.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the pod axis is pure
data parallelism (one cross-pod gradient all-reduce per step; DCN-friendly).

`make_production_mesh` is a function (never a module constant) so importing
this module touches no jax device state — required because the dry-run must
set XLA_FLAGS before any backend initialization.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """The production mesh with Auto axes: the model stack is laid out by
    sharding constraints that GSPMD propagates, not by sharding in types
    (`jax.make_mesh`'s default axis type, Explicit, would demand an
    output sharding at every gather and update)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever this host actually has — used by examples/tests."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"))


def make_candidate_mesh(shard: int):
    """1-D mesh for DSE candidate-grid fan-out (`search(..., shard=N)`).

    The single axis is named after `parallel.sharding.CANDIDATE_AXIS`; its
    size is `shard` clamped to the devices this process actually has, so
    `shard=4` on a 1-device CPU box still runs (one shard) and the same
    call fans out across 4 devices under
    `XLA_FLAGS=--xla_force_host_platform_device_count=4` or on real
    hardware. Results are byte-identical either way — the shard count only
    moves where the per-shard reductions run.
    """
    from repro.parallel.sharding import CANDIDATE_AXIS

    k = max(1, min(int(shard), len(jax.devices())))
    return jax.make_mesh((k,), (CANDIDATE_AXIS,))
