"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module does not touch jax device state — the dry-run must set XLA_FLAGS
before first jax init.

Every mesh here has ``Auto`` axes. ``jax.make_mesh`` defaults to
``Explicit`` axes, under which shardings become part of array types and
ops such as ``dynamic_update_slice`` reject operands whose shardings
differ; the model code instead places arrays with sharding constraints
and lets the compiler propagate them, which is what ``Auto`` axes mean.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def auto_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto`` (see the module doc)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the "pod" axis is
    pure data parallelism over DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for smoke-testing launcher code paths."""
    return auto_mesh((1, 1), ("data", "model"))


def make_rollout_mesh(n_shards: int):
    """1-D ``("tensor",)`` mesh for one sharded rollout instance.

    A rollout "instance" in the paper is a resource pool, not a chip; the
    sharded backend (``repro.rollout.sharded``) spans one instance across
    ``n_shards`` devices of this mesh — params head-sharded, the paged KV
    pool split on its KV-head axis. Uses the first ``n_shards`` local
    devices; raises early (with the fix spelled out) when the process has
    fewer, since ``jax.make_mesh``'s own error is opaque.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    avail = jax.device_count()
    if n_shards > avail:
        raise ValueError(
            f"rollout mesh needs {n_shards} devices but only {avail} are "
            f"visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards} before the "
            f"first jax call"
        )
    return auto_mesh((n_shards,), ("tensor",))
