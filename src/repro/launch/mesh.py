"""Production mesh construction.

Single pod: (data=16, model=16) — a 256-chip v5e pod.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across 2 pods, with the
"pod" axis crossing the OCS interconnect the paper's scheduler plans
(collectives/planner.py).

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""

from __future__ import annotations

import jax

__all__ = [
    "make_production_mesh",
    "make_local_mesh",
    "mesh_axis_sizes",
    "data_axis_size",
    "data_sharding",
    "place",
    "init_distributed",
    "process_shard",
]


def _auto_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with Auto axis types on every axis.

    jax defaults to Explicit axes, which make every array's sharding part
    of its type; the batched stages place inputs with `NamedSharding`s and
    leave propagation to the compiler, which needs Auto.
    """
    from jax.sharding import AxisType

    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) == n:
        return _auto_mesh(shape, axes)
    if len(devices) > n:  # dry-run forces 512; single-pod uses the first 256
        return _auto_mesh(shape, axes, devices=devices[:n])
    raise RuntimeError(
        f"need {n} devices for mesh {shape}, have {len(devices)} — run under "
        "the dry-run entrypoint (XLA_FLAGS=--xla_force_host_platform_device_count=512)"
    )


def make_local_mesh():
    """All-local-devices data mesh with the production axis names.

    One device per ``data`` shard (CPU tests see 1 unless
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` forces more).
    """
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_axis_size(mesh) -> int:
    """Number of shards along the ensemble (``data``) axis."""
    return int(mesh_axis_sizes(mesh).get("data", 1))


def data_sharding(mesh):
    """`NamedSharding` that splits an array's leading axis over ``data``.

    The ensemble member axis of every batched scheduling stage
    (`repro.pipeline.ensemble_batch`) is placed with this; trailing axes
    stay replicated.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec("data"))


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    local_device_ids=None,
) -> bool:
    """Bring up `jax.distributed` for a multi-host sweep; returns whether
    a multi-process runtime is active.

    The single-process degenerate case (no coordinator, ``num_processes``
    unset or 1) is a no-op returning False, so the sharded runner
    (`repro.experiments.runner`) can call this unconditionally: one
    entrypoint covers the laptop run and the fleet launch.  Re-initializing
    an already-initialized runtime is tolerated (idempotent per process).
    Arguments default to the ``JAX_COORDINATOR_ADDRESS`` /
    ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` environment contract of
    `jax.distributed.initialize`.
    """
    if coordinator_address is None and num_processes in (None, 1):
        return jax.process_count() > 1
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
        )
    except RuntimeError as e:  # already initialized: keep the first bring-up
        if "already initialized" not in str(e).lower():
            raise
    return jax.process_count() > 1


def process_shard() -> tuple[int, int]:
    """This host's (shard, num_shards) under the distributed runtime.

    ``(0, 1)`` on a single process — the runner's sharding contract is
    identical either way: shard i of n computes the i-th contiguous cell
    slice and writes one shard artifact for the global row gather.
    """
    return int(jax.process_index()), int(jax.process_count())


def place(x, sharding=None):
    """Stage-input placement: to device, under ``sharding`` when given.

    The one definition of how batched-stage inputs reach devices (LP
    solve, allocation scan, circuit calendar all route through this), so
    placement policy changes happen in one spot.
    """
    import jax.numpy as jnp

    x = jnp.asarray(x)
    return x if sharding is None else jax.device_put(x, sharding)
