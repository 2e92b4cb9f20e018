"""Ensemble construction: bucket instances by padded shape for batched LP.

The paper's figures (Sec. V) are each evaluated over *sweeps* of synthesized
instances, so the ensemble — not the single instance — is the natural unit
of compute.  Solving the ordering LP one instance at a time starves the
batched `lp_terms` contraction at the small M of a single instance; this
module groups instances into shape buckets (M and 2N rounded up to a
quantum) and solves each bucket with the array-form ensemble solver
(`lp.pack_lp_arrays` → `lp.solve_subgradient_batch_arrays`), turning a
sweep's LP phase into a handful of vectorized programs.  With ``mesh=``
each bucket's member axis is padded to the mesh's ``data``-axis size and
the solve runs SPMD across devices (`repro.launch.mesh.data_sharding`);
members are independent, so sharded and unsharded solves are
bit-identical per member.

Bucketing trades compile-cache hits against padding: a larger quantum means
fewer distinct batched-program shapes but more padded (masked) work.  With
``m_quantum = p_quantum = 1`` instances are grouped by exact shape and each
bucket member follows bit-for-bit the trajectory `lp.solve_subgradient`
would give it alone; with padding the trajectories agree up to f32
reduction-order noise (~1e-5 relative on the objective).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

from repro.core import lp
from repro.core.coflow import CoflowInstance
from repro.experiments.results import device_gather
from repro.trace import count, span

__all__ = [
    "COLLAPSED",
    "Bucket",
    "bucket_shape",
    "build_buckets",
    "solve_ensemble_lp",
]

#: `bucket_shape` sentinel for an axis collapsed to the ensemble maximum
#: (quantum ``None``).  Distinct from 0 on purpose: a genuinely empty axis
#: (an M=0 instance) rounds to 0 under any quantum, and must keep its own
#: zero-shaped bucket instead of silently inheriting the ensemble maximum.
COLLAPSED = -1


def _round_up(n: int, quantum: int) -> int:
    return -(-n // quantum) * quantum


def bucket_shape(
    instance: CoflowInstance,
    m_quantum: int | None = 8,
    p_quantum: int | None = 8,
) -> tuple[int, int]:
    """Padded (coflows, flat ports) bucket an instance falls into.

    A quantum of ``None`` collapses that axis to the `COLLAPSED` sentinel:
    every instance lands in the same bucket, padded to the ensemble
    maximum (resolved in `build_buckets`).
    """
    return (
        COLLAPSED
        if m_quantum is None
        else _round_up(instance.num_coflows, m_quantum),
        COLLAPSED
        if p_quantum is None
        else _round_up(2 * instance.num_ports, p_quantum),
    )


@dataclasses.dataclass(frozen=True)
class Bucket:
    """A group of instances sharing one padded LP shape."""

    num_coflows: int  # padded M
    num_flat_ports: int  # padded 2N
    indices: tuple[int, ...]  # positions in the original ensemble

    def __len__(self) -> int:
        return len(self.indices)


def build_buckets(
    instances: Sequence[CoflowInstance],
    m_quantum: int | None = 8,
    p_quantum: int | None = 8,
) -> list[Bucket]:
    """Group ensemble members by padded shape, preserving input order.

    ``None`` quanta collapse the corresponding axis to the ensemble
    maximum — ``m_quantum=p_quantum=None`` yields a single bucket (one
    compile, maximal padding), the cheapest mode for cold one-shot sweeps.
    Degenerate axes keep their true (zero) padding: an M=0 instance under
    a numeric quantum stays in a zero-coflow bucket.
    """
    groups: dict[tuple[int, int], list[int]] = {}
    for i, inst in enumerate(instances):
        groups.setdefault(bucket_shape(inst, m_quantum, p_quantum), []).append(i)
    max_m = max((inst.num_coflows for inst in instances), default=0)
    max_p = max((2 * inst.num_ports for inst in instances), default=0)
    return [
        Bucket(
            num_coflows=max_m if m == COLLAPSED else m,
            num_flat_ports=max_p if p == COLLAPSED else p,
            indices=tuple(idx),
        )
        for (m, p), idx in sorted(groups.items())
    ]


def solve_ensemble_lp(
    instances: Sequence[CoflowInstance],
    iters: int = 3000,
    m_quantum: int | None = 8,
    p_quantum: int | None = 8,
    mesh=None,
) -> list[lp.LPSolution]:
    """Ordering-LP solutions for a whole ensemble, one batched solve per
    shape bucket.  Returns solutions in input order.

    With ``mesh`` the padded member axis of every bucket is sharded over
    the mesh's ``data`` axis (`NamedSharding`); bucket sizes that do not
    divide the device count round up with fully-masked members.

    Each bucket's host packing, device solve (until its batch is host
    arrays) and unpacking are the `repro.trace` spans ``lp.pack``,
    ``lp.wait`` and ``lp.unpack``; the counters ``lp.buckets``,
    ``lp.members`` (real members) and ``lp.member_slots`` (the members
    the batched solve runs, padding included) count what was solved.
    """
    instances = list(instances)
    solutions: list[lp.LPSolution | None] = [None] * len(instances)
    sharding = None
    n_shards = 1
    if mesh is not None:
        from repro.launch.mesh import data_axis_size, data_sharding

        sharding = data_sharding(mesh)
        n_shards = data_axis_size(mesh)
    for bucket in build_buckets(instances, m_quantum, p_quantum):
        members = [instances[i] for i in bucket.indices]
        count("lp.buckets")
        count("lp.members", len(members))
        count("lp.member_slots", lp.padded_members(len(members), n_shards))
        with span("lp.pack"):
            arrays = lp.pack_lp_arrays(
                members,
                pad_coflows=bucket.num_coflows,
                pad_ports=bucket.num_flat_ports,
                pad_members=_round_up(len(members), n_shards),
            )
        with span("lp.wait"):
            batch = lp.solve_subgradient_batch_arrays(
                arrays, iters=iters, sharding=sharding
            )
            # The batch as host arrays (assembled from every device's
            # shard under a mesh): the solve has finished here.
            batch = device_gather(batch)
        with span("lp.unpack"):
            sols = batch.unpack([inst.num_coflows for inst in members])
        for i, sol in zip(bucket.indices, sols):
            solutions[i] = sol
    return solutions  # type: ignore[return-value]
