"""Stage protocols and concrete stages for the scheduling pipeline.

Algorithm 1's three phases, as pluggable objects:

  * `OrderStage`    — global coflow order (Line 2 / the ordering baselines);
  * `AllocateStage` — inter-core flow allocation (Lines 3–15), with an
    optional ensemble-batched path (`allocate_batch`);
  * `CircuitStage`  — intra-core scheduling (Lines 16–30 / the scheduling
    baselines), returning per-core schedules (when circuit structures are
    kept) and the realized per-coflow CCT vector, with an optional
    ensemble-batched path (`schedule_batch`).

Stages are tiny adapters over the reference implementations in
`repro.core.*`; the per-instance NumPy paths stay the oracle, and the
batched compute paths are `repro.pipeline.batch_alloc` (vectorized
allocation, via `GreedyAllocate.allocate_batch`) and
`repro.pipeline.batch_circuit` (the padded event-calendar list scheduler,
via `ListCircuit.schedule_batch`).
"""

from __future__ import annotations

from typing import Protocol, runtime_checkable

import numpy as np

from repro.core import bvn as bvn_mod
from repro.core import lp as lp_mod
from repro.core.allocation import Allocation, allocate
from repro.core.circuit import CoreSchedule
from repro.core.coflow import CoflowInstance
from repro.core.eps import eps_ccts, fluid_schedule_core
from repro.core.ordering import fifo_order, lp_guided_order, wspt_order
from repro.core.scheduler import _flow_priorities, _schedule_all_cores
from repro.core.validate import ccts_from_schedules

__all__ = [
    "OrderStage",
    "AllocateStage",
    "CircuitStage",
    "LPOrder",
    "WsptOrder",
    "FifoOrder",
    "GreedyAllocate",
    "ListCircuit",
    "SequentialCircuit",
    "BvnCircuit",
    "FluidCircuit",
]


# ---------------------------------------------------------------------------
# Protocols
# ---------------------------------------------------------------------------


@runtime_checkable
class OrderStage(Protocol):
    """Produces the global coflow priority order (highest first)."""

    kind: str
    needs_lp: bool

    def order(
        self,
        instance: CoflowInstance,
        lp_solution: lp_mod.LPSolution | None = None,
    ) -> tuple[np.ndarray, lp_mod.LPSolution | None]:
        """Return (order, lp_solution-or-None).  A shared LP solution may be
        passed in to amortize one solve across schemes; stages that do not
        use the LP return None so results record no spurious solution."""
        ...


@runtime_checkable
class AllocateStage(Protocol):
    """Assigns every flow whole to one core along the global order."""

    kind: str

    def allocate(
        self, instance: CoflowInstance, order: np.ndarray
    ) -> Allocation:
        ...

    # Optional batched forms (absent or returning None means fall back):
    #   allocate_batch_arrays(ensemble, orders) -> AllocationBatch | None
    #     — the array path over the unified EnsembleBatch pytree;
    #   allocate_batch(instances, orders) -> list[Allocation] | None
    #     — the legacy list path.


@runtime_checkable
class CircuitStage(Protocol):
    """Schedules each core's flows; returns (schedules-or-None, ccts)."""

    kind: str

    def schedule(
        self,
        instance: CoflowInstance,
        alloc: Allocation,
        order: np.ndarray,
    ) -> tuple[list[CoreSchedule] | None, np.ndarray]:
        ...

    # Optional batched forms (absent or returning None means fall back):
    #   schedule_batch_arrays(ensemble, alloc_batch) ->
    #     list[(schedules, ccts)] | None — the array path;
    #   schedule_batch(instances, allocs, orders) ->
    #     list[(schedules, ccts)] | None — the legacy list path.
    # Optional batched order form on OrderStage:
    #   order_batch(ensemble, lp_completion=None) -> (Bp, Mp) array | None.


# ---------------------------------------------------------------------------
# Ordering stages
# ---------------------------------------------------------------------------


def _masked_stable_order(key: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """(B, Mp) stable argsort with padded slots pushed to the tail.

    Row ``b`` restricted to its real prefix is bit-identical to the
    per-instance ``np.argsort(key_b, kind="stable")``: masking padded
    slots to +inf cannot disturb the relative order of real entries.
    """
    return np.argsort(
        np.where(mask, key, np.inf), axis=1, kind="stable"
    )


class LPOrder:
    """LP-guided order: non-decreasing T~_m (Algorithm 1 Line 2)."""

    kind = "lp"
    needs_lp = True

    def __init__(self, method: str = "exact", iters: int = 3000):
        self.method = method
        self.iters = iters

    def order(self, instance, lp_solution=None):
        if lp_solution is None:
            kwargs = (
                {"iters": self.iters} if self.method == "subgradient" else {}
            )
            _, lp_solution = lp_guided_order(
                instance, method=self.method, **kwargs
            )
        return lp_solution.order(), lp_solution

    def order_batch(self, ensemble, lp_completion=None):
        """(Bp, Mp) padded orders from padded LP completion times; None
        (fall back to the per-instance loop) when no shared LP batch is
        available — this stage must then solve per instance."""
        if lp_completion is None:
            return None
        return _masked_stable_order(lp_completion, ensemble.coflow_mask)


class WsptOrder:
    """WSPT-ORDER baseline [31]: non-increasing w_m / T_LB(D_m)."""

    kind = "wspt"
    needs_lp = False

    def order(self, instance, lp_solution=None):
        return wspt_order(instance), None

    def order_batch(self, ensemble, lp_completion=None):
        # Same f64 elementwise arithmetic as `wspt_order`, whole bucket.
        score = ensemble.weights / np.maximum(ensemble.glb, 1e-300)
        return _masked_stable_order(-score, ensemble.coflow_mask)


class FifoOrder:
    """Release-time FIFO — ablation reference."""

    kind = "fifo"
    needs_lp = False

    def order(self, instance, lp_solution=None):
        return fifo_order(instance), None

    def order_batch(self, ensemble, lp_completion=None):
        return _masked_stable_order(
            ensemble.releases, ensemble.coflow_mask
        )


# ---------------------------------------------------------------------------
# Allocation stage
# ---------------------------------------------------------------------------


class GreedyAllocate:
    """Prefix-aware greedy allocation (Lines 3–15); tau-blind when
    ``include_tau=False`` (LOAD-ONLY)."""

    kind = "greedy"

    def __init__(self, include_tau: bool = True):
        self.include_tau = include_tau

    def allocate(self, instance, order):
        return allocate(instance, order, include_tau=self.include_tau)

    def allocate_batch(self, instances, orders):
        from repro.pipeline.batch_alloc import allocate_batch

        return allocate_batch(
            instances, orders, include_tau=self.include_tau
        )

    def allocate_batch_arrays(self, ensemble, orders):
        """Array form: `EnsembleBatch` + (Bp, Mp) orders -> `AllocationBatch`."""
        from repro.pipeline.batch_alloc import allocate_batch_arrays

        return allocate_batch_arrays(
            ensemble, orders, include_tau=self.include_tau
        )


# ---------------------------------------------------------------------------
# Circuit stages
# ---------------------------------------------------------------------------


class ListCircuit:
    """Not-all-stop greedy port-matching list scheduler (Lines 16–30).

    Two backends with bit-identical schedules: ``"batch"`` (default) runs
    the whole ensemble's padded event calendar as one JAX program
    (`repro.pipeline.batch_circuit`); ``"loop"`` keeps the per-instance
    NumPy event loop — the parity oracle and the explicit fallback,
    mirroring the ``alloc="batch"|"loop"`` convention.  ``schedule_batch``
    returns None under the loop backend so `Pipeline.run_batch` can fall
    back (or error under ``require_batch``).  The batch backend runs the
    device calendar on TPU and GPU and the host calendar on a CPU, chosen
    by platform inside `repro.pipeline.batch_circuit`.
    """

    kind = "list"

    def __init__(self, discipline: str = "greedy", backend: str = "batch"):
        if backend not in ("batch", "loop"):
            raise ValueError(f"unknown circuit backend {backend!r}")
        self.discipline = discipline
        self.backend = backend

    def schedule(self, instance, alloc, order):
        schedules = _schedule_all_cores(
            instance, alloc, order, discipline=self.discipline
        )
        return schedules, ccts_from_schedules(instance.num_coflows, schedules)

    def schedule_batch(self, instances, allocs, orders):
        if self.backend != "batch":
            return None
        from repro.pipeline.batch_circuit import schedule_batch

        return schedule_batch(
            instances, allocs, orders, discipline=self.discipline
        )

    def schedule_batch_arrays(self, ensemble, alloc_batch):
        """Array form: padded pytrees in, per-instance (schedules, ccts)
        out; None under the ``"loop"`` backend so `Pipeline.run_batch`
        falls back (or errors under ``require_batch``)."""
        if self.backend != "batch":
            return None
        from repro.pipeline.batch_circuit import schedule_batch_arrays

        return schedule_batch_arrays(
            ensemble, alloc_batch, discipline=self.discipline
        )

    def cct_batch_arrays(self, ensemble, alloc_batch):
        """Lean CCT-only array form — candidate-search refinement's inner
        evaluation (`repro.pipeline.refine`): same calendar, no
        `CoreSchedule` materialization.  None under the ``"loop"``
        backend (refinement then runs its sequential oracle)."""
        if self.backend != "batch":
            return None
        from repro.pipeline.batch_circuit import cct_batch_arrays

        return cct_batch_arrays(
            ensemble, alloc_batch, discipline=self.discipline
        )


class SequentialCircuit:
    """Sunflow-style one-coflow-at-a-time intra-core scheduling."""

    kind = "sequential"

    def schedule(self, instance, alloc, order):
        schedules = _schedule_all_cores(instance, alloc, order, sequential=True)
        return schedules, ccts_from_schedules(instance.num_coflows, schedules)


class BvnCircuit:
    """Birkhoff–von Neumann decomposition under the all-stop model.

    No circuit structures are kept (matching the legacy BVN-S path), so the
    returned schedule list is None and feasibility validation is skipped.
    """

    kind = "bvn"

    def schedule(self, instance, alloc, order):
        M, N, K = instance.num_coflows, instance.num_ports, instance.num_cores
        per_core = alloc.per_core_demand(M, N)
        ccts = np.zeros(M)
        for k in range(K):
            mats = [(int(m), per_core[k, m]) for m in order]
            done = bvn_mod.bvn_execute_core(
                mats, instance.releases, float(instance.rates[k]), instance.delta
            )
            for m, t_done in done.items():
                ccts[m] = max(ccts[m], t_done)
        return None, ccts


class FluidCircuit:
    """EPS priority fluid rate allocation (paper Theorem 2; delta = 0)."""

    kind = "fluid"

    def schedule(self, instance, alloc, order):
        if instance.delta != 0:
            # Theorem 2 models electrical packet switching: no circuit
            # reconfiguration exists, so scheduling an OCS instance with
            # delta > 0 here would silently drop the delay and report
            # invalid (unfairly favorable) CCTs.
            raise ValueError("EPS fluid scheduling requires delta == 0")
        M, N, H = instance.num_coflows, instance.num_ports, instance.num_cores
        prio = _flow_priorities(alloc, order, M)
        schedules = []
        for h in range(H):
            sel = alloc.core == h
            schedules.append(
                fluid_schedule_core(
                    coflow=alloc.coflow[sel],
                    src=alloc.src[sel],
                    dst=alloc.dst[sel],
                    size=alloc.size[sel],
                    priority=prio[sel],
                    releases=instance.releases,
                    num_ports=N,
                    rate=float(instance.rates[h]),
                )
            )
        # EpsCoreSchedule is not a circuit CoreSchedule: no establishment
        # times exist under fluid rates, so nothing to validate downstream.
        return None, eps_ccts(instance, schedules)
