"""Algorithm 1 end-to-end and the paper's ablation baselines (Sec. V-B).

Schemes:
  OURS        — LP-guided order + tau-aware greedy allocation + not-all-stop
                greedy circuit scheduling (the paper's Algorithm 1).
  WSPT-ORDER  — heuristic w_m / T_LB(D_m) order [31]; allocation+scheduling
                as OURS.
  LOAD-ONLY   — OURS order; allocation ignores the reconfiguration term.
  SUNFLOW-S   — OURS order+allocation; one-coflow-at-a-time intra-core
                scheduling (Sunflow-style, not-all-stop).
  BvN-S       — OURS order+allocation; Birkhoff–von Neumann decomposition
                intra-core scheduling under the all-stop model.

`run` is now a deprecation shim over the stage-based `repro.pipeline` API,
which regenerates all five schemes from declarative `SchemeSpec` registry
entries and adds an ensemble-batched execution path.  This module keeps:

  * the shared `ScheduleResult` type and the `total_weighted_cct` /
    `tail_cct` helpers (not deprecated — the pipeline re-exports them);
  * `_flow_priorities` / `_schedule_all_cores`, the flow-priority and
    per-core scheduling primitives both APIs (and `core.localsearch`,
    `collectives.planner`) build on;
  * `_legacy_run`, the original scheme-name if-chain, retained solely as
    the parity oracle for `tests/test_pipeline.py` — it is no longer on
    any execution path.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable

import numpy as np

from repro.core import bvn as bvn_mod
from repro.core import lp as lp_mod
from repro.core.allocation import Allocation, allocate
from repro.core.circuit import CoreSchedule, schedule_core, schedule_core_sequential
from repro.core.coflow import CoflowInstance
from repro.core.ordering import lp_guided_order, wspt_order
from repro.core.validate import ccts_from_schedules, validate_schedule

__all__ = ["ScheduleResult", "run", "SCHEMES", "total_weighted_cct", "tail_cct"]


@dataclasses.dataclass
class ScheduleResult:
    scheme: str
    order: np.ndarray  # (M,) coflow ids, highest priority first
    allocation: Allocation
    core_schedules: list[CoreSchedule] | None  # None for BvN (no circuits kept)
    ccts: np.ndarray  # (M,) realized completion times (original ids)
    total_weighted_cct: float
    lp: lp_mod.LPSolution | None

    def normalized_to(self, other: "ScheduleResult") -> float:
        return self.total_weighted_cct / other.total_weighted_cct


def total_weighted_cct(instance: CoflowInstance, ccts: np.ndarray) -> float:
    return float(np.dot(instance.weights, ccts))


def tail_cct(ccts: np.ndarray, q: float) -> float:
    """p-quantile CCT (paper reports p95/p99)."""
    return float(np.quantile(ccts, q))


def _flow_priorities(alloc: Allocation, order: np.ndarray, M: int) -> np.ndarray:
    """Priority per flow: coflow global rank, intra-coflow allocation order."""
    pos = np.empty(M, dtype=np.int64)
    pos[order] = np.arange(M)
    # Allocation emits flows in (order, largest-first) sequence, so the flow's
    # index within the table is already the intra-coflow tie-break.
    F = alloc.num_flows()
    return pos[alloc.coflow].astype(np.float64) * (F + 1) + np.arange(F)


def _schedule_all_cores(
    instance: CoflowInstance,
    alloc: Allocation,
    order: np.ndarray,
    sequential: bool = False,
    discipline: str = "reserving",
) -> list[CoreSchedule]:
    M, N, K = instance.num_coflows, instance.num_ports, instance.num_cores
    prio = _flow_priorities(alloc, order, M)
    pos = np.empty(M, dtype=np.int64)
    pos[order] = np.arange(M)
    out = []
    for k in range(K):
        sel = alloc.core == k
        if sequential:
            cs = schedule_core_sequential(
                coflow=alloc.coflow[sel],
                src=alloc.src[sel],
                dst=alloc.dst[sel],
                size=alloc.size[sel],
                priority=prio[sel],
                coflow_rank=pos,
                releases=instance.releases,
                num_ports=N,
                rate=float(instance.rates[k]),
                delta=instance.delta,
            )
        else:
            cs = schedule_core(
                coflow=alloc.coflow[sel],
                src=alloc.src[sel],
                dst=alloc.dst[sel],
                size=alloc.size[sel],
                priority=prio[sel],
                releases=instance.releases,
                num_ports=N,
                rate=float(instance.rates[k]),
                delta=instance.delta,
                discipline=discipline,
            )
        out.append(cs)
    return out


def _run_circuit_scheme(
    instance: CoflowInstance,
    scheme: str,
    order: np.ndarray,
    lp_sol: lp_mod.LPSolution | None,
    include_tau: bool = True,
    sequential: bool = False,
    discipline: str = "reserving",
    validate: bool = True,
) -> ScheduleResult:
    alloc = allocate(instance, order, include_tau=include_tau)
    schedules = _schedule_all_cores(
        instance, alloc, order, sequential=sequential, discipline=discipline
    )
    if validate:
        validate_schedule(instance, schedules)
    ccts = ccts_from_schedules(instance.num_coflows, schedules)
    return ScheduleResult(
        scheme=scheme,
        order=order,
        allocation=alloc,
        core_schedules=schedules,
        ccts=ccts,
        total_weighted_cct=total_weighted_cct(instance, ccts),
        lp=lp_sol,
    )


def _run_bvn(
    instance: CoflowInstance, order: np.ndarray, lp_sol
) -> ScheduleResult:
    alloc = allocate(instance, order, include_tau=True)
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
    return ScheduleResult(
        scheme="BVN-S",
        order=order,
        allocation=alloc,
        core_schedules=None,
        ccts=ccts,
        total_weighted_cct=total_weighted_cct(instance, ccts),
        lp=lp_sol,
    )


_DEPRECATION_WARNED = False


def run(
    instance: CoflowInstance,
    scheme: str = "ours",
    lp_method: str = "exact",
    lp_solution: lp_mod.LPSolution | None = None,
    discipline: str = "greedy",
    validate: bool = True,
) -> ScheduleResult:
    """Deprecated shim: run one scheme end-to-end via `repro.pipeline`.

    Equivalent to ``pipeline.get_pipeline(scheme, discipline=...,
    lp_method=...).run(instance, lp_solution=..., validate=...)``; kept so
    existing callers keep working.  Warns `DeprecationWarning` once per
    process.
    """
    global _DEPRECATION_WARNED
    if not _DEPRECATION_WARNED:
        _DEPRECATION_WARNED = True
        warnings.warn(
            "repro.core.scheduler.run is deprecated; build schemes from the "
            "repro.pipeline registry instead (pipeline.get_pipeline(scheme) "
            ".run(...) / .run_batch(...))",
            DeprecationWarning,
            stacklevel=2,
        )
    from repro.pipeline import get_pipeline

    return get_pipeline(scheme, discipline=discipline, lp_method=lp_method).run(
        instance, lp_solution=lp_solution, validate=validate
    )


def _legacy_run(
    instance: CoflowInstance,
    scheme: str = "ours",
    lp_method: str = "exact",
    lp_solution: lp_mod.LPSolution | None = None,
    discipline: str = "greedy",
    validate: bool = True,
) -> ScheduleResult:
    """The original string-dispatched scheme runner.

    Not reachable from `run` anymore; kept verbatim as the reference
    oracle `tests/test_pipeline.py` checks the stage-based pipeline (and
    its batched allocation path) against, bit for bit.
    """
    scheme = scheme.lower()
    needs_lp = scheme in ("ours", "load_only", "sunflow_s", "bvn_s")
    lp_sol = lp_solution
    if needs_lp and lp_sol is None:
        _, lp_sol = lp_guided_order(instance, method=lp_method)
    if scheme == "ours":
        return _run_circuit_scheme(
            instance, "OURS", lp_sol.order(), lp_sol,
            discipline=discipline, validate=validate,
        )
    if scheme == "wspt_order":
        return _run_circuit_scheme(
            instance, "WSPT-ORDER", wspt_order(instance), None,
            discipline=discipline, validate=validate,
        )
    if scheme == "load_only":
        return _run_circuit_scheme(
            instance, "LOAD-ONLY", lp_sol.order(), lp_sol,
            include_tau=False, discipline=discipline, validate=validate,
        )
    if scheme == "sunflow_s":
        return _run_circuit_scheme(
            instance, "SUNFLOW-S", lp_sol.order(), lp_sol,
            sequential=True, validate=validate,
        )
    if scheme == "bvn_s":
        return _run_bvn(instance, lp_sol.order(), lp_sol)
    raise ValueError(f"unknown scheme {scheme!r}")


#: Legacy scheme table (all keys route through the `run` shim); prefer
#: `repro.pipeline.list_schemes()` / `get_scheme` for the live registry.
SCHEMES: dict[str, Callable] = {
    "ours": run,
    "wspt_order": run,
    "load_only": run,
    "sunflow_s": run,
    "bvn_s": run,
}
