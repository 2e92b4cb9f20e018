"""The stage-composed scheduling pipeline and its builders.

`Pipeline` glues one `OrderStage`, one `AllocateStage` and one
`CircuitStage` together with two execution paths:

  * `run(instance)` — per-instance, parity with the legacy
    `repro.core.scheduler.run` (which now delegates here);
  * `run_batch(ensemble)` — batch-first and array-first: the instance
    list is packed **once** into the unified padded
    `repro.pipeline.ensemble_batch.EnsembleBatch` pytree, and ordering
    (`order_batch`), allocation (`allocate_batch_arrays` ->
    `AllocationBatch`) and circuit scheduling (`schedule_batch_arrays`)
    hand padded arrays to each other with no per-stage host re-padding;
    per-instance `ScheduleResult`s are materialized only at the end.
    Stages without an array form fall back to their legacy batched list
    APIs and then to the per-instance loop (``require_batch=True`` turns
    a fallback of a batch-capable stage into an error).  With ``mesh=``
    the batch is sharded across the mesh's ``data`` axis and every jitted
    stage runs SPMD over the ensemble.

`build_pipeline` materializes a declarative `SchemeSpec` into stages via
per-kind factories — scheme *names* never drive execution, only stage
kinds chosen at construction time.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import numpy as np

from repro.core.coflow import CoflowInstance
from repro.core.lp import LPSolution
from repro.core.scheduler import ScheduleResult, total_weighted_cct
from repro.core.validate import validate_schedule
from repro.pipeline import stages as st
from repro.pipeline.ensemble_batch import EnsembleBatch, build_ensemble_batch
from repro.pipeline.refine import (
    RefineOutcome,
    as_refine_spec,
    refine_batch_arrays,
    refine_key,
    refine_sequential,
)
from repro.pipeline.spec import SchemeSpec, get_scheme
from repro.trace import span

__all__ = ["Pipeline", "build_pipeline", "get_pipeline", "order_view"]


def order_view(weights, glb, releases, coflow_mask):
    """Minimal batch an ordering stage's ``order_batch`` accepts.

    Every `OrderStage.order_batch` implementation reads exactly four
    per-coflow fields of the ensemble — ``weights``, ``glb``,
    ``releases`` (all (Bp, Mp) f64) and ``coflow_mask`` — plus the
    separately-passed LP completion.  This view packages arbitrary
    arrays under that contract so callers that keep their own resident
    representation (the streaming service's slot pool, gathered to the
    dense convention) can run the *same* ordering code as `run_batch`
    without building an `EnsembleBatch`.  Masked (padding) entries sort
    to the tail in index order, exactly as in the full batch.
    """
    import types

    return types.SimpleNamespace(
        weights=weights, glb=glb, releases=releases, coflow_mask=coflow_mask
    )

#: Reserved `stage_cache` keys: the ensemble fingerprint guarding against
#: cross-ensemble reuse, and the shared `EnsembleBatch` built once per
#: ensemble (all schemes of a sweep read the same padded pytree).
_FINGERPRINT_KEY = "__ensemble_fingerprint__"
_ENSEMBLE_KEY = "__ensemble_batch__"


def _ensemble_fingerprint(instances, lp_solutions) -> tuple:
    """Identity of the (instances, lp_solutions) pair a stage_cache binds to.

    Holds strong references to the objects themselves (not bare ``id``s,
    which CPython reuses after garbage collection): as long as the cache
    lives, no other ensemble can alias this fingerprint, so reuse of one
    dict across different ensembles is a hard error instead of a silent
    stale-read.
    """
    return (
        tuple(instances),
        None if lp_solutions is None else tuple(lp_solutions),
    )


def _same_fingerprint(a: tuple, b: tuple) -> bool:
    """Element-wise *identity* comparison of two fingerprints (instances
    and LP solutions hold arrays, so ``==`` equality is neither cheap nor
    well-defined; identity is the contract the cache binds to)."""

    def same_seq(xs, ys):
        if xs is None or ys is None:
            return xs is ys
        return len(xs) == len(ys) and all(
            x is y for x, y in zip(xs, ys)
        )

    return same_seq(a[0], b[0]) and same_seq(a[1], b[1])


@dataclasses.dataclass
class Pipeline:
    """Order → allocate → circuit-schedule, as composed stages."""

    spec: SchemeSpec
    order_stage: Any
    allocate_stage: Any
    circuit_stage: Any

    def _resolve_refine(self, refine):
        """Effective `RefineSpec` for a run: an explicit ``refine=``
        argument wins, ``None`` defers to the spec, ``False`` disables a
        spec-level refine."""
        if refine is None:
            refine = self.spec.refine
        if refine in (None, False):
            return None
        return as_refine_spec(refine)

    def _sequential_refine_eval(self, instance):
        """Objective callback for `refine_sequential` through THIS
        pipeline's per-instance stages (so sequential refinement evaluates
        exactly the scheme's allocation + circuit configuration)."""

        def evaluate(order: np.ndarray) -> float:
            alloc = self.allocate_stage.allocate(instance, order)
            _, ccts = self.circuit_stage.schedule(instance, alloc, order)
            return total_weighted_cct(instance, ccts)

        return evaluate

    def run(
        self,
        instance: CoflowInstance,
        lp_solution: LPSolution | None = None,
        validate: bool = True,
        refine=None,
    ) -> ScheduleResult:
        """Run one instance end to end (legacy `scheduler.run` parity).

        ``lp_solution`` shares one LP solve across schemes; ordering stages
        that do not consume the LP ignore it (and record None).
        ``refine`` enables candidate-search refinement of the order on the
        realized objective (a `RefineSpec` / ``True`` / field dict;
        default None defers to ``spec.refine``, ``False`` disables it) —
        here via the per-instance `refine_sequential` oracle, bit-identical
        to `run_batch`'s batched search.
        """
        order, lp_sol = self.order_stage.order(instance, lp_solution)
        eff_refine = self._resolve_refine(refine)
        if eff_refine is not None:
            order, _, _, _, _ = refine_sequential(
                order, eff_refine, self._sequential_refine_eval(instance)
            )
        alloc = self.allocate_stage.allocate(instance, order)
        schedules, ccts = self.circuit_stage.schedule(instance, alloc, order)
        if validate and schedules is not None:
            validate_schedule(instance, schedules)
        return ScheduleResult(
            scheme=self.spec.name,
            order=order,
            allocation=alloc,
            core_schedules=schedules,
            ccts=ccts,
            total_weighted_cct=total_weighted_cct(instance, ccts),
            lp=lp_sol,
        )

    def _order_key(self) -> tuple:
        """Stage-identity key for sharing computed orders across pipelines
        (same kind + config on the same ensemble => same orders)."""
        st = self.order_stage
        return (
            "order", st.kind,
            getattr(st, "method", None), getattr(st, "iters", None),
        )

    def _refine_key(self, refine_t: tuple) -> tuple:
        """Stage-identity key of a refinement pass.  Refined orders depend
        on everything the search evaluates through — the refine config AND
        the allocation/circuit configuration — so all of it joins the
        key."""
        ast = self.allocate_stage
        cst = self.circuit_stage
        return (
            "refine", refine_t,
            ast.kind, getattr(ast, "include_tau", None),
            cst.kind, getattr(cst, "discipline", None),
            getattr(cst, "backend", None),
        ) + self._order_key()

    def _alloc_key(self, refine_t: tuple | None = None) -> tuple:
        st = self.allocate_stage
        return (
            "alloc", st.kind, getattr(st, "include_tau", None),
        ) + (
            self._order_key() if refine_t is None
            else self._refine_key(refine_t)
        )

    def _circuit_key(self, refine_t: tuple | None = None) -> tuple:
        st = self.circuit_stage
        return (
            "circuit", st.kind,
            getattr(st, "discipline", None), getattr(st, "backend", None),
        ) + self._alloc_key(refine_t)

    def run_batch(
        self,
        instances: Sequence[CoflowInstance],
        lp_solutions: Sequence[LPSolution | None] | None = None,
        validate: bool = True,
        require_batch: bool = False,
        stage_cache: dict | None = None,
        ensemble: EnsembleBatch | None = None,
        mesh=None,
        refine=None,
    ) -> list[ScheduleResult]:
        """Run a whole ensemble as one array pipeline over an `EnsembleBatch`.

        The instance list is packed exactly once into the unified padded
        pytree (``ensemble`` plugs a prebuilt one in; with a
        ``stage_cache`` the build is shared across every scheme of a
        sweep) and the stages exchange padded arrays: `order_batch`
        produces the (Bp, Mp) order array, `allocate_batch_arrays` the
        `AllocationBatch`, `schedule_batch_arrays` the calendar outputs.
        Per-instance `ScheduleResult`s are materialized only at the end.
        ``mesh`` shards the member axis over the mesh's ``data`` axis
        (see `repro.pipeline.ensemble_batch`); results are bit-identical
        to the unsharded run.

        ``lp_solutions`` plugs the output of `solve_subgradient_batch` /
        `solve_ensemble_lp` straight in (one solution per instance, input
        order).  The stages time themselves as `repro.trace` spans
        (``pipeline.*``, ``alloc.*``, ``calendar.*``).

        ``stage_cache`` shares computed stage outputs between pipelines
        run over the *same* ``(instances, lp_solutions)``: pass one dict
        to every scheme's `run_batch` and schemes that differ only in
        their circuit stage (e.g. OURS / SUNFLOW-S / BvN-S) reuse one
        ordering pass and one batched allocation — and pipelines that
        differ only in circuit *discipline* (e.g. greedy vs reserving
        OURS, as `sweep(certify=True)` runs) additionally share everything
        up to the circuit stage.  The cache binds to the ensemble it was
        first used on (an identity fingerprint of instances and LP
        solutions): reusing one dict across different ensembles raises
        `ValueError` instead of silently returning stale stage outputs.

        ``refine`` enables candidate-search refinement of the computed
        orders on the realized objective (a `RefineSpec` / ``True`` /
        field dict; default None defers to ``spec.refine``, ``False``
        disables it).  With array-capable allocation and circuit stages
        the search runs batched — candidate orders become extra member
        rows of the same `EnsembleBatch` via `refine_batch_arrays`, one
        alloc+circuit pass per round over all instances × candidates —
        otherwise it falls back to the per-instance `refine_sequential`
        oracle (an error under ``require_batch`` when the stages ARE
        array-capable, e.g. the ``"loop"`` circuit backend).  The refine
        config and the alloc/circuit configuration join the stage-cache
        key chain, so refined and unrefined pipelines share the ordering
        pass but nothing downstream of it.
        """
        instances = list(instances)
        B = len(instances)
        if lp_solutions is not None:
            lp_solutions = list(lp_solutions)
            if len(lp_solutions) != B:
                raise ValueError("lp_solutions length mismatch")
        if stage_cache is not None:
            fp = _ensemble_fingerprint(instances, lp_solutions)
            prev = stage_cache.setdefault(_FINGERPRINT_KEY, fp)
            if prev is not fp and not _same_fingerprint(prev, fp):
                raise ValueError(
                    "stage_cache reuse across different ensembles: this "
                    "cache was built for another (instances, lp_solutions) "
                    "pair — pass a fresh dict per ensemble"
                )
        if B == 0:
            return []

        # --- the unified padded pytree: built once per ensemble ----------
        if ensemble is None and stage_cache is not None:
            ensemble = stage_cache.get(_ENSEMBLE_KEY)
        if ensemble is None:
            # run_batch never solves the ordering LP itself (solutions are
            # supplied, or LP-needing stages solve per instance), so skip
            # packing the heavy LP solver inputs.
            with span("pipeline.build"):
                ensemble = build_ensemble_batch(
                    instances, mesh=mesh, with_lp_arrays=False
                )
        elif mesh is not None:
            # A cached/provided batch carries its own sharding; a
            # *different* explicit mesh request must not be silently
            # dropped.  (mesh=None inherits whatever the batch has.)
            from repro.launch.mesh import data_sharding

            if ensemble.sharding != data_sharding(mesh):
                raise ValueError(
                    "run_batch(mesh=...) does not match the sharding of "
                    "the cached/provided EnsembleBatch — pass the same "
                    "mesh on every call sharing a stage_cache (or a "
                    "fresh cache)"
                )
        if stage_cache is not None:
            stage_cache.setdefault(_ENSEMBLE_KEY, ensemble)
        Ms = ensemble.num_coflows

        # --- ordering: one (Bp, Mp) array for the whole ensemble ----------
        cached = None if stage_cache is None else stage_cache.get(
            self._order_key()
        )
        if cached is None:
            orders_arr = None
            lp_list = lp_solutions
            order_batch_fn = getattr(self.order_stage, "order_batch", None)
            if order_batch_fn is not None:
                if getattr(self.order_stage, "needs_lp", False):
                    if lp_solutions is not None and all(
                        sol is not None for sol in lp_solutions
                    ):
                        comp = np.zeros(ensemble.weights.shape)
                        for b, sol in enumerate(lp_solutions):
                            comp[b, : Ms[b]] = sol.completion
                        orders_arr = order_batch_fn(ensemble, comp)
                else:
                    orders_arr = order_batch_fn(ensemble)
                    lp_list = [None] * B
            if orders_arr is None:
                # Stage has no array form (or needs an LP it must solve
                # itself): per-instance ordering, padded once.
                sols_in = lp_solutions or [None] * B
                ordered = [
                    self.order_stage.order(inst, sol)
                    for inst, sol in zip(instances, sols_in)
                ]
                orders_arr = ensemble.pad_orders([o for o, _ in ordered])
                lp_list = [s for _, s in ordered]
            cached = (orders_arr, lp_list)
            if stage_cache is not None:
                stage_cache[self._order_key()] = cached
        orders_arr, lp_list = cached
        lp_list = lp_list if lp_list is not None else [None] * B

        # --- refinement: candidate search on the realized objective -------
        eff_refine = self._resolve_refine(refine)
        refine_t = None
        if eff_refine is not None:
            refine_t = refine_key(eff_refine)
            outcome = None if stage_cache is None else stage_cache.get(
                self._refine_key(refine_t)
            )
            if outcome is None:
                alloc_arrays_fn = getattr(
                    self.allocate_stage, "allocate_batch_arrays", None
                )
                cct_arrays_fn = getattr(
                    self.circuit_stage, "cct_batch_arrays", None
                )
                batch_capable = (
                    alloc_arrays_fn is not None and cct_arrays_fn is not None
                )
                if batch_capable and getattr(
                    self.circuit_stage, "backend", "batch"
                ) == "batch":
                    outcome = refine_batch_arrays(
                        ensemble, orders_arr, eff_refine,
                        alloc_fn=alloc_arrays_fn, cct_fn=cct_arrays_fn,
                    )
                else:
                    if require_batch and batch_capable:
                        raise RuntimeError(
                            f"run_batch fell back to the sequential "
                            f"refinement loop for scheme {self.spec.key!r} "
                            f"(circuit stage "
                            f"{type(self.circuit_stage).__name__}, backend "
                            f"{getattr(self.circuit_stage, 'backend', None)!r})"
                        )
                    ref_orders = np.array(orders_arr)
                    objective = np.zeros(B)
                    base_obj = np.zeros(B)
                    rounds = evals = 0
                    for b, inst in enumerate(instances):
                        o2, cur_b, base_b, r_b, e_b = refine_sequential(
                            orders_arr[b, : Ms[b]], eff_refine,
                            self._sequential_refine_eval(inst),
                        )
                        ref_orders[b, : Ms[b]] = o2
                        objective[b], base_obj[b] = cur_b, base_b
                        rounds = max(rounds, r_b)
                        evals += e_b
                    outcome = RefineOutcome(
                        orders=ref_orders, objective=objective,
                        base_objective=base_obj, rounds=rounds,
                        evaluations=evals, batched=False,
                    )
                if stage_cache is not None:
                    stage_cache[self._refine_key(refine_t)] = outcome
            orders_arr = outcome.orders

        orders = [orders_arr[b, : Ms[b]] for b in range(B)]

        # --- allocation: AllocationBatch, materialized once ---------------
        a_cached = None if stage_cache is None else stage_cache.get(
            self._alloc_key(refine_t)
        )
        if a_cached is None:
            alloc_batch = None
            arrays_fn = getattr(
                self.allocate_stage, "allocate_batch_arrays", None
            )
            if arrays_fn is not None:
                alloc_batch = arrays_fn(ensemble, orders_arr)
            if alloc_batch is not None:
                with span("alloc.materialize"):
                    allocs = alloc_batch.materialize(ensemble)
            else:
                batch_fn = getattr(
                    self.allocate_stage, "allocate_batch", None
                )
                allocs = (
                    batch_fn(instances, orders)
                    if batch_fn is not None
                    else None
                )
                if allocs is None:
                    if require_batch:
                        raise RuntimeError(
                            f"run_batch fell back to the per-instance "
                            f"allocation loop for scheme {self.spec.key!r} "
                            f"(allocation stage "
                            f"{type(self.allocate_stage).__name__} "
                            f"has no batched path)"
                        )
                    allocs = [
                        self.allocate_stage.allocate(inst, o)
                        for inst, o in zip(instances, orders)
                    ]
            a_cached = (alloc_batch, allocs)
            if stage_cache is not None:
                stage_cache[self._alloc_key(refine_t)] = a_cached
        alloc_batch, allocs = a_cached

        # --- circuit: padded calendar off the pytrees ---------------------
        # Stages without any batched form (sequential / bvn / fluid —
        # baselines whose calendars are inherently per-instance) run the
        # loop.  ``require_batch`` turns a *fallback* of a batch-capable
        # stage (e.g. backend "loop") into an error, but leaves loop-only
        # stages alone.
        pairs = None if stage_cache is None else stage_cache.get(
            self._circuit_key(refine_t)
        )
        if pairs is None:
            arrays_fn = getattr(
                self.circuit_stage, "schedule_batch_arrays", None
            )
            batch_fn = getattr(self.circuit_stage, "schedule_batch", None)
            if arrays_fn is not None and alloc_batch is not None:
                pairs = arrays_fn(ensemble, alloc_batch)
            if pairs is None and batch_fn is not None:
                pairs = batch_fn(instances, allocs, orders)
            if pairs is None:
                if require_batch and (
                    arrays_fn is not None or batch_fn is not None
                ):
                    raise RuntimeError(
                        f"run_batch fell back to the per-instance circuit "
                        f"loop for scheme {self.spec.key!r} (circuit stage "
                        f"{type(self.circuit_stage).__name__}, backend "
                        f"{getattr(self.circuit_stage, 'backend', None)!r})"
                    )
                pairs = [
                    self.circuit_stage.schedule(inst, alloc, order)
                    for inst, order, alloc in zip(instances, orders, allocs)
                ]
            if stage_cache is not None:
                stage_cache[self._circuit_key(refine_t)] = pairs

        # --- materialize per-instance results (end of the pipeline) -------
        results = []
        for i, (inst, order, lp_sol, alloc) in enumerate(
            zip(instances, orders, lp_list, allocs)
        ):
            schedules, ccts = pairs[i]
            if validate and schedules is not None:
                with span("pipeline.validate"):
                    validate_schedule(inst, schedules)
            results.append(
                ScheduleResult(
                    scheme=self.spec.name,
                    order=order,
                    allocation=alloc,
                    core_schedules=schedules,
                    ccts=ccts,
                    total_weighted_cct=total_weighted_cct(inst, ccts),
                    lp=lp_sol,
                )
            )
        return results


# ---------------------------------------------------------------------------
# Spec -> stages
# ---------------------------------------------------------------------------

_ORDER_STAGES = {
    "lp": lambda lp_method, lp_iters: st.LPOrder(lp_method, lp_iters),
    "wspt": lambda lp_method, lp_iters: st.WsptOrder(),
    "fifo": lambda lp_method, lp_iters: st.FifoOrder(),
}

_CIRCUIT_STAGES = {
    "list": lambda discipline, backend: st.ListCircuit(discipline, backend),
    "sequential": lambda discipline, backend: st.SequentialCircuit(),
    "bvn": lambda discipline, backend: st.BvnCircuit(),
    "fluid": lambda discipline, backend: st.FluidCircuit(),
}


def build_pipeline(
    spec: SchemeSpec,
    *,
    discipline: str = "greedy",
    lp_method: str = "exact",
    lp_iters: int = 3000,
    circuit_backend: str = "batch",
) -> Pipeline:
    """Materialize a `SchemeSpec` into an executable `Pipeline`.

    ``discipline`` applies to list-scheduler circuits whose spec leaves it
    open (the spec's own pin wins); ``lp_method``/``lp_iters`` configure
    LP-ordering stages that have to solve for themselves.
    ``circuit_backend`` selects the list scheduler's `run_batch` path:
    ``"batch"`` (default — the whole-ensemble padded event calendar) or
    ``"loop"`` (per-instance NumPy oracle).  The batch calendar is the
    device calendar on TPU and GPU and the host calendar on a CPU, chosen
    by platform (`repro.pipeline.batch_circuit`).  Stages without a
    batched form ignore the backend.
    """
    try:
        order_stage = _ORDER_STAGES[spec.order](lp_method, lp_iters)
    except KeyError:
        raise ValueError(f"unknown order stage kind {spec.order!r}") from None
    try:
        circuit_stage = _CIRCUIT_STAGES[spec.circuit](
            spec.discipline or discipline, circuit_backend
        )
    except KeyError:
        raise ValueError(
            f"unknown circuit stage kind {spec.circuit!r}"
        ) from None
    return Pipeline(
        spec=spec,
        order_stage=order_stage,
        allocate_stage=st.GreedyAllocate(include_tau=spec.include_tau),
        circuit_stage=circuit_stage,
    )


def get_pipeline(scheme: str, **kwargs) -> Pipeline:
    """Pipeline for a registered scheme key (see `repro.pipeline.spec`)."""
    return build_pipeline(get_scheme(scheme), **kwargs)
