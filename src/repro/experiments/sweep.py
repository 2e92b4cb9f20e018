"""Instance-sweep driver: one batched LP phase, batch-first scheme runs.

`sweep` is the engine behind the figure reproductions: it takes a whole
ensemble of instances, solves the ordering LP for all of them at once
(`ensemble.solve_ensemble_lp`, shape-bucketed array solves), then
executes every requested scheme through the stage-based `repro.pipeline`
API.  With ``alloc="batch"`` (the default) each scheme's
`Pipeline.run_batch` packs the ensemble once into the unified
`EnsembleBatch` pytree (shared across schemes via the stage cache) and
runs ordering, allocation and circuit scheduling as one array pipeline;
``alloc="loop"`` keeps the per-instance NumPy reference path (the oracle
the batched path is bit-checked against).  ``mesh=`` shards the batched
stages' ensemble axis across the mesh's ``data`` axis, bit-identically.

``cache=`` plugs in the content-addressed result cache
(`repro.experiments.cache.SweepCache`): every (instance, scheme) cell is
keyed by instance + scheme + config + code fingerprint, cache hits
short-circuit the LP *and* the batched pipeline for that cell, and only
missing cells are computed (and stored back).  Re-running an identical
sweep computes zero cells; a perturbed sweep recomputes exactly the
changed ones.  `SweepResult.cache_stats` reports the per-call counters.

``lp_method``:
  * ``"batch"``       — batched subgradient (default; fast, ~1% of optimum).
  * ``"exact"``       — per-instance HiGHS.  Required when downstream
                        consumers need a true *lower bound* (approximation-
                        ratio figures, certificates): the subgradient
                        objective upper-bounds the LP optimum.
  * ``"subgradient"`` — per-instance JAX solver (reference/baseline for the
                        batched engine's throughput claims).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Mapping, Sequence

import numpy as np

from repro import pipeline as pipeline_mod
from repro import trace
from repro.core import lp, scheduler, theory
from repro.core.coflow import CoflowInstance
from repro.experiments import cache as cache_mod
from repro.experiments.ensemble import solve_ensemble_lp
from repro.experiments.results import save_rows, tail_columns

__all__ = ["DEFAULT_SCHEMES", "InstanceRecord", "SweepResult", "sweep"]

DEFAULT_SCHEMES = pipeline_mod.PAPER_SCHEMES


@dataclasses.dataclass
class InstanceRecord:
    """Everything computed for one ensemble member.

    ``lp`` / ``results`` / certificates may be the cached stand-ins
    (`repro.experiments.cache.CachedLP` etc.) when the cell came out of
    the sweep cache: they carry exactly the fields the row export reads.
    """

    index: int
    meta: dict[str, Any]
    lp: Any  # lp.LPSolution | cache.CachedLP
    results: dict[str, Any]  # scheme -> ScheduleResult | CachedScheduleResult
    cert_greedy: Any | None = None
    cert_reserving: Any | None = None

    def _base(self, base: str):
        """Normalization baseline; falls back to the first scheme run when
        the requested one (default "ours") was not part of the sweep."""
        return self.results.get(base) or next(iter(self.results.values()))

    def normalized(self, base: str = "ours") -> dict[str, float]:
        b = self._base(base).total_weighted_cct
        return {s: r.total_weighted_cct / b for s, r in self.results.items()}

    def tail_ratio(self, q: float, base: str = "ours") -> dict[str, float]:
        b = scheduler.tail_cct(self._base(base).ccts, q)
        return {
            s: scheduler.tail_cct(r.ccts, q) / b
            for s, r in self.results.items()
        }


@dataclasses.dataclass
class SweepResult:
    """A sweep's records plus its host spans and counters (`repro.trace`:
    seconds per span name and totals per counter name over the call)."""

    records: list[InstanceRecord]
    lp_method: str
    lp_time_s: float  # the ``sweep.lp`` span
    wall_time_s: float
    cache_stats: dict[str, int] | None = None
    spans: dict[str, float] = dataclasses.field(default_factory=dict)
    counts: dict[str, int] = dataclasses.field(default_factory=dict)

    def __len__(self) -> int:
        return len(self.records)

    def rows(self, base: str = "ours") -> list[dict[str, Any]]:
        """One flat row per (instance, scheme) — the JSON/CSV export shape.

        Besides the normalized aggregate/tail ratios, every row carries the
        scheme's absolute tail CCTs (``p95_cct`` / ``p99_cct``, via
        `scheduler.tail_cct`) so figure scripts can plot tails without
        re-deriving them from raw schedules.  Rows are derived from the
        per-cell absolutes only, so cached and freshly computed cells
        export byte-identically.
        """
        out = []
        for rec in self.records:
            nw = rec.normalized(base)
            p95 = rec.tail_ratio(0.95, base)
            p99 = rec.tail_ratio(0.99, base)
            for s, res in rec.results.items():
                row: dict[str, Any] = {"instance": rec.index, **rec.meta}
                row.update(
                    scheme=s,
                    total_weighted_cct=res.total_weighted_cct,
                    norm_weighted_cct=nw[s],
                    norm_p95=p95[s],
                    norm_p99=p99[s],
                    **tail_columns(res.ccts),
                    lp_objective=rec.lp.objective,
                )
                if s == "ours" and rec.cert_greedy is not None:
                    row["approx_ratio"] = rec.cert_greedy.approx_ratio
                    row["bound"] = rec.cert_greedy.bound
                if s == "ours" and rec.cert_reserving is not None:
                    row["approx_ratio_reserving"] = (
                        rec.cert_reserving.approx_ratio
                    )
                    row["certified_reserving"] = rec.cert_reserving.ok()
                out.append(row)
        return out

    def save(self, name: str, base: str = "ours") -> tuple[str, str]:
        return save_rows(name, self.rows(base))


def _cell_payload(results: dict, scheme: str, sol, cert_g, cert_r) -> dict:
    """The cached absolutes of one (instance, scheme) cell."""
    res = results[scheme]
    payload: dict[str, Any] = {
        "total_weighted_cct": float(res.total_weighted_cct),
        "ccts": [float(c) for c in res.ccts],
        "lp_objective": float(sol.objective),
    }
    if scheme == "ours" and cert_g is not None:
        payload["cert_greedy"] = {
            "approx_ratio": float(cert_g.approx_ratio),
            "bound": float(cert_g.bound),
        }
    if scheme == "ours" and cert_r is not None:
        payload["cert_reserving"] = {
            "approx_ratio": float(cert_r.approx_ratio),
            "ok": bool(cert_r.ok()),
        }
    return payload


def sweep(
    instances: Sequence[CoflowInstance],
    *,
    schemes: Sequence[str] = DEFAULT_SCHEMES,
    lp_method: str = "batch",
    lp_iters: int = 3000,
    m_quantum: int = 8,
    p_quantum: int = 8,
    discipline: str = "greedy",
    alloc: str = "batch",
    circuit: str = "batch",
    certify: bool = False,
    metas: Sequence[Mapping[str, Any]] | None = None,
    validate: bool = True,
    mesh=None,
    cache: "cache_mod.SweepCache | str | None" = None,
    refine=None,
) -> SweepResult:
    """Run an ensemble end to end with one shared LP phase.

    ``metas`` attaches a dict of sweep coordinates (seed, K, N, delta, ...)
    to each instance; it is carried into every exported row.  ``alloc``
    selects the post-LP execution path: ``"batch"`` runs each scheme
    through `Pipeline.run_batch` (allocation vectorized via
    `repro.pipeline.batch_alloc`), ``"loop"`` runs the fully per-instance
    reference (`Pipeline.run`) that every batched path is bit-checked
    against.  ``circuit`` selects the list scheduler's backend *within*
    the batched path — ``"batch"`` (the `batch_circuit` padded event
    calendar) or ``"loop"`` (the per-instance oracle inside `run_batch`);
    with ``alloc="loop"`` the whole pipeline is already per-instance, so
    ``circuit`` has no effect there.  The batched calendar is the device
    calendar on TPU and GPU and the host calendar on a CPU, chosen by
    platform (`repro.pipeline.batch_circuit`).

    ``mesh`` shards the ensemble axis of every batched stage over the
    mesh's ``data`` axis (`jax.sharding.NamedSharding` via
    `repro.launch.mesh.data_sharding`): the bucketed LP solves, the
    allocation scan and the device circuit calendar all run SPMD, with
    member counts padded up to the device count (fully-masked no-op
    members) and results gathered back on host
    (`repro.experiments.results.device_gather`).  Members are
    independent, so a sharded sweep's rows are bit-identical to the
    single-device run; the per-instance ``alloc="loop"`` reference path
    ignores it.  ``mesh`` does not participate in cache keys for the
    same reason.

    ``cache`` (a `SweepCache` or a cache-root path) keys every
    (instance, scheme) cell and computes only the misses: the LP phase
    runs over the instances with at least one missing cell, and each
    scheme's pipeline runs over exactly the instances missing that
    scheme.  Stored payloads carry the per-cell absolutes the row export
    reads, so cached and fresh rows are byte-identical.

    With ``certify=True`` the OURS run is certified against the paper's
    Lemma 2-4 / Theorem 1 chain (greedy discipline for the practical
    ratio, reserving for the per-coflow guarantee) — this forces an exact
    LP; the reserving rerun differs from OURS only in circuit discipline,
    so it shares the sweep's ordering pass and batched allocation through
    the stage cache and re-runs just the circuit stage.  Certificates
    ride in the OURS cell, so ``certify=True`` with a cache requires
    ``"ours"`` among the schemes.

    ``refine`` applies candidate-search refinement on the realized
    objective to EVERY scheme of this sweep (a
    `repro.pipeline.RefineSpec`, ``True`` for the default dial, or a
    field dict; schemes whose spec pins its own refine — OURS+LS — use
    theirs when ``refine`` is None).  Under ``alloc="batch"`` the search
    runs batched (candidate orders as extra `EnsembleBatch` member
    rows); under ``alloc="loop"`` it runs the bit-identical sequential
    oracle.  The canonical refine config joins the cell key via the
    config digest — refined and unrefined sweeps never share cells.
    """
    instances = list(instances)
    schemes = tuple(schemes)
    if metas is None:
        metas = [{} for _ in instances]
    if len(metas) != len(instances):
        raise ValueError("metas length mismatch")
    if certify and lp_method != "exact":
        raise ValueError(
            "certify=True needs lp_method='exact': the subgradient objective "
            "upper-bounds the LP optimum and is not a valid ratio baseline"
        )
    if alloc not in ("batch", "loop"):
        raise ValueError(f"unknown alloc mode {alloc!r}")
    if circuit not in ("batch", "loop"):
        raise ValueError(f"unknown circuit mode {circuit!r}")
    if refine not in (None, False):
        from repro.pipeline.refine import as_refine_spec

        refine = as_refine_spec(refine)
    else:
        refine = None
    if isinstance(cache, str):
        cache = cache_mod.SweepCache(cache)
    if cache is not None and certify and "ours" not in schemes:
        raise ValueError(
            "certify=True with a cache requires 'ours' among the schemes "
            "(certificates are stored in the OURS cell)"
        )

    t0 = time.perf_counter()
    n = len(instances)
    totals = trace.Tally()  # the call's spans and counters

    # ---- cell keying: which (instance, scheme) cells need computing ----
    # The cache key folds in everything that determines a cell's value;
    # `validate` and `mesh` are excluded by the bit-identity contracts.
    keys: dict[tuple[int, str], str] = {}
    payloads: dict[tuple[int, str], dict] = {}
    if cache is not None:
        config_digest = cache_mod.canonical_digest(
            dict(
                lp_method=lp_method,
                lp_iters=lp_iters,
                m_quantum=m_quantum,
                p_quantum=p_quantum,
                discipline=discipline,
                alloc=alloc,
                circuit=circuit,
                certify=certify,
                # The sweep-level refine override joins every cell key
                # (None when schemes run their spec-pinned refine, which
                # the scheme digest already captures).
                refine=refine,
            )
        )
        inst_digests = [cache_mod.instance_digest(inst) for inst in instances]
        schm_digests = {s: cache_mod.scheme_digest(s) for s in schemes}
        miss: set[tuple[int, str]] = set()
        for i in range(n):
            for s in schemes:
                key = cache_mod.cell_key(
                    inst_digests[i], schm_digests[s],
                    config_digest, cache.fingerprint,
                )
                keys[(i, s)] = key
                payload = cache.get(key)
                if payload is None:
                    miss.add((i, s))
                else:
                    payloads[(i, s)] = payload
    else:
        miss = {(i, s) for i in range(n) for s in schemes}

    # ---- LP phase: only instances with at least one missing cell -------
    need_idx = sorted({i for i, _ in miss})
    sols_by_idx: dict[int, Any] = {}
    lp_time = 0.0
    if need_idx:
        sub = [instances[i] for i in need_idx]
        with trace.collect() as tally, trace.span("sweep.lp") as lp_span:
            if lp_method == "batch":
                sub_sols = solve_ensemble_lp(
                    sub, iters=lp_iters, m_quantum=m_quantum,
                    p_quantum=p_quantum, mesh=mesh,
                )
            elif lp_method == "exact":
                sub_sols = [lp.solve_exact(inst) for inst in sub]
            elif lp_method == "subgradient":
                sub_sols = [
                    lp.solve_subgradient(inst, iters=lp_iters) for inst in sub
                ]
            else:
                raise ValueError(f"unknown lp_method {lp_method!r}")
        totals.add(tally)
        lp_time = lp_span.seconds
        sols_by_idx = dict(zip(need_idx, sub_sols))
    elif lp_method not in ("batch", "exact", "subgradient"):
        raise ValueError(f"unknown lp_method {lp_method!r}")

    # ---- scheme runs over each scheme's missing instances --------------
    # One stage_cache per distinct instance subset: schemes sharing a
    # subset (the common all-miss case, and the certify-reserving rerun)
    # share one ordering pass and one batched allocation, exactly as the
    # cache-free sweep always did.
    stage_caches: dict[tuple[int, ...], dict] = {}

    def _run(scheme_key: str, disc: str, idx: list[int]):
        pipe = pipeline_mod.get_pipeline(
            scheme_key, discipline=disc, circuit_backend=circuit
        )
        sub = [instances[i] for i in idx]
        subsols = [sols_by_idx[i] for i in idx]
        with trace.collect() as tally:
            if alloc == "batch":
                sc = stage_caches.setdefault(tuple(idx), {})
                res = pipe.run_batch(
                    sub, lp_solutions=subsols, validate=validate,
                    stage_cache=sc, mesh=mesh, refine=refine,
                )
            else:
                res = [
                    pipe.run(
                        inst, lp_solution=sol, validate=validate,
                        refine=refine,
                    )
                    for inst, sol in zip(sub, subsols)
                ]
        totals.add(tally)
        return dict(zip(idx, res))

    scheme_results: dict[str, dict[int, Any]] = {}
    for s in schemes:
        idx_s = sorted(i for i, s2 in miss if s2 == s)
        scheme_results[s] = _run(s, discipline, idx_s) if idx_s else {}

    # ---- certification reruns (exact LP enforced above) ----------------
    ours_by_idx = reserving_by_idx = None
    if certify:
        if "ours" in schemes:
            cert_idx = sorted(i for i, s2 in miss if s2 == "ours")
            ours_by_idx = scheme_results["ours"]
        else:
            cert_idx = list(range(n))
            ours_by_idx = _run("ours", discipline, cert_idx)
        reserving_by_idx = (
            _run("ours", "reserving", cert_idx) if cert_idx else {}
        )

    # ---- assemble records (cached cells -> stand-ins), store misses ----
    records = []
    for i, (inst, meta) in enumerate(zip(instances, metas)):
        results: dict[str, Any] = {}
        cached_lp_obj = None
        cert_g = cert_r = None
        for s in schemes:
            if (i, s) in miss:
                results[s] = scheme_results[s][i]
            else:
                p = payloads[(i, s)]
                results[s] = cache_mod.CachedScheduleResult(
                    scheme=s,
                    total_weighted_cct=p["total_weighted_cct"],
                    ccts=np.asarray(p["ccts"], dtype=np.float64),
                )
                cached_lp_obj = p["lp_objective"]
        sol = sols_by_idx.get(i)
        if certify:
            if ours_by_idx is not None and i in ours_by_idx:
                res = ours_by_idx[i]
                cert_g = theory.certify(
                    inst, res.order, sol.completion, res.allocation, res.ccts
                )
                res_r = reserving_by_idx[i]
                cert_r = theory.certify(
                    inst, res_r.order, sol.completion, res_r.allocation,
                    res_r.ccts,
                )
            else:  # OURS cell was cached — certificates ride in its payload
                p = payloads[(i, "ours")]
                cg, cr = p.get("cert_greedy"), p.get("cert_reserving")
                if cg is not None:
                    cert_g = cache_mod.CachedCertificate(
                        approx_ratio=cg["approx_ratio"], bound=cg["bound"]
                    )
                if cr is not None:
                    cert_r = cache_mod.CachedCertificate(
                        approx_ratio=cr["approx_ratio"], bound=0.0,
                        certified=cr["ok"],
                    )
        rec = InstanceRecord(
            index=i,
            meta=dict(meta),
            lp=sol if sol is not None else cache_mod.CachedLP(cached_lp_obj),
            results=results,
            cert_greedy=cert_g,
            cert_reserving=cert_r,
        )
        records.append(rec)
        if cache is not None:
            for s in schemes:
                if (i, s) in miss:
                    cache.put(
                        keys[(i, s)],
                        _cell_payload(results, s, sol, cert_g, cert_r),
                        meta={"scheme": s},
                    )
    cache_stats = None
    if cache is not None:
        cache.flush()
        cache_stats = dict(
            cells=n * len(schemes),
            hits=n * len(schemes) - len(miss),
            misses=len(miss),
            computed=len(miss),
        )
    return SweepResult(
        records=records,
        lp_method=lp_method,
        lp_time_s=lp_time,
        wall_time_s=time.perf_counter() - t0,
        cache_stats=cache_stats,
        spans=totals.spans,
        counts=totals.counts,
    )
