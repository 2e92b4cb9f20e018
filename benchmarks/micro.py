"""Micro-benchmarks: scheduler stages, LP solvers, Pallas kernel oracles,
the batched LP-ensemble engine vs the sequential per-instance loop, and
the batch-first post-LP pipeline (`Pipeline.run_batch`, allocation and
circuit stages both ensemble-batched) vs the per-instance
order -> allocate -> schedule loop, with the circuit stage additionally
timed on its own (``circuit_batch_speedup_x``).

``python -m benchmarks.micro --batch-smoke`` runs only the pipeline case
with ``require_batch=True`` (any fallback to the per-instance allocation
or circuit loop is an error), prints cold/warm timings and merges them
into ``results/benchmarks/micro.json`` — the CI smoke step and its
uploaded perf-trajectory artifact.  ``--sharded-smoke`` runs the
data-axis-sharded sweep (``sweep(mesh=make_local_mesh())``) against the
single-device run, asserts bit-identical rows, and merges
``sharded_sweep_speedup_x`` into the same artifact (CI forces
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for it).

``--engines`` times the two circuit-calendar executors (the host
calendar ``wide`` and the device calendar ``kernel``, each forced
whatever the platform) on one shared ensemble with bit-parity asserted,
reports the device calendar's roofline distance
(`repro.launch.perf.measured_roofline`),
and with ``--trajectory`` appends a timestamped snapshot to the
repo-tracked ``BENCH_micro.json``.  ``--streaming-smoke`` drives the
online streaming service on a small Poisson-arrival trace: single-batch
replay parity against the offline pipeline and the (8K+1) bound are
asserted, and the warm-start re-solve speedup
(``streaming_resolve_warm_x``) joins the same artifacts.
``--refine-smoke`` runs the batched candidate-search refinement against
the per-candidate Python loop on the mixed-shape ensemble (bit-parity of
winners asserted, ``run_batch(ours_ls, require_batch=True)`` guarded
against a sequential fallback) and merges ``refine_batch_speedup_x``.
``--cache-smoke`` runs one sweep uncached / cached-fresh / cached-replay
(replay must compute zero cells, exports byte-identical) and merges the
replay speedup + cache-overhead ratio into the artifact, leaving the
cache manifest under ``results/benchmarks/cache_smoke/`` for upload.
``--check-floors`` gates the current
``results/benchmarks/micro.json`` against ``benchmarks/floors.json``
(exit 1 on any speedup below its floor) — the CI regression gate;
``--floor-keys a,b`` restricts the gate to a subset so CI jobs running
disjoint bench subsets each gate only what they produced."""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_json
from repro.core import lp
from repro.core.allocation import allocate
from repro.core.ordering import wspt_order
from repro.pipeline import get_pipeline
from repro.traffic.instances import paper_default_instance, random_instance


def _time(fn, reps=3):
    fn()  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6  # us


def bench_lp_ensemble(quick=False, ensemble_size=32, iters=None):
    """Batched LP-ensemble engine vs the sequential per-instance loop.

    Models exactly the work a figure sweep does: a cold run over a
    mixed-shape ensemble (every sweep point samples its own M and N).  The
    sequential loop — what the benchmarks did before the engine — pays one
    XLA compile per distinct instance shape on top of the per-instance
    solves; the engine pads the ensemble into a single bucket and runs one
    batched program.  Both paths run the same solver with the same
    iteration count, from a cleared compile cache.
    """
    import jax as _jax

    from repro.experiments import solve_ensemble_lp

    B = 8 if quick else ensemble_size
    iters = iters or (200 if quick else 400)
    rng = np.random.default_rng(0)
    ens = [
        random_instance(
            num_coflows=int(rng.integers(20, 52)),
            num_ports=int(rng.integers(4, 12)),
            seed=s,
        )
        for s in range(B)
    ]

    _jax.clear_caches()
    t0 = time.perf_counter()
    sols_seq = [lp.solve_subgradient(inst, iters=iters) for inst in ens]
    t_seq = time.perf_counter() - t0

    _jax.clear_caches()
    t0 = time.perf_counter()
    sols_bat = solve_ensemble_lp(
        ens, iters=iters, m_quantum=None, p_quantum=None
    )
    t_bat = time.perf_counter() - t0
    gap = max(
        abs(a.objective - b.objective) / abs(a.objective)
        for a, b in zip(sols_seq, sols_bat)
    )
    return B, t_seq, t_bat, t_seq / t_bat, gap


def bench_pipeline_batch(
    quick=False, ensemble_size=32, lp_iters=300, require_batch=False
):
    """Batch-first post-LP pipeline vs the per-instance scheme loop.

    Post-LP wall time only: the shared LP phase is solved once up front
    (as a sweep does) and both paths consume the same solutions.  The loop
    path is `Pipeline.run` per instance — order, NumPy reference
    allocation, NumPy event-loop circuit scheduling; the batch path is
    `Pipeline.run_batch` with both the allocation stage and the circuit
    stage (padded event calendar) vectorized across the mixed-shape
    ensemble.

    The circuit stage is additionally timed on its own (loop vs batched
    calendar, cold and warm) on the allocations both paths share.  Cold
    numbers are first-call-in-process: nothing clears the XLA cache, so
    each padded bucket compiles exactly once and every later call in the
    process — including the pipeline cold run, which reuses the circuit
    bucket the circuit bench just compiled — hits the cached program
    (this is what un-regressed `pipeline_batch_cold` vs the loop).
    Results are checked bit-identical to the loop.

    Returns a dict of row-name -> seconds (plus the ensemble size ``B``).
    """
    from repro.experiments import solve_ensemble_lp
    from repro.pipeline.batch_circuit import schedule_batch

    B = 8 if quick else ensemble_size
    rng = np.random.default_rng(1)
    ens = [
        random_instance(
            num_coflows=int(rng.integers(20, 52)),
            num_ports=int(rng.integers(4, 12)),
            num_cores=int(rng.integers(2, 5)),
            seed=100 + s,
        )
        for s in range(B)
    ]
    sols = solve_ensemble_lp(
        ens, iters=100 if quick else lp_iters, m_quantum=None, p_quantum=None
    )
    pipe = get_pipeline("ours")

    t0 = time.perf_counter()
    res_loop = [
        pipe.run(inst, lp_solution=sol, validate=False)
        for inst, sol in zip(ens, sols)
    ]
    t_loop = time.perf_counter() - t0

    # Circuit stage in isolation, on the allocations both paths share.
    orders = [sol.order() for sol in sols]
    allocs = pipe.allocate_stage.allocate_batch(ens, orders)
    t0 = time.perf_counter()
    ref_pairs = [
        pipe.circuit_stage.schedule(inst, alloc, order)
        for inst, alloc, order in zip(ens, allocs, orders)
    ]
    t_circuit_loop = time.perf_counter() - t0
    t0 = time.perf_counter()
    pairs = schedule_batch(ens, allocs, orders, pipe.circuit_stage.discipline)
    t_circuit_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    pairs = schedule_batch(ens, allocs, orders, pipe.circuit_stage.discipline)
    t_circuit_warm = time.perf_counter() - t0
    for (_, got), (_, ref) in zip(pairs, ref_pairs):
        if not np.array_equal(got, ref):
            raise AssertionError("batched circuit diverged from the loop")

    t0 = time.perf_counter()
    pipe.run_batch(
        ens, lp_solutions=sols, validate=False, require_batch=require_batch
    )
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    res_warm = pipe.run_batch(
        ens, lp_solutions=sols, validate=False, require_batch=require_batch
    )
    t_warm = time.perf_counter() - t0

    mismatch = max(
        abs(a.total_weighted_cct - b.total_weighted_cct)
        for a, b in zip(res_loop, res_warm)
    )
    if mismatch != 0.0:
        raise AssertionError(
            f"run_batch diverged from the per-instance loop by {mismatch}"
        )
    return {
        "B": B,
        f"pipeline_loop_ensemble{B}_s": t_loop,
        f"pipeline_batch_cold_ensemble{B}_s": t_cold,
        f"pipeline_batch_warm_ensemble{B}_s": t_warm,
        "pipeline_batch_speedup_x": t_loop / t_warm,
        f"circuit_loop_ensemble{B}_s": t_circuit_loop,
        f"circuit_batch_cold_ensemble{B}_s": t_circuit_cold,
        f"circuit_batch_warm_ensemble{B}_s": t_circuit_warm,
        "circuit_batch_speedup_x": t_circuit_loop / t_circuit_warm,
    }


def bench_calendar_executors(quick=False, ensemble_size=24, lp_iters=200):
    """Per-engine circuit-calendar timings on one shared ensemble.

    Runs the same (instances, allocs, orders) through `schedule_batch`
    under both executors, whatever the platform would pick — ``"wide"``
    (the host calendar, lockstep NumPy) and ``"kernel"`` (the device
    calendar, lockstep pair space with the Pallas round reduction) —
    asserting both produce bit-identical establishment times and CCTs,
    and times each cold (first call in this function) and warm.

    For the device calendar the compiled program is also pushed through
    `lower_calendar` -> `repro.launch.hlo_cost` -> roofline to report how
    far the measured warm time sits from the cost model's hardware bound
    (``*_roofline_frac``; the measured time includes host packing, so
    this is a floor on the achieved fraction).  Device/backend metadata
    rides along so `BENCH_micro.json` trajectory entries are
    interpretable across machines.
    """
    from repro.experiments import solve_ensemble_lp
    from repro.launch.perf import measured_roofline
    from repro.launch.roofline import PEAKS
    from repro.pipeline import batch_circuit as bc
    from repro.pipeline.batch_circuit import (
        lower_calendar,
        member_tables,
        schedule_batch,
    )

    B = 8 if quick else ensemble_size
    rng = np.random.default_rng(3)
    ens = [
        random_instance(
            num_coflows=int(rng.integers(20, 52)),
            num_ports=int(rng.integers(4, 12)),
            num_cores=int(rng.integers(2, 5)),
            seed=300 + s,
        )
        for s in range(B)
    ]
    sols = solve_ensemble_lp(
        ens, iters=100 if quick else lp_iters, m_quantum=None, p_quantum=None
    )
    pipe = get_pipeline("ours")
    discipline = pipe.circuit_stage.discipline
    orders = [sol.order() for sol in sols]
    allocs = pipe.allocate_stage.allocate_batch(ens, orders)

    stats = {
        "engines_B": B,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "num_devices": len(jax.devices()),
        "jax_version": jax.__version__,
    }
    results = {}
    platform_engine = bc._engine
    for engine in ("wide", "kernel"):
        bc._engine = lambda e=engine: e
        try:
            t0 = time.perf_counter()
            pairs = schedule_batch(ens, allocs, orders, discipline)
            t_cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            pairs = schedule_batch(ens, allocs, orders, discipline)
            t_warm = time.perf_counter() - t0
        finally:
            bc._engine = platform_engine
        results[engine] = pairs
        stats[f"circuit_{engine}_cold_ensemble{B}_s"] = t_cold
        stats[f"circuit_{engine}_warm_ensemble{B}_s"] = t_warm

    for (scheds, ccts), (rscheds, rccts) in zip(
        results["kernel"], results["wide"]
    ):
        if not np.array_equal(ccts, rccts):
            raise AssertionError("engine 'kernel' CCTs != wide oracle")
        for s, r in zip(scheds, rscheds):
            if not (
                np.array_equal(s.establish, r.establish)
                and np.array_equal(s.complete, r.complete)
            ):
                raise AssertionError(
                    "engine 'kernel' schedules != wide oracle"
                )
    stats["circuit_kernel_vs_wide_warm_x"] = (
        stats[f"circuit_wide_warm_ensemble{B}_s"]
        / stats[f"circuit_kernel_warm_ensemble{B}_s"]
    )

    # Roofline distance of the device calendar (the host calendar is
    # NumPy: no HLO exists for it, by design).  Only a device with
    # published peaks has one: anywhere else it is "not measured".
    kind = stats["device_kind"]
    if kind not in PEAKS:
        for key in ("bound_s", "frac", "dominant"):
            stats[f"circuit_kernel_roofline_{key}"] = "not measured"
        return stats
    tabs = [
        tab
        for inst, alloc, order in zip(ens, allocs, orders)
        for tab in member_tables(inst, alloc, order)
        if tab["coflow"].shape[0]
    ]
    nmax = max(inst.num_ports for inst in ens)
    hlo = lower_calendar(tabs, nmax, discipline).compile().as_text()
    terms = measured_roofline(
        hlo, stats[f"circuit_kernel_warm_ensemble{B}_s"], kind
    )
    stats["circuit_kernel_roofline_bound_s"] = terms["bound_s"]
    stats["circuit_kernel_roofline_frac"] = terms["roofline_frac"]
    stats["circuit_kernel_roofline_dominant"] = terms["dominant"]
    return stats


# Every trajectory entry must carry these: without them a committed
# number is uninterpretable (was that 3x on CPU or on a v5e?).
TRAJECTORY_META = ("backend", "device_kind", "num_devices", "jax_version")

# Keys every *service* (streaming trace scenario) entry must carry.  A
# metric that did not exist when an entry was recorded is normalized to
# an explicit ``null`` — absent keys are a schema error, so a reader can
# always distinguish "not measured yet" from "silently dropped".
SERVICE_KEYS = (
    "service_epochs",
    "service_warm_resolves",
    "service_bound_margin_x",
    "service_resolve_p50_ms",
    "service_epoch_warm_x",
)


def backend_metadata():
    """The per-entry device/backend stamp for ``BENCH_micro.json``."""
    return {
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "num_devices": len(jax.devices()),
        "jax_version": jax.__version__,
    }


def validate_trajectory(doc, path="BENCH_micro.json"):
    """Schema check for the trajectory file.

    Every entry's stats must carry all ``TRAJECTORY_META`` keys plus a
    ``bench`` family tag (``engines`` / ``streaming`` / ``trace`` / ...),
    and every entry carrying service metrics must carry the full
    ``SERVICE_KEYS`` set — explicit ``null`` for metrics that predate
    the entry, never a missing key.  Returns failure strings."""
    failures = []
    if doc.get("schema") != "bench-micro-trajectory-v1":
        failures.append(f"{path}: bad schema {doc.get('schema')!r}")
    for i, entry in enumerate(doc.get("entries", [])):
        stats = entry.get("stats", {})
        missing = [k for k in TRAJECTORY_META if k not in stats]
        if missing:
            failures.append(
                f"{path} entry {i} ({entry.get('timestamp')}): "
                f"missing metadata keys {missing}"
            )
        if "bench" not in stats:
            failures.append(
                f"{path} entry {i} ({entry.get('timestamp')}): "
                f"missing 'bench' family tag"
            )
        if stats.get("bench") == "trace" or any(
            k.startswith("service_") for k in stats
        ):
            missing_s = [k for k in SERVICE_KEYS if k not in stats]
            if missing_s:
                failures.append(
                    f"{path} entry {i} ({entry.get('timestamp')}): "
                    f"service entry missing keys {missing_s} "
                    f"(record unmeasured metrics as null)"
                )
    return failures


def record_trajectory(stats, path=None):
    """Append one entry to the repo-tracked ``BENCH_micro.json``.

    Unlike ``results/benchmarks/micro.json`` (gitignored, per-run), the
    trajectory file is committed: each entry is a timestamped snapshot of
    the engine timings plus the backend metadata that makes numbers from
    different machines comparable, so perf history survives in review.
    The ``TRAJECTORY_META`` backend stamp is added automatically when the
    caller's stats lack it, and the whole file (old entries included) is
    schema-validated on every append — a malformed entry can't land.
    """
    import json
    import os

    if path is None:
        path = os.path.join(os.path.dirname(__file__), "..", "BENCH_micro.json")
    path = os.path.abspath(path)
    doc = {"schema": "bench-micro-trajectory-v1", "entries": []}
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
    stats = {**backend_metadata(), **stats}
    doc["entries"].append(
        {
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "stats": {
                k: (float(f"{v:.6g}") if isinstance(v, float) else v)
                for k, v in stats.items()
            },
        }
    )
    failures = validate_trajectory(doc, path)
    if failures:
        raise AssertionError("; ".join(failures))
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    return path


def check_floors(floors_path=None, keys=None):
    """Benchmark-regression gate: compare the current run's
    ``results/benchmarks/micro.json`` against ``benchmarks/floors.json``.

    Every checked key must be present in the results and meet its floor
    (all floors are lower bounds on speedup ratios).  ``keys`` restricts
    the check to a subset of the floors file — CI jobs that run disjoint
    benchmark subsets each gate only the keys they produced.  Returns
    the list of failure strings — empty means pass; the CLI exits
    nonzero on any failure so CI can gate on it.
    """
    import json
    import os

    from benchmarks.common import results_dir

    if floors_path is None:
        floors_path = os.path.join(os.path.dirname(__file__), "floors.json")
    with open(floors_path) as f:
        floors = json.load(f)
    if keys is not None:
        unknown = [k for k in keys if k not in floors]
        if unknown:
            return [f"floor keys not in {floors_path}: {unknown}"]
        floors = {k: floors[k] for k in keys}
    res_path = os.path.join(results_dir(), "micro.json")
    if not os.path.exists(res_path):
        return [f"no results at {res_path}: run the benchmark first"]
    with open(res_path) as f:
        results = json.load(f)
    failures = []
    for key, floor in floors.items():
        got = results.get(key)
        if got is None:
            failures.append(f"{key}: missing from {res_path} (floor {floor})")
        elif got < floor:
            failures.append(f"{key}: {got:.3f} below floor {floor}")
    return failures


def run(quick=False):
    rows = []
    inst = paper_default_instance(seed=0)
    sol = lp.solve_exact(inst)
    pipe_ours = get_pipeline("ours")

    rows.append(("lp_exact_M100", _time(lambda: lp.solve_exact(inst), 1)))
    rows.append(
        ("lp_subgradient_M100", _time(lambda: lp.solve_subgradient(inst), 1))
    )
    order = wspt_order(inst)
    rows.append(("allocation_M100", _time(lambda: allocate(inst, order))))
    rows.append(
        (
            "full_ours_M100",
            _time(lambda: pipe_ours.run(inst, lp_solution=sol), 1),
        )
    )

    # Batched LP-ensemble engine vs sequential loop.
    B, t_seq, t_bat, speedup, gap = bench_lp_ensemble(quick=quick)
    rows.append((f"lp_sequential_ensemble{B}", t_seq * 1e6))
    rows.append((f"lp_batch_ensemble{B}", t_bat * 1e6))
    rows.append(("lp_batch_speedup_x", speedup))
    rows.append(("lp_batch_objective_gap", gap))

    # Batch-first post-LP pipeline vs the per-instance scheme loop, plus
    # the circuit stage on its own (whole-ensemble seconds, same
    # names/units as the --batch-smoke log).
    stats = bench_pipeline_batch(quick=quick)
    stats.pop("B")
    rows.extend(stats.items())

    # Both circuit calendars (wide / kernel) with roofline distance for
    # the device calendar.
    estats = bench_calendar_executors(quick=quick)
    rows.extend(
        (k, v) for k, v in estats.items() if isinstance(v, (int, float))
    )

    # Batched candidate-search refinement vs the per-candidate loop.
    rows.extend(bench_refine(quick=quick).items())

    # Sharded-ensemble sweep vs single device (data-axis NamedSharding;
    # 1-device meshes still exercise the sharded code path).
    rows.extend(bench_sharded_sweep(quick=quick).items())

    # Content-addressed sweep cache: replay speedup + overhead ratio.
    rows.extend(bench_sweep_cache(quick=quick).items())

    # Kernel oracles (interpret mode on CPU).
    from repro.kernels.lp_terms import lp_terms, lp_terms_batch
    from repro.kernels.port_stats import port_stats

    d = jnp.asarray(inst.demands, jnp.float32)
    rows.append(
        ("port_stats_kernel", _time(lambda: jax.block_until_ready(port_stats(d))))
    )
    M = inst.num_coflows
    X = jnp.eye(M, dtype=jnp.float32)
    rho = jnp.asarray(inst.port_stats()[0], jnp.float32)
    rows.append(
        (
            "lp_terms_kernel",
            _time(
                lambda: jax.block_until_ready(
                    lp_terms(X, rho, rho, 1 / 60.0, 8 / 3.0)
                )
            ),
        )
    )
    Bk = 4 if quick else 8
    Xb = jnp.broadcast_to(X, (Bk, M, M))
    rhob = jnp.broadcast_to(rho, (Bk,) + rho.shape)
    scales = jnp.full((Bk,), 1 / 60.0, jnp.float32)
    doks = jnp.full((Bk,), 8 / 3.0, jnp.float32)
    rows.append(
        (
            f"lp_terms_batch_kernel_B{Bk}",
            _time(
                lambda: jax.block_until_ready(
                    lp_terms_batch(Xb, rhob, rhob, scales, doks)
                )
            ),
        )
    )
    save_json("micro", dict(rows))
    return rows


def batch_smoke(quick=False):
    """CI smoke: the batched pipeline must not fall back to any loop.

    `bench_pipeline_batch(require_batch=True)` raises if `run_batch` takes
    the per-instance allocation *or* circuit path (or if the batched
    results diverge from the loop); circuit-stage and whole-pipeline
    cold/warm timings land in the job log and in
    ``results/benchmarks/micro.json`` (the CI perf-trajectory artifact).
    """
    stats = bench_pipeline_batch(quick=quick, require_batch=True)
    stats.pop("B")
    for name, val in stats.items():
        print(f"micro,{name},{val:.4f}")
    _merge_micro_json(stats)
    return stats


def engines_smoke(quick=False, trajectory=False):
    """CI smoke: both circuit calendars, bit-parity asserted.

    Prints each engine's cold/warm timings plus the roofline fractions,
    merges them into ``results/benchmarks/micro.json`` (the per-run CI
    artifact) and — with ``trajectory=True`` — appends a timestamped
    entry to the repo-tracked ``BENCH_micro.json``.
    """
    stats = {"bench": "engines", **bench_calendar_executors(quick=quick)}
    for name, val in stats.items():
        if isinstance(val, float):
            print(f"micro,{name},{val:.6g}")
        else:
            print(f"micro,{name},{val}")
    _merge_micro_json(
        {k: v for k, v in stats.items() if isinstance(v, (int, float))}
    )
    if trajectory:
        path = record_trajectory(stats)
        print(f"trajectory appended to {path}")
    return stats


def _merge_micro_json(stats):
    """Update ``results/benchmarks/micro.json`` in place: consecutive
    smoke runs against one results dir accumulate rows instead of
    clobbering each other."""
    import json
    import os

    from benchmarks.common import results_dir

    path = os.path.join(results_dir(), "micro.json")
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            merged = json.load(f)
    merged.update(stats)
    save_json("micro", merged)


def bench_sharded_sweep(quick=False, ensemble_size=32, lp_iters=200):
    """Sharded multi-device sweep vs the single-device run.

    Runs the same mixed-shape ensemble through `sweep` twice — unsharded,
    then with the ensemble axis sharded over `make_local_mesh()`'s
    ``data`` axis — asserts the exported rows are identical, and times
    the warm second pass of each path (both paths pay their own compile
    on the first pass; warm wall time is what a repeated figure sweep
    sees).  Under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    this is the 8-way SPMD path on one host; on real multi-device
    backends the same code shards across accelerators.
    """
    import json

    import jax

    from repro.experiments import sweep
    from repro.launch.mesh import data_axis_size, make_local_mesh

    B = 8 if quick else ensemble_size
    iters = 100 if quick else lp_iters
    rng = np.random.default_rng(2)
    ens = [
        random_instance(
            num_coflows=int(rng.integers(20, 52)),
            num_ports=int(rng.integers(4, 12)),
            num_cores=int(rng.integers(2, 5)),
            seed=200 + s,
        )
        for s in range(B)
    ]
    mesh = make_local_mesh()
    kwargs = dict(
        schemes=("ours",), lp_iters=iters,
        m_quantum=None, p_quantum=None, validate=False,
    )

    def timed_pair(**kw):
        sweep(ens, **kwargs, **kw)  # compile/warmup pass
        t0 = time.perf_counter()
        res = sweep(ens, **kwargs, **kw)
        return res, time.perf_counter() - t0

    res_single, t_single = timed_pair()
    res_sharded, t_sharded = timed_pair(mesh=mesh)
    if json.dumps(res_single.rows(), default=float) != json.dumps(
        res_sharded.rows(), default=float
    ):
        raise AssertionError(
            "sharded sweep rows diverged from the single-device run"
        )
    return {
        "sharded_devices": len(jax.devices()),
        "sharded_data_axis": data_axis_size(mesh),
        f"sweep_single_ensemble{B}_s": t_single,
        f"sweep_sharded_ensemble{B}_s": t_sharded,
        "sharded_sweep_speedup_x": t_single / t_sharded,
    }


def sharded_smoke(quick=False):
    """CI smoke for the sharded sweep path (forced multi-device host).

    Asserts bit-identical rows between the sharded and single-device
    sweeps and records ``sharded_sweep_speedup_x`` (plus raw timings and
    the device count) into ``results/benchmarks/micro.json``, merging
    with whatever that file already holds (local runs of both smokes
    accumulate one file; the CI jobs run on separate runners and upload
    separately-named artifacts).
    """
    stats = bench_sharded_sweep(quick=quick)
    for name, val in stats.items():
        print(f"micro,{name},{val:.4f}")
    _merge_micro_json(stats)
    return stats


def bench_streaming(quick=False, lp_iters=1500):
    """Streaming service: replay parity gate + warm-start re-solve speedup.

    Three checks on one small Poisson-arrival trace:

      1. parity — a single arrival batch with preemption disabled must
         replay bit-identically to the offline ``Pipeline.run_batch``
         (same realized weighted CCT, same per-coflow completions);
      2. bound — every streamed run (warm or cold) must realize weighted
         CCT within the paper's (8K+1) factor of the exact LP lower
         bound;
      3. speedup — ``streaming_resolve_warm_x``: mean per-epoch LP wall
         time of cold re-solves over warm ones.  Each variant runs twice
         and only the second pass is measured (compiles amortized); the
         first epoch of every run is cold by construction, so the mean is
         taken over re-solve epochs (index >= 1) only.  Warm epochs seed
         the subgradient with the previous iterate's full precedence
         matrix and run ``lp_iters_warm = lp_iters // 3`` iterations, so
         the expected speedup is ~3x minus fixed per-epoch overhead;
      4. compile stability — after the timed runs warmed every bucket,
         one more identical resident-mode stream must add zero entries
         to the fused epoch step's compile cache
         (``streaming_epoch_retraces == 0``).
    """
    from repro.experiments import stream
    from repro.traffic.arrivals import poisson_arrivals, with_releases

    M = 10 if quick else 16
    iters = 400 if quick else lp_iters
    # Mean inter-arrival well under a coflow's CCT so epochs overlap:
    # warm re-solves need carried-over actives to be warm about.
    inst = with_releases(
        random_instance(num_coflows=M, num_ports=6, num_cores=2, seed=9),
        poisson_arrivals(M, mean_interarrival_ms=4.0, seed=9),
    )

    # 1. Parity gate: replay == offline, bit-identical.
    pipe = get_pipeline("ours", discipline="greedy", lp_method="exact")
    off = pipe.run_batch([inst], lp_solutions=[lp.solve_exact(inst)])[0]
    rep = stream(inst, lp_method="exact", n_batches=1, preempt=False)
    if not (
        np.array_equal(rep.finish, off.ccts)
        and rep.realized_weighted_cct == off.total_weighted_cct
    ):
        raise AssertionError(
            "single-batch streaming replay diverged from the offline "
            "Pipeline.run_batch"
        )

    # 2 + 3. Warm vs cold re-solves on the same 4-batch arrival split.
    bound = 8.0 * inst.num_cores + 1.0  # releases > 0 on this trace
    lb = lp.solve_exact(inst).objective
    kw = dict(lp_method="batch", lp_iters=iters, n_batches=4)

    def timed(warm):
        stream(inst, warm_start=warm, **kw)  # compile/warmup pass
        res = stream(inst, warm_start=warm, **kw)
        if res.realized_weighted_cct > bound * lb * (1 + 1e-9):
            raise AssertionError(
                f"streamed run (warm_start={warm}) violated the "
                f"(8K+1) bound: {res.realized_weighted_cct} > "
                f"{bound} * {lb}"
            )
        resolves = [e.lp_wall_s for e in res.epochs[1:]]
        return res, sum(resolves) / max(len(resolves), 1)

    cold_res, t_cold = timed(False)
    warm_res, t_warm = timed(True)
    if warm_res.warm_resolves < 3:
        raise AssertionError(
            f"expected >= 3 warm re-solve epochs, got "
            f"{warm_res.warm_resolves}"
        )

    # 4. compile stability — the device-resident epoch driver must be
    #    fully warmed up by now (lp_method="batch" resolves epoch_mode
    #    "auto" -> "resident", and each variant above already ran twice):
    #    one more identical stream must add ZERO entries to the fused
    #    epoch step's compile cache.  A retrace here means the resident
    #    path is rebuilding shapes per epoch — exactly the cost the
    #    slot-pool representation exists to kill.
    from repro.pipeline import batch_alloc

    retraces = None
    probe = getattr(batch_alloc._scan_all, "_cache_size", None)
    if probe is not None:
        before = probe()
        res_probe = stream(inst, warm_start=True, **kw)
        if res_probe.epoch_mode != "resident":
            raise AssertionError(
                f"expected resident epoch driver for lp_method='batch', "
                f"got {res_probe.epoch_mode!r}"
            )
        retraces = probe() - before
        if retraces != 0:
            raise AssertionError(
                f"resident epoch step retraced after warm-up: "
                f"{retraces} new compile-cache entries"
            )
    return {
        "streaming_epochs": cold_res.num_resolves,
        "streaming_epoch_mode": warm_res.epoch_mode,
        "streaming_epoch_retraces": retraces,
        "streaming_warm_resolves": warm_res.warm_resolves,
        "streaming_iteration_savings": warm_res.iteration_savings,
        "streaming_cold_resolve_s": t_cold,
        "streaming_warm_resolve_s": t_warm,
        "streaming_resolve_warm_x": t_cold / t_warm,
    }


def streaming_smoke(quick=False, trajectory=False):
    """CI smoke for the streaming service.

    Asserts single-batch replay parity against the offline pipeline and
    the (8K+1) bound on warm and cold streamed runs, then records the
    warm-start re-solve speedup (``streaming_resolve_warm_x``) into
    ``results/benchmarks/micro.json``; with ``trajectory=True`` the
    stats also land in the repo-tracked ``BENCH_micro.json``.
    """
    stats = {"bench": "streaming", **bench_streaming(quick=quick)}
    for name, val in stats.items():
        if isinstance(val, float):
            print(f"micro,{name},{val:.6g}")
        else:
            print(f"micro,{name},{val}")
    _merge_micro_json(stats)
    if trajectory:
        path = record_trajectory(stats)
        print(f"trajectory appended to {path}")
    return stats


def bench_refine(quick=False, ensemble_size=32, lp_iters=300):
    """Batched candidate-search refinement vs the per-candidate Python loop.

    The mixed-shape micro ensemble's LP orders are refined twice with the
    same `RefineSpec`: once through `refine_batch_arrays` (candidate
    orders as extra `EnsembleBatch` member rows, one batched alloc+circuit
    pass per round) and once through the sequential oracle
    (`refine_sequential` over `evaluate_order` — one full per-instance
    allocation + circuit pass per candidate, the shape
    `core.localsearch.refine_order` always had).  Winners must be
    **bit-identical** — same refined orders, same objectives, same
    evaluation counts — before any timing is reported; the refined
    ensemble is then pushed through ``Pipeline.run_batch(ours_ls,
    require_batch=True)`` so a silent fallback to the sequential loop
    fails the smoke rather than skewing the numbers.

    ``refine_batch_speedup_x`` is sequential wall / warm batched wall —
    the quality-vs-compute dial's price tag, gated by
    ``benchmarks/floors.json``.
    """
    from repro.core.localsearch import evaluate_order
    from repro.experiments import solve_ensemble_lp
    from repro.pipeline import ensemble_batch as eb
    from repro.pipeline.refine import (
        RefineSpec,
        refine_batch_arrays,
        refine_sequential,
    )

    B = 8 if quick else ensemble_size
    iters = 100 if quick else lp_iters
    rng = np.random.default_rng(4)
    ens = [
        random_instance(
            num_coflows=int(rng.integers(20, 52)),
            num_ports=int(rng.integers(4, 12)),
            num_cores=int(rng.integers(2, 5)),
            seed=400 + s,
        )
        for s in range(B)
    ]
    sols = solve_ensemble_lp(
        ens, iters=iters, m_quantum=None, p_quantum=None
    )
    orders = [sol.order() for sol in sols]
    spec = RefineSpec()  # the registry's OURS+LS dial
    batch = eb.build_ensemble_batch(ens, with_lp_arrays=False)
    padded = batch.pad_orders(orders)

    t0 = time.perf_counter()
    refine_batch_arrays(batch, padded, spec)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = refine_batch_arrays(batch, padded, spec)
    t_warm = time.perf_counter() - t0

    t0 = time.perf_counter()
    seq = [
        refine_sequential(
            orders[b], spec,
            lambda o, inst=ens[b]: evaluate_order(inst, o),
        )
        for b in range(B)
    ]
    t_seq = time.perf_counter() - t0

    for b, (o2, cur, base, _r, _e) in enumerate(seq):
        M = ens[b].num_coflows
        if not (
            np.array_equal(out.orders[b, :M], o2)
            and out.objective[b] == cur
            and out.base_objective[b] == base
        ):
            raise AssertionError(
                f"batched refinement diverged from the sequential oracle "
                f"on instance {b}"
            )
    if out.evaluations != sum(e for *_, e in seq):
        raise AssertionError(
            f"evaluation counts diverged: batched {out.evaluations} vs "
            f"sequential {sum(e for *_, e in seq)}"
        )

    # End-to-end gate: OURS+LS through run_batch must stay on the batched
    # refinement path (require_batch errors on the sequential fallback).
    get_pipeline("ours_ls").run_batch(
        ens, lp_solutions=sols, validate=False, require_batch=True
    )
    return {
        "refine_B": B,
        "refine_rounds": spec.rounds,
        "refine_candidates": spec.candidates,
        "refine_evaluations": out.evaluations,
        "refine_improved_frac": float(out.improved.mean()),
        f"refine_seq_ensemble{B}_s": t_seq,
        f"refine_batch_cold_ensemble{B}_s": t_cold,
        f"refine_batch_warm_ensemble{B}_s": t_warm,
        "refine_batch_speedup_x": t_seq / t_warm,
    }


def refine_smoke(quick=False, trajectory=False):
    """CI smoke for batched candidate-search refinement.

    Asserts batched-vs-sequential bit-parity (orders, objectives and
    evaluation counts) and that ``run_batch(ours_ls,
    require_batch=True)`` stays on the batched path, then merges
    ``refine_batch_speedup_x`` (+ raw timings) into
    ``results/benchmarks/micro.json``; with ``trajectory=True`` the
    stats also land in the repo-tracked ``BENCH_micro.json``.
    """
    stats = {"bench": "refine", **bench_refine(quick=quick)}
    for name, val in stats.items():
        if isinstance(val, float):
            print(f"micro,{name},{val:.6g}")
        else:
            print(f"micro,{name},{val}")
    _merge_micro_json(stats)
    if trajectory:
        path = record_trajectory(stats)
        print(f"trajectory appended to {path}")
    return stats


def bench_sweep_cache(quick=False, ensemble_size=12, lp_iters=200):
    """Content-addressed sweep cache: replay speedup + byte-identity.

    One mixed-shape ensemble through ``sweep`` three ways — uncached,
    cached-fresh (every cell a miss: compute + store) and cached-replay
    (every cell a hit: the pipeline is short-circuited entirely).  The
    replay pass must report **zero computed cells** via the sweep's
    cache-hit counters, and all three passes must export byte-identical
    rows — the cache is a pure memo, never an approximation.

    Metrics: ``sweep_cache_replay_x`` (uncached wall / replay wall, the
    point of the cache) and ``sweep_cache_fresh_vs_uncached_x``
    (uncached wall / cached-fresh wall — a *cache overhead* gate: hashing
    + storing a miss must stay a small fraction of compute).
    """
    import json
    import os
    import shutil

    from benchmarks.common import results_dir
    from repro.experiments import SweepCache, sweep

    B = 6 if quick else ensemble_size
    iters = 100 if quick else lp_iters
    rng = np.random.default_rng(7)
    ens = [
        random_instance(
            num_coflows=int(rng.integers(12, 32)),
            num_ports=int(rng.integers(4, 10)),
            num_cores=int(rng.integers(2, 5)),
            seed=700 + s,
        )
        for s in range(B)
    ]
    cache_root = os.path.join(results_dir(), "cache_smoke")
    shutil.rmtree(cache_root, ignore_errors=True)
    kwargs = dict(
        schemes=("ours", "wspt_order"), lp_iters=iters, validate=False
    )

    sweep(ens, **kwargs)  # compile/warmup pass
    t0 = time.perf_counter()
    res_uncached = sweep(ens, **kwargs)
    t_uncached = time.perf_counter() - t0

    t0 = time.perf_counter()
    res_fresh = sweep(ens, cache=cache_root, **kwargs)
    t_fresh = time.perf_counter() - t0
    if res_fresh.cache_stats["computed"] != res_fresh.cache_stats["cells"]:
        raise AssertionError(
            f"fresh cached pass expected all-miss, got {res_fresh.cache_stats}"
        )

    # Replay through a NEW SweepCache on the same root: exercises the
    # manifest-reload (restart) path, not just in-memory state.
    t0 = time.perf_counter()
    res_replay = sweep(ens, cache=SweepCache(cache_root), **kwargs)
    t_replay = time.perf_counter() - t0
    if res_replay.cache_stats["computed"] != 0:
        raise AssertionError(
            f"replay recomputed cells: {res_replay.cache_stats}"
        )

    blobs = [
        json.dumps(r.rows(), default=float)
        for r in (res_uncached, res_fresh, res_replay)
    ]
    if len(set(blobs)) != 1:
        raise AssertionError(
            "cached sweep rows diverged from the uncached run"
        )
    return {
        "cache_B": B,
        "cache_cells": res_replay.cache_stats["cells"],
        "cache_replay_hits": res_replay.cache_stats["hits"],
        f"sweep_uncached_ensemble{B}_s": t_uncached,
        f"sweep_cached_fresh_ensemble{B}_s": t_fresh,
        f"sweep_cached_replay_ensemble{B}_s": t_replay,
        "sweep_cache_replay_x": t_uncached / t_replay,
        "sweep_cache_fresh_vs_uncached_x": t_uncached / t_fresh,
    }


def cache_smoke(quick=False, trajectory=False):
    """CI smoke for the experiment cache.

    Runs the same sweep uncached / cached-fresh / cached-replay, asserts
    the replay pass computed **zero** cells and all three exports are
    byte-identical, then merges ``sweep_cache_replay_x`` and the
    overhead ratio ``sweep_cache_fresh_vs_uncached_x`` into
    ``results/benchmarks/micro.json``.  The cache itself lands under
    ``results/benchmarks/cache_smoke/`` so CI can upload its
    ``manifest.json`` as an artifact next to micro.json.
    """
    stats = {"bench": "cache", **bench_sweep_cache(quick=quick)}
    for name, val in stats.items():
        if isinstance(val, float):
            print(f"micro,{name},{val:.6g}")
        else:
            print(f"micro,{name},{val}")
    _merge_micro_json(stats)
    if trajectory:
        path = record_trajectory(stats)
        print(f"trajectory appended to {path}")
    return stats


def main(quick=False):
    rows = run(quick=quick)
    print("micro: name,value (us_per_call unless suffixed)")
    for name, val in rows:
        print(f"micro,{name},{val:.6g}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--batch-smoke",
        action="store_true",
        help="run only the batched-allocation pipeline case; error on any "
        "fallback to the per-instance loop",
    )
    ap.add_argument(
        "--sharded-smoke",
        action="store_true",
        help="run only the sharded-sweep case (sweep(mesh=...) vs the "
        "single-device run; bit-identical rows asserted, "
        "sharded_sweep_speedup_x merged into micro.json)",
    )
    ap.add_argument(
        "--engines",
        action="store_true",
        help="run only the per-engine circuit-calendar case (wide/"
        "kernel timed on one ensemble, bit-parity asserted, roofline "
        "fractions merged into micro.json)",
    )
    ap.add_argument(
        "--streaming-smoke",
        action="store_true",
        help="run only the streaming-service case (single-batch replay "
        "parity vs the offline pipeline asserted, (8K+1) bound checked, "
        "streaming_resolve_warm_x merged into micro.json)",
    )
    ap.add_argument(
        "--refine-smoke",
        action="store_true",
        help="run only the batched-refinement case (candidate search as "
        "extra EnsembleBatch member rows vs the per-candidate Python "
        "loop; bit-parity and the batched run_batch path asserted, "
        "refine_batch_speedup_x merged into micro.json)",
    )
    ap.add_argument(
        "--cache-smoke",
        action="store_true",
        help="run only the sweep-cache case (same sweep uncached / "
        "cached-fresh / cached-replay; replay must compute zero cells, "
        "exports byte-identical; sweep_cache_replay_x merged into "
        "micro.json, cache manifest under results/benchmarks/cache_smoke)",
    )
    ap.add_argument(
        "--trajectory",
        action="store_true",
        help="with --engines, --streaming-smoke, --refine-smoke or "
        "--cache-smoke: also append a timestamped entry to the "
        "repo-tracked BENCH_micro.json (backend metadata stamped and "
        "schema-enforced on every entry)",
    )
    ap.add_argument(
        "--check-floors",
        action="store_true",
        help="compare results/benchmarks/micro.json against "
        "benchmarks/floors.json and exit nonzero on any regression",
    )
    ap.add_argument(
        "--floor-keys",
        default=None,
        help="with --check-floors: comma-separated subset of floors.json "
        "keys to gate (CI jobs gate only the keys their benches produce)",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.check_floors:
        import sys

        keys = args.floor_keys.split(",") if args.floor_keys else None
        failures = check_floors(keys=keys)
        for f in failures:
            print(f"FLOOR REGRESSION: {f}")
        if failures:
            sys.exit(1)
        print(f"floors: all pass ({'all keys' if keys is None else keys})")
    elif args.batch_smoke:
        batch_smoke(quick=args.quick)
    elif args.sharded_smoke:
        sharded_smoke(quick=args.quick)
    elif args.engines:
        engines_smoke(quick=args.quick, trajectory=args.trajectory)
    elif args.streaming_smoke:
        streaming_smoke(quick=args.quick, trajectory=args.trajectory)
    elif args.refine_smoke:
        refine_smoke(quick=args.quick, trajectory=args.trajectory)
    elif args.cache_smoke:
        cache_smoke(quick=args.quick, trajectory=args.trajectory)
    else:
        main(quick=args.quick)
