"""`stream()` — the event-driven online scheduler (sweep()'s sibling).

Event loop (one *epoch* per event):

  1. **Arrival batches.**  Coflows are sorted by release time and grouped
     into arrival batches — ``n_batches`` equal chunks (replay-style: a
     chunk is admitted when its first coflow arrives, original releases
     are honored as lower bounds) or a ``batch_window`` grouping (true
     online: the scheduler acts when the last coflow of the window has
     arrived).  The default (``batch_window=None``) re-solves once per
     distinct arrival instant.
  2. **Advance.**  At epoch time ``now`` the incumbent calendar is
     settled — one masked array pass over the calendar rows: flows with
     ``complete <= now`` are delivered (their exact size leaves the
     residual demand), flows with ``establish >= now`` are cancelled
     back into the pool, and in-flight flows are either *preempted*
     (``preempt=True``: the bytes sent so far leave the residual; the
     remainder re-pays the reconfiguration delta when it is
     re-established) or *committed* (``preempt=False``: the flow runs
     to completion as a phantom busy circuit blocking its port pair in
     every later calendar — see ``schedule_batch_arrays(busy=...)``).
     Coflows whose residual reaches zero free their pool slot (one
     batched ``release_many`` / ``forget_slots`` per epoch).
  3. **Admit.**  Queued arrivals take free slots in ring order
     (`repro.streaming.pool.SlotPool`); overflow waits (admission
     latency is reported per coflow).
  4. **Re-solve.**  The active set runs the *same* stages as the offline
     `Pipeline.run_batch`: ordering LP → masked stable order → batched
     allocation scan → batched circuit calendar.  The ordering LP is
     warm-started: the previous epoch's precedence iterate is stored per
     slot pair and seeds ``Y0`` for every pair of coflows that was
     already solved together, and warm epochs run ``lp_iters_warm``
     (< ``lp_iters``) subgradient steps.

Epoch modes (``epoch_mode``):

  * ``"rebuild"`` — the PR 7 path: every epoch packs a dense residual
    `CoflowInstance` and builds a fresh `EnsembleBatch`.  Each distinct
    (active count, flow count) is a new padded shape, so the jitted
    stages retrace nearly every epoch; kept as the oracle the resident
    mode is parity-tested against, and as the host of the per-epoch
    exact LP (``lp_method="exact"``).
  * ``"resident"`` — the device-resident path: ONE `EnsembleBatch`
    padded to the pool capacity `S` lives for the whole stream
    (`repro.pipeline.ensemble_batch.SlotPoolBatch`); epochs scatter
    residuals/weights/releases into occupied slots in place
    (`update_slots` / `free_slots` — the controlled build-once
    exemption) and drive LP → order → alloc → circuit off the resident
    arrays at **fixed** padded shapes, so after warm-up no stage
    retraces (the only new shapes are the geometric flow-arena growth
    ladder — the epoch compile-cache buckets).  The `_WarmState`
    precedence matrix lives on device and is gathered/scattered by slot
    index inside small jits (`repro.core.lp.warm_gather_device` /
    ``warm_scatter_device``).  With ``warm_start=False`` the resident
    epoch is **bit-identical** to the rebuild epoch: the dense-gathered
    LP inputs equal `pack_lp_arrays`'s output at the same padded shapes
    (so the same compiled program produces the same floats), the dense
    order view sorts the same keys, and the slot-space allocation scan
    differs from the dense one only by invalid no-op steps.  Warm
    streams may differ from rebuild by f32 rounding (device-side
    ``1 - y`` vs. the host's f64 round-trip) — the bound and structural
    invariants are asserted either way.
  * ``"auto"`` (default) — ``"resident"`` for the batched subgradient
    solver, ``"rebuild"`` for ``lp_method="exact"``.

With one arrival batch and preemption disabled the loop degenerates to
exactly one epoch whose instance *is* the offline instance, so orders,
allocations and CCTs are bit-identical to `Pipeline.run_batch` —
`tests/test_streaming.py` fuzzes that replay-parity contract, and the
paper's (8K+1) arbitrary-release bound is asserted on every streamed
run against the exact LP lower bound of the full instance.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

import jax.numpy as jnp

from repro import trace
from repro.core import lp
from repro.core.allocation import Allocation
from repro.core.circuit import CoreSchedule
from repro.core.coflow import CoflowInstance
from repro.core.validate import validate_schedule
from repro.pipeline import build_ensemble_batch, get_pipeline
from repro.pipeline.pipeline import order_view
from repro.pipeline.batch_circuit import schedule_batch_arrays
from repro.pipeline.ensemble_batch import (
    _round_up,
    build_slot_pool_batch,
    free_slots,
    set_slot_releases,
    update_slots,
)
from repro.pipeline.stages import ListCircuit
from repro.streaming.pool import SlotPool

__all__ = ["EPOCH_MODES", "EpochRecord", "StreamResult", "stream"]

EPOCH_MODES = ("auto", "rebuild", "resident")


@dataclasses.dataclass
class EpochRecord:
    """One re-solve: who was active, what the scheduler decided.

    ``spans`` holds the host seconds of each `repro.trace` span of the
    epoch, from settling the incumbent calendar to appending this record:
    the leaf spans (``stream.advance`` / ``admit`` / ``scatter`` / ``lp``
    / ``order`` / ``validate`` / ``record``, ``alloc.*``, ``calendar.*``)
    tile it, and ``stream.epoch`` encloses them all.  ``counts`` holds
    the epoch's counters (``host_reads``, ``calendar.*``, ``slot.*``).
    ``lp_wall_s`` is the ``stream.lp`` span; ``wall_s`` runs from the
    decision's start (after admission) to this record.
    """

    index: int
    time: float  # epoch (event) time
    actives: np.ndarray  # global coflow ids, dense order (ascending id)
    admitted: np.ndarray  # global ids admitted at this epoch
    order: np.ndarray  # global ids, highest priority first
    allocation: Allocation | None  # epoch-dense coflow indexing
    ccts: np.ndarray  # (Me,) projected absolute completions, dense
    lp: lp.LPSolution | None
    warm: bool  # LP seeded from the previous iterate
    lp_iters_used: int
    lp_wall_s: float
    num_busy: int  # phantom committed circuits carried in
    wall_s: float
    lp_objective: float | None = None  # kept even when `lp` is dropped
    spans: dict[str, float] = dataclasses.field(default_factory=dict)
    counts: dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class StreamResult:
    """Realized outcome of one streamed run (absolute times throughout)."""

    scheme: str
    discipline: str
    lp_method: str
    preempt: bool
    warm_start: bool
    pool_size: int
    lp_iters: int
    lp_iters_warm: int
    weights: np.ndarray  # (M,)
    arrival: np.ndarray  # (M,) release/arrival times
    admission: np.ndarray  # (M,) epoch time the coflow got a slot
    finish: np.ndarray  # (M,) realized completion (last byte delivered)
    epochs: list[EpochRecord]
    lp_time_s: float
    wall_time_s: float
    admission_policy: str = "fifo"  # slot-pool policy (see SlotPool)
    epoch_mode: str = "rebuild"  # resolved epoch driver (never "auto")
    # Call totals of the `repro.trace` spans and counters (every epoch,
    # the resident pool's build and the final settlement).
    spans: dict[str, float] = dataclasses.field(default_factory=dict)
    counts: dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def realized_weighted_cct(self) -> float:
        """Sum_m w_m T_m with T_m the realized absolute completion."""
        return float(np.dot(self.weights, self.finish))

    @property
    def num_resolves(self) -> int:
        return len(self.epochs)

    @property
    def warm_resolves(self) -> int:
        return sum(1 for e in self.epochs if e.warm)

    @property
    def iteration_savings(self) -> int:
        """Subgradient iterations avoided by warm-started re-solves."""
        return sum(
            self.lp_iters - e.lp_iters_used for e in self.epochs if e.warm
        )

    def coflow_rows(self, base: dict | None = None) -> list[dict]:
        """One row per coflow: arrival → admission → completion."""
        base = dict(base or {})
        rows = []
        for m in range(self.weights.shape[0]):
            rows.append(
                dict(
                    base,
                    coflow=m,
                    weight=float(self.weights[m]),
                    arrival=float(self.arrival[m]),
                    admission=float(self.admission[m]),
                    completion=float(self.finish[m]),
                    cct=float(self.finish[m] - self.arrival[m]),
                    latency=float(self.finish[m] - self.admission[m]),
                    wait=float(self.admission[m] - self.arrival[m]),
                )
            )
        return rows

    def epoch_rows(self, base: dict | None = None) -> list[dict]:
        base = dict(base or {})
        return [
            dict(
                base,
                epoch=e.index,
                time=e.time,
                num_active=int(e.actives.shape[0]),
                num_admitted=int(e.admitted.shape[0]),
                num_busy=e.num_busy,
                warm=e.warm,
                lp_iters_used=e.lp_iters_used,
                lp_objective=(
                    e.lp_objective
                    if e.lp_objective is not None
                    else (float(e.lp.objective) if e.lp is not None else None)
                ),
                lp_wall_s=e.lp_wall_s,
                wall_s=e.wall_s,
            )
            for e in self.epochs
        ]

    def summary(self) -> dict[str, Any]:
        cct = self.finish - self.arrival
        return dict(
            scheme=self.scheme,
            discipline=self.discipline,
            lp_method=self.lp_method,
            preempt=self.preempt,
            warm_start=self.warm_start,
            pool_size=self.pool_size,
            admission_policy=self.admission_policy,
            epoch_mode=self.epoch_mode,
            num_coflows=int(self.weights.shape[0]),
            realized_weighted_cct=self.realized_weighted_cct,
            num_resolves=self.num_resolves,
            warm_resolves=self.warm_resolves,
            iteration_savings=self.iteration_savings,
            mean_cct=float(cct.mean()) if cct.size else 0.0,
            p95_cct=float(np.quantile(cct, 0.95)) if cct.size else 0.0,
            mean_wait=(
                float((self.admission - self.arrival).mean())
                if cct.size
                else 0.0
            ),
            lp_time_s=self.lp_time_s,
            wall_time_s=self.wall_time_s,
        )

    def save(self, name: str) -> dict[str, str]:
        """Write `{name}_coflows` / `{name}_epochs` JSON+CSV rows and a
        `{name}_summary` JSON into `repro.experiments.results.results_dir`."""
        from repro.experiments.results import save_json, save_rows

        base = dict(scheme=self.scheme, discipline=self.discipline)
        cj, cc = save_rows(f"{name}_coflows", self.coflow_rows(base))
        ej, ec = save_rows(f"{name}_epochs", self.epoch_rows(base))
        sj = save_json(f"{name}_summary", self.summary())
        return dict(
            coflows_json=cj, coflows_csv=cc,
            epochs_json=ej, epochs_csv=ec, summary_json=sj,
        )


class _WarmState:
    """Slot-pair warm-start memory for the subgradient LP.

    ``Y[sa, sb]`` stores the full precedence value x_{a,b} (prob. the
    coflow in slot ``sa`` precedes the one in ``sb``) from the last
    solve that contained both; storing the *full* matrix (not just the
    upper triangle) makes the gather orientation-free: dense pair
    (i, j), i < j reads ``Y[s_i, s_j]`` whatever the slot order is.
    A slot's rows go stale the moment it is freed (``solved`` cleared).

    ``device=True`` (the resident epoch mode) keeps ``Y`` as a device
    (S, S) f32 array for the life of the stream: epochs gather it into
    the dense warm start and scatter the solved pairs back through
    fixed-shape jits (`repro.core.lp.warm_gather_device` /
    ``warm_scatter_device``) — the precedence matrix never round-trips
    through the host.  Only the tiny (S,) ``solved`` mask stays
    host-side (it feeds pre-solve control flow and per-free forgets).
    """

    def __init__(self, size: int, device: bool = False):
        self.size = size
        self.device = device
        if device:
            self.Y = jnp.zeros((size, size), dtype=jnp.float32)
        else:
            self.Y = np.zeros((size, size), dtype=np.float32)
        self.solved = np.zeros(size, dtype=bool)

    # -- host path (rebuild mode) -----------------------------------------
    def gather(self, slots: np.ndarray, default_Y0: np.ndarray) -> tuple:
        """Warm Y0 for the dense active set; returns (Y0, any_warm)."""
        prev = self.solved[slots]
        both = prev[:, None] & prev[None, :]
        if not np.triu(both, k=1).any():
            return default_Y0, False
        Ys = self.Y[np.ix_(slots, slots)]
        return np.triu(np.where(both, Ys, default_Y0), k=1), True

    def scatter(self, slots: np.ndarray, precedence: np.ndarray) -> None:
        self.Y[np.ix_(slots, slots)] = precedence.astype(np.float32)
        self.solved[slots] = True

    # -- device path (resident mode) --------------------------------------
    def gather_device(self, slots_padded: np.ndarray, default_Y0) -> tuple:
        """Device warm Y0 ((S, S) f32) for dense positions ``slots_padded``
        (padded with the out-of-range index S); returns (Y0, any_warm)."""
        Y0, any_warm = lp.warm_gather_device(
            self.Y, jnp.asarray(self.solved), jnp.asarray(slots_padded),
            default_Y0,
        )
        return Y0, bool(trace.to_host(any_warm))

    def scatter_device(
        self, slots_padded: np.ndarray, slots: np.ndarray, y_dense
    ) -> None:
        """Write the solver's dense strict-upper ``y`` back at slot pairs."""
        self.Y = lp.warm_scatter_device(
            self.Y, jnp.asarray(slots_padded), y_dense
        )
        self.solved[slots] = True

    # -- shared ------------------------------------------------------------
    def forget_slots(self, slots) -> None:
        """Batch-invalidate freed slots (one scatter per drain event)."""
        self.solved[np.asarray(slots, dtype=np.int64)] = False

    def forget(self, slot: int) -> None:
        self.forget_slots(np.asarray([slot], dtype=np.int64))


@dataclasses.dataclass
class _Calendar:
    """Incumbent calendar as parallel arrays: one row per scheduled flow.

    The `_advance` settlement is a handful of masked array ops over these
    rows instead of a Python loop — (m, i, j) triples are unique within a
    calendar (a flow is placed on exactly one core and scheduled once),
    so plain fancy-indexed subtraction settles residuals exactly.
    """

    m: np.ndarray  # (n,) global coflow ids
    k: np.ndarray  # (n,) core ids
    i: np.ndarray  # (n,) ingress ports
    j: np.ndarray  # (n,) egress ports
    size: np.ndarray  # (n,) scheduled sizes
    est: np.ndarray  # (n,) establish times
    comp: np.ndarray  # (n,) completion times

    @classmethod
    def empty(cls) -> "_Calendar":
        z = np.zeros(0)
        zi = np.zeros(0, dtype=np.int64)
        return cls(zi, zi, zi, zi, z, z, z)

    @classmethod
    def from_schedules(
        cls, schedules: list[CoreSchedule], coflow_map: np.ndarray
    ) -> "_Calendar":
        """Concatenate per-core schedules; ``coflow_map`` sends the
        schedules' coflow ids (dense or slot) to global ids."""
        ms, ks, is_, js, sz, es, cp = [], [], [], [], [], [], []
        for k, cs in enumerate(schedules):
            if len(cs.coflow) == 0:
                continue
            ms.append(coflow_map[cs.coflow])
            ks.append(np.full(len(cs.coflow), k, dtype=np.int64))
            is_.append(np.asarray(cs.src, dtype=np.int64))
            js.append(np.asarray(cs.dst, dtype=np.int64))
            sz.append(np.asarray(cs.size, dtype=np.float64))
            es.append(np.asarray(cs.establish, dtype=np.float64))
            cp.append(np.asarray(cs.complete, dtype=np.float64))
        if not ms:
            return cls.empty()
        return cls(
            np.concatenate(ms), np.concatenate(ks), np.concatenate(is_),
            np.concatenate(js), np.concatenate(sz), np.concatenate(es),
            np.concatenate(cp),
        )


@dataclasses.dataclass
class _Busy:
    """Committed in-flight circuits as parallel arrays (k, i, j, end)."""

    k: np.ndarray
    i: np.ndarray
    j: np.ndarray
    end: np.ndarray

    @classmethod
    def empty(cls) -> "_Busy":
        zi = np.zeros(0, dtype=np.int64)
        return cls(zi, zi, zi, np.zeros(0))

    def keep_after(self, now: float) -> "_Busy":
        sel = self.end > now
        return _Busy(self.k[sel], self.i[sel], self.j[sel], self.end[sel])

    def extend(self, k, i, j, end) -> "_Busy":
        return _Busy(
            np.concatenate([self.k, k]), np.concatenate([self.i, i]),
            np.concatenate([self.j, j]), np.concatenate([self.end, end]),
        )

    def tables(self, now: float, num_cores: int) -> dict | None:
        """`schedule_batch_arrays(busy=...)` phantom tables (or None)."""
        if self.k.size == 0:
            return None
        tabs = {}
        for k in range(num_cores):
            sel = self.k == k
            n = int(sel.sum())
            if n:
                tabs[0, k] = dict(
                    src=self.i[sel], dst=self.j[sel],
                    rel=np.full(n, now, dtype=np.float64),
                    dur=self.end[sel] - now,
                )
        return tabs


def _arrival_batches(
    releases: np.ndarray,
    n_batches: int | None,
    batch_window: float | None,
) -> list[tuple[float, list[int]]]:
    """Group coflows into arrival batches: [(epoch_time, [global ids])].

    ``n_batches``: split the release-sorted trace into that many chunks;
    a chunk's epoch fires when its FIRST coflow arrives (replay-style —
    later members are admitted early but their releases still lower-bound
    every establishment).  ``batch_window``: group coflows whose releases
    fall within one window; the epoch fires at the LAST release of the
    group (true online — nothing is known before it arrives).  Default
    (both None): one batch per distinct release instant.
    """
    if n_batches is not None and batch_window is not None:
        raise ValueError("pass n_batches or batch_window, not both")
    order = np.argsort(releases, kind="stable")
    if order.size == 0:
        return []
    if n_batches is not None:
        if n_batches <= 0:
            raise ValueError(f"n_batches must be positive, got {n_batches}")
        chunks = np.array_split(order, min(n_batches, order.size))
        return [
            (float(releases[c[0]]), [int(m) for m in c])
            for c in chunks
            if c.size
        ]
    window = 0.0 if batch_window is None else float(batch_window)
    if window < 0:
        raise ValueError(f"batch_window must be >= 0, got {batch_window}")
    rs = releases[order]
    batches = []
    i = 0
    while i < order.size:
        j = i + 1
        while j < order.size and rs[j] <= rs[i] + window:
            j += 1
        batches.append((float(rs[j - 1]), [int(m) for m in order[i:j]]))
        i = j
    return batches


def stream(
    instance: CoflowInstance,
    *,
    scheme: str = "ours",
    lp_method: str = "batch",
    lp_iters: int = 3000,
    lp_iters_warm: int | None = None,
    discipline: str = "greedy",
    n_batches: int | None = None,
    batch_window: float | None = None,
    pool_size: int | None = None,
    preempt: bool = True,
    warm_start: bool = True,
    validate: bool = True,
    admission: str = "fifo",
    epoch_mode: str = "auto",
    flow_quantum: int = 64,
) -> StreamResult:
    """Schedule `instance`'s coflows online, admitting by release time.

    ``instance.releases`` are the arrival times (use
    `repro.traffic.arrivals.with_releases` to stamp a generated arrival
    process onto any workload).  ``lp_method`` is ``"batch"`` (the
    warm-startable subgradient solver — the production path) or
    ``"exact"`` (per-epoch HiGHS; deterministic, used by the parity
    tests).  ``admission`` picks the slot-pool policy under contention
    (``"fifo"`` / ``"weighted"`` / ``"size_aware"``, see
    `repro.streaming.pool.SlotPool`); it only matters when ``pool_size``
    binds.  ``epoch_mode`` selects the epoch driver (see the module
    docstring): ``"resident"`` keeps one slot-pool `EnsembleBatch` and
    the warm-state precedence matrix device-resident across epochs so
    re-solves stop retracing; ``"rebuild"`` re-packs per epoch (PR 7);
    ``"auto"`` picks resident for the batched solver.  ``flow_quantum``
    quantizes the resident flow arena: capacity starts at one quantum
    (or the stream's expected concurrent flow count, whichever is
    larger) and grows geometrically, so arena shapes — the epoch compile
    -cache buckets — stay logarithmic in the trace's flow volume.  Each
    epoch's calendar is the device calendar on TPU and GPU and the host
    calendar on a CPU, chosen by platform
    (`repro.pipeline.batch_circuit`).  See the module docstring for the
    event-loop semantics; with ``n_batches=1`` and ``preempt=False`` the
    run replays the offline `Pipeline.run_batch` bit for bit.
    """
    t_start = time.perf_counter()
    M = instance.num_coflows
    if lp_method not in ("batch", "exact"):
        raise ValueError(f"lp_method must be 'batch' or 'exact', {lp_method!r}")
    if epoch_mode not in EPOCH_MODES:
        raise ValueError(
            f"epoch_mode must be one of {EPOCH_MODES}, got {epoch_mode!r}"
        )
    if epoch_mode == "auto":
        epoch_mode = "resident" if lp_method == "batch" else "rebuild"
    if epoch_mode == "resident" and lp_method == "exact":
        raise ValueError(
            "epoch_mode='resident' drives the batched subgradient solver "
            "off the resident slot pool; use lp_method='batch' (or "
            "epoch_mode='rebuild' for per-epoch exact LPs)"
        )
    if lp_iters_warm is None:
        lp_iters_warm = max(lp_iters // 3, 1)

    # The pipeline's own LP stage is never asked to solve (epoch LPs are
    # solved here, warm-started, and fed in as completions), so its
    # lp_method is immaterial; "exact" keeps the registry validation happy.
    pipe = get_pipeline(
        scheme,
        discipline=discipline,
        lp_method="exact",
        lp_iters=lp_iters,
        circuit_backend="batch",
    )
    circuit = pipe.circuit_stage
    if not isinstance(circuit, ListCircuit) or circuit.backend != "batch":
        raise ValueError(
            f"stream() requires a batched list-circuit scheme; {scheme!r} "
            f"uses {type(circuit).__name__}"
        )
    order_stage = pipe.order_stage
    needs_lp = bool(getattr(order_stage, "needs_lp", False))

    S = M if pool_size is None else int(pool_size)
    result = StreamResult(
        scheme=scheme, discipline=discipline, lp_method=lp_method,
        preempt=preempt, warm_start=warm_start, pool_size=S,
        lp_iters=lp_iters, lp_iters_warm=lp_iters_warm,
        weights=np.asarray(instance.weights, dtype=np.float64).copy(),
        arrival=np.asarray(instance.releases, dtype=np.float64).copy(),
        admission=np.zeros(M), finish=np.zeros(M),
        epochs=[], lp_time_s=0.0, wall_time_s=0.0,
    )
    result.admission_policy = admission
    result.epoch_mode = epoch_mode
    if M == 0:
        result.wall_time_s = time.perf_counter() - t_start
        return result

    rates_by_core = np.asarray(instance.rates, dtype=np.float64)
    residual = np.asarray(instance.demands, dtype=np.float64).copy()
    pool = SlotPool(
        S,
        policy=admission,
        weights=result.weights,
        sizes=residual.reshape(M, -1).sum(axis=1),
    )
    resident = epoch_mode == "resident"
    warm = _WarmState(S, device=resident)
    totals = trace.Tally()  # the call's spans and counters
    rpool = None
    slot_to_global = None
    if resident:
        # Size the arena so a full pool of average coflows fits without
        # growth; the geometric ladder covers estimate misses.
        nnz = int(np.count_nonzero(residual))
        expected = -(-nnz * min(S, M) // M) if M else 0
        with trace.collect() as setup:
            rpool = build_slot_pool_batch(
                S, instance.num_ports, rates_by_core, instance.delta,
                flow_quantum=_round_up(
                    max(int(flow_quantum), expected, 1),
                    max(int(flow_quantum), 1),
                ),
            )
        totals.add(setup)
        slot_to_global = np.full(S, -1, dtype=np.int64)
    finished = np.zeros(M, dtype=bool)
    calendar = _Calendar.empty()
    busy = _Busy.empty()
    last_ccts = np.zeros(M)  # projected completion per active id
    two_pi_ports = 2 * instance.num_ports  # flat port axis for LP padding

    def _advance(now: float) -> np.ndarray:
        """Settle the incumbent calendar at `now`; free drained slots.

        Returns the global ids whose residual changed and who are still
        active (the slots the resident pool must re-scatter)."""
        nonlocal calendar, busy
        dirty = np.zeros(0, dtype=np.int64)
        if calendar.m.size:
            delivered = calendar.comp <= now
            started = calendar.est < now
            if preempt:
                inflight = ~delivered & started
                sent = rates_by_core[calendar.k] * np.maximum(
                    0.0, now - calendar.est - instance.delta
                )
                full = inflight & (sent >= calendar.size)
                deliver = delivered | full  # complete within float rounding
                partial = inflight & ~full
            else:  # committed: in-flight runs to completion as a phantom
                deliver = delivered | started
                partial = np.zeros_like(deliver)
            # (m, i, j) rows are unique per calendar — no accumulation.
            residual[
                calendar.m[deliver], calendar.i[deliver], calendar.j[deliver]
            ] -= calendar.size[deliver]
            if partial.any():
                residual[
                    calendar.m[partial], calendar.i[partial],
                    calendar.j[partial],
                ] -= sent[partial]
            np.maximum.at(
                result.finish, calendar.m[deliver], calendar.comp[deliver]
            )
            dirty = np.unique(calendar.m[deliver | partial])
            busy = busy.keep_after(now)
            if not preempt:
                committed = ~delivered & started
                busy = busy.extend(
                    calendar.k[committed], calendar.i[committed],
                    calendar.j[committed], calendar.comp[committed],
                )
            # Rows with est >= now were never established — cancelled
            # back into the pool with their residual untouched.
            calendar = _Calendar.empty()
        else:
            busy = busy.keep_after(now)
        np.maximum(residual, 0.0, out=residual)  # exact-0 guard only
        act = pool.active_array()
        if act.size:
            drained = act[~residual[act].reshape(act.size, -1).any(axis=1)]
            if drained.size:
                finished[drained] = True
                slots = pool.release_many(drained)
                warm.forget_slots(slots)
                if resident:
                    free_slots(rpool, slots)
                    slot_to_global[slots] = -1
                dirty = np.setdiff1d(dirty, drained, assume_unique=True)
        return dirty

    def _admit(now: float) -> list[int]:
        """Move queued arrivals into free slots (ring order, FIFO)."""
        admitted_all = []
        while True:
            admitted = pool.admit_waiting()
            if not admitted:
                return admitted_all
            for m in admitted:
                result.admission[m] = now
                if residual[m].any():
                    admitted_all.append(m)
                else:  # degenerate zero-demand coflow: done on arrival
                    result.finish[m] = max(result.finish[m], now)
                    finished[m] = True
                    warm.forget(pool.release(m))

    def _busy_count() -> int:
        return int(busy.k.size)

    def _epoch_rebuild(
        now: float, admitted: list[int], tally: trace.Tally
    ) -> None:
        """PR 7 epoch: dense residual instance, fresh `EnsembleBatch`."""
        nonlocal calendar
        t_epoch = time.perf_counter()
        with trace.span("stream.scatter"):
            actives = pool.active_ids()
            if not actives:
                return
            act = np.asarray(actives, dtype=np.int64)
            Me = act.shape[0]
            inst_e = CoflowInstance(
                demands=residual[act].copy(),
                weights=result.weights[act].copy(),
                releases=np.maximum(result.arrival[act], now),
                rates=rates_by_core.copy(),
                delta=instance.delta,
            )

        lp_sol = None
        is_warm = False
        iters_used = 0
        lp_wall = 0.0
        if needs_lp:
            with trace.span("stream.lp") as lp_span:
                if lp_method == "exact":
                    lp_sol = lp.solve_exact(inst_e)
                else:
                    arrays = lp.pack_lp_arrays(
                        [inst_e], pad_coflows=S, pad_ports=two_pi_ports
                    )
                    slots = pool.slots_of(actives)
                    if warm_start:
                        Y0, is_warm = warm.gather(
                            slots, arrays["Y0"][0, :Me, :Me]
                        )
                        arrays["Y0"][0, :Me, :Me] = Y0
                    iters_used = lp_iters_warm if is_warm else lp_iters
                    batch = lp.solve_subgradient_batch_arrays(
                        arrays, iters=iters_used
                    )
                    lp_sol = batch.unpack([Me])[0]
                    warm.scatter(slots, lp_sol.precedence)
            lp_wall = lp_span.seconds
            result.lp_time_s += lp_wall

        with trace.span("stream.scatter"):
            ensemble = build_ensemble_batch([inst_e], with_lp_arrays=False)
        with trace.span("stream.order"):
            if needs_lp:
                comp = np.zeros(ensemble.weights.shape)
                comp[0, :Me] = lp_sol.completion
                orders_arr = order_stage.order_batch(ensemble, comp)
            else:
                orders_arr = order_stage.order_batch(ensemble)
        alloc_batch = pipe.allocate_stage.allocate_batch_arrays(
            ensemble, orders_arr
        )
        with trace.span("calendar.pack"):
            busy_tabs = busy.tables(now, instance.num_cores)
        pairs = schedule_batch_arrays(
            ensemble, alloc_batch,
            discipline=circuit.discipline, busy=busy_tabs,
        )
        with trace.span("calendar.readback"):
            schedules, ccts_e = pairs[0]
        if validate:
            with trace.span("stream.validate"):
                validate_schedule(inst_e, schedules)

        with trace.span("stream.record"):
            calendar = _Calendar.from_schedules(schedules, act)
            last_ccts[act] = np.asarray(ccts_e, dtype=np.float64)

            alloc = alloc_batch.materialize(ensemble)[0]
            order_dense = np.asarray(orders_arr[0][:Me])
            result.epochs.append(
                EpochRecord(
                    index=len(result.epochs),
                    time=now,
                    actives=act,
                    admitted=np.asarray(admitted, dtype=np.int64),
                    order=act[order_dense],
                    allocation=alloc,
                    ccts=np.asarray(ccts_e, dtype=np.float64).copy(),
                    lp=lp_sol,
                    warm=is_warm,
                    lp_iters_used=iters_used,
                    lp_wall_s=lp_wall,
                    num_busy=0 if busy_tabs is None else _busy_count(),
                    wall_s=time.perf_counter() - t_epoch,
                    lp_objective=(
                        float(lp_sol.objective) if lp_sol is not None
                        else None
                    ),
                    spans=tally.spans,
                    counts=tally.counts,
                )
            )

    def _epoch_resident(
        now: float, admitted: list[int], dirty: np.ndarray,
        tally: trace.Tally,
    ) -> None:
        """Device-resident epoch: scatter into the slot pool, solve at
        fixed padded shapes, read the calendar back in slot space."""
        nonlocal calendar
        t_epoch = time.perf_counter()
        with trace.span("stream.scatter"):
            actives = pool.active_ids()
            if not actives:
                return
            act = np.asarray(actives, dtype=np.int64)
            Me = act.shape[0]
            slots = pool.slots_of(actives)  # aligned with ascending-id order
            rel_clamped = np.maximum(result.arrival[act], now)

            # In-place slot scatter: residuals that changed since the last
            # epoch (settled/preempted) plus fresh admissions; every active
            # slot gets the per-epoch release clamp.
            upd = np.union1d(np.asarray(admitted, dtype=np.int64), dirty)
            if upd.size:
                upd_slots = pool.slots_of(upd)
                update_slots(
                    rpool, upd_slots, residual[upd], result.weights[upd],
                    np.maximum(result.arrival[upd], now),
                )
                slot_to_global[upd_slots] = upd
            set_slot_releases(rpool, slots, rel_clamped)
            b = rpool.batch

        lp_sol_objective = None
        is_warm = False
        iters_used = 0
        lp_wall = 0.0
        comp_dense = None
        if needs_lp:
            with trace.span("stream.lp") as lp_span:
                # Dense-gathered LP inputs: bit-equal to
                # `pack_lp_arrays([inst_e], pad_coflows=S, pad_ports=2N)`
                # (per-slot f32 rows were cast from the same f64 values at
                # scatter time), so the same compiled solver program runs —
                # zero LP retraces across epochs.
                Y0_default = np.zeros((S, S), dtype=np.float32)
                Y0_default[:Me, :Me] = lp.warm_start_Y0_dense(
                    result.weights[act], b.glb[0, slots]
                )
                slots_padded = np.full(S, S, dtype=np.int32)
                slots_padded[:Me] = slots
                if warm_start:
                    Y0_dev, is_warm = warm.gather_device(
                        slots_padded, jnp.asarray(Y0_default)
                    )
                else:
                    Y0_dev = jnp.asarray(Y0_default)
                rho_d = np.zeros_like(b.lp_rho)
                tau_d = np.zeros_like(b.lp_tau)
                w_d = np.zeros_like(b.lp_weights)
                r_d = np.zeros_like(b.lp_releases)
                mask_d = np.zeros_like(b.coflow_mask)
                rho_d[0, :Me] = b.lp_rho[0, slots]
                tau_d[0, :Me] = b.lp_tau[0, slots]
                w_d[0, :Me] = b.lp_weights[0, slots]
                r_d[0, :Me] = b.lp_releases[0, slots]
                mask_d[0, :Me] = True
                arrays = dict(
                    Y0=Y0_dev[None], p_rho=rho_d, p_tau=tau_d, weights=w_d,
                    releases=r_d, inv_R=b.inv_R, delta_over_K=b.delta_over_K,
                    coflow_mask=mask_d, port_mask=b.port_mask,
                )
                iters_used = lp_iters_warm if is_warm else lp_iters
                batch_sol = lp.solve_subgradient_batch_arrays(
                    arrays, iters=iters_used
                )
                comp_all, obj_all = trace.to_host(
                    batch_sol.completion, batch_sol.objective
                )
                comp_dense = comp_all[0]
                lp_sol_objective = float(obj_all[0])
                warm.scatter_device(slots_padded, slots, batch_sol.y[0])
            lp_wall = lp_span.seconds
            result.lp_time_s += lp_wall

        with trace.span("stream.order"):
            # Dense ordering view over the resident vectors (gathered to
            # the ascending-global-id dense convention, masked padding at
            # the tail) — the same keys, masks and stable sort as the
            # rebuild path, so dense positions 0..Me-1 order identically.
            w64 = np.zeros((1, S))
            glb64 = np.zeros((1, S))
            rel64 = np.zeros((1, S))
            mask64 = np.zeros((1, S), dtype=bool)
            w64[0, :Me] = b.weights[0, slots]
            glb64[0, :Me] = b.glb[0, slots]
            rel64[0, :Me] = rel_clamped
            mask64[0, :Me] = True
            view = order_view(w64, glb64, rel64, mask64)
            if needs_lp:
                comp = np.zeros((1, S))
                comp[0, :Me] = comp_dense[:Me]
                orders_dense = order_stage.order_batch(view, comp)
            else:
                orders_dense = order_stage.order_batch(view)
            order_dense = np.asarray(orders_dense[0][:Me])

            # Slot-space order: active slots by dense priority, free slots
            # at the tail (their flows are invalid — exact no-op scan
            # steps).
            order_slots = np.empty(S, dtype=np.int64)
            order_slots[:Me] = slots[order_dense]
            order_slots[Me:] = np.setdiff1d(
                np.arange(S, dtype=np.int64), slots, assume_unique=True
            )
        alloc_batch = pipe.allocate_stage.allocate_batch_arrays(
            b, order_slots[None, :]
        )
        with trace.span("calendar.pack"):
            busy_tabs = busy.tables(now, instance.num_cores)
        pairs = schedule_batch_arrays(
            b, alloc_batch,
            discipline=circuit.discipline, busy=busy_tabs,
        )
        with trace.span("calendar.readback"):
            schedules, ccts_slot = pairs[0]  # slot-indexed (S,) CCTs
            ccts_dense = np.asarray(ccts_slot, dtype=np.float64)[slots]
        if validate:
            with trace.span("stream.validate"):
                inst_e = CoflowInstance(
                    demands=residual[act].copy(),
                    weights=result.weights[act].copy(),
                    releases=rel_clamped,
                    rates=rates_by_core.copy(),
                    delta=instance.delta,
                )
                dense_of_slot = np.full(S, -1, dtype=np.int64)
                dense_of_slot[slots] = np.arange(Me, dtype=np.int64)
                remapped = [
                    CoreSchedule(
                        coflow=dense_of_slot[cs.coflow], src=cs.src,
                        dst=cs.dst, size=cs.size, establish=cs.establish,
                        complete=cs.complete, rate=cs.rate, delta=cs.delta,
                    )
                    for cs in schedules
                ]
                validate_schedule(inst_e, remapped)

        with trace.span("stream.record"):
            calendar = _Calendar.from_schedules(schedules, slot_to_global)
            last_ccts[act] = ccts_dense

            result.epochs.append(
                EpochRecord(
                    index=len(result.epochs),
                    time=now,
                    actives=act,
                    admitted=np.asarray(admitted, dtype=np.int64),
                    order=act[order_dense],
                    allocation=None,  # slot-space; see `epochs[...].order`
                    ccts=ccts_dense.copy(),
                    lp=None,
                    warm=is_warm,
                    lp_iters_used=iters_used,
                    lp_wall_s=lp_wall,
                    num_busy=0 if busy_tabs is None else _busy_count(),
                    wall_s=time.perf_counter() - t_epoch,
                    lp_objective=lp_sol_objective,
                    spans=tally.spans,
                    counts=tally.counts,
                )
            )

    def _epoch(now: float, ids: list[int] | None) -> None:
        """One event: settle at ``now``, queue ``ids`` (arrivals; None
        for a drain event), admit, re-solve.  The epoch's spans and
        counters go to its `EpochRecord` and the call's totals."""
        with trace.collect() as tally, trace.span("stream.epoch"):
            with trace.span("stream.advance"):
                dirty = _advance(now)
            with trace.span("stream.admit"):
                if ids is not None:
                    pool.push(ids)
                admitted = _admit(now)
                if ids is None and not admitted:
                    raise RuntimeError(
                        "drain epoch freed no slot — non-increasing "
                        "calendar?"
                    )
            if resident:
                _epoch_resident(now, admitted, dirty, tally)
            else:
                _epoch_rebuild(now, admitted, tally)
        totals.add(tally)

    # --- event loop -------------------------------------------------------
    for now, ids in _arrival_batches(result.arrival, n_batches, batch_window):
        _epoch(now, ids)

    while pool.queue:  # pool-bound overflow: admit as slots drain
        act = pool.active_array()
        if act.size == 0:
            raise RuntimeError("admission queue stuck with an empty pool")
        _epoch(float(last_ccts[act].min()), None)

    # Final calendar runs to completion undisturbed.
    with trace.collect() as tally, trace.span("stream.finish"):
        if calendar.m.size:
            residual[calendar.m, calendar.i, calendar.j] -= calendar.size
            np.maximum.at(result.finish, calendar.m, calendar.comp)
            calendar = _Calendar.empty()
        np.maximum(residual, 0.0, out=residual)
        act = pool.active_array()
        for m in act:
            if residual[m].any():
                raise RuntimeError(
                    f"coflow {m} left {residual[m].sum():g} undelivered "
                    "demand"
                )
        if act.size:
            finished[act] = True
            slots = pool.release_many(act)
            warm.forget_slots(slots)
            if resident:
                free_slots(rpool, slots)
                slot_to_global[slots] = -1
    totals.add(tally)
    if not finished.all():
        missing = np.nonzero(~finished)[0]
        raise RuntimeError(f"coflows never completed: {missing.tolist()}")

    result.spans, result.counts = totals.spans, totals.counts
    result.wall_time_s = time.perf_counter() - t_start
    return result
