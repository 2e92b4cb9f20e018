"""The device-resident `EnsembleBatch`: one padded pytree from LP to circuit.

Before this module, each batched stage of Algorithm 1 re-extracted and
re-padded its own arrays from the host-side `CoflowInstance` list — the LP
packed (B, Mp, Pp) port stats, allocation re-walked every demand matrix
into flow tables, and the circuit calendar re-derived member tables from
`Allocation` objects.  `EnsembleBatch` hoists all of that into **one**
construction per shape bucket:

  * the LP solver's padded arrays (`lp_arrays` — exactly
    `repro.core.lp.pack_lp_arrays`'s layout, f32 + masks);
  * f64 per-coflow vectors (`weights`, `releases`, `glb`) that the
    ordering stages sort batched;
  * the canonical flow table (`flow_*`): every instance's nonzero flows
    in (coflow id ascending, largest-first within coflow) order, padded to
    a shared flow axis — order-*independent*, so applying a global coflow
    order is a stable segment permutation (`permute_flows`), not a
    re-extraction;
  * per-core arrays (`inv_rates`, `rates`, masks) for allocation's
    prefix-argmin scan and the circuit calendar's durations.

Downstream, `repro.pipeline.batch_alloc.allocate_batch_arrays` and
`repro.pipeline.batch_circuit.schedule_batch_arrays` consume these arrays
directly (producing the `AllocationBatch` pytree and padded calendar
outputs), and `Pipeline.run_batch` materializes per-instance results only
at the very end.  Each construction counts one ``ensemble.build`` in the
current `repro.trace` tally, so tests can assert the one-build-per-bucket
contract at stage boundaries.

Sharding: `build_ensemble_batch(..., mesh=...)` pads the member axis to a
multiple of the mesh's ``"data"`` axis and records a
`jax.sharding.NamedSharding` for it; the jitted stages `device_put` their
inputs with it, so the whole pipeline runs SPMD across the ensemble.
Members are independent (every batched program is a vmap over the member
axis), so sharded and unsharded runs are bit-identical per member.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import numpy as np

import jax

from repro.core import lp as lp_mod
from repro.core.allocation import Allocation
from repro.core.coflow import CoflowInstance, flows_of, port_stats
from repro.trace import count

__all__ = [
    "EnsembleBatch",
    "AllocationBatch",
    "SlotPoolBatch",
    "build_ensemble_batch",
    "build_slot_pool_batch",
    "update_slots",
    "set_slot_releases",
    "free_slots",
    "expansion_maps",
    "PAD_LB",
]

# Padded-core sentinel: dominates every real candidate bound but stays
# finite so padded-step arithmetic never produces inf * 0 = NaN.
# (`repro.pipeline.batch_alloc` re-exports this as its historical name.)
PAD_LB = 1e30

# Counters (`repro.trace.count`, in the current tally):
#
#   * ``ensemble.build`` — `EnsembleBatch` constructions.  `Pipeline.
#     run_batch` must build exactly one per ensemble (and the bucketed LP
#     phase one per bucket) — tests read it to assert no stage re-pads
#     behind the pipeline's back.
#   * ``slot.scatter`` — the **controlled exemption** from the build-once
#     contract: in-place slot scatters (`update_slots` / `free_slots`)
#     into a resident `SlotPoolBatch`.  The streaming service mutates one
#     long-lived batch instead of rebuilding per epoch, so its builds stay
#     at the pool constructions while this counter tracks the epoch
#     updates — tests read both to assert the service never re-packs.
#   * ``slot.grow`` — arena regrowths (flow-axis capacity bumps) of
#     resident slot pools: each one is a new padded flow shape, i.e. one
#     entry of the epoch compile-cache bucket ladder.  Geometric growth
#     bounds it to O(log(total flows) / log 2) distinct shapes per pool.


def _round_up(n: int, q: int) -> int:
    return -(-n // q) * q


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EnsembleBatch:
    """One shape bucket of instances as a single padded pytree.

    Array fields have a leading member axis of size ``pad_members``
    (>= ``num_instances``; larger only when padding to a sharding multiple
    — padded members are fully masked and discarded on unpack).  Static
    metadata (`meta_fields`) records the true per-instance sizes used to
    unpad.
    """

    # --- LP arrays (f32 + masks; `repro.core.lp.pack_lp_arrays` layout) --
    lp_Y0: np.ndarray  # (Bp, Mp, Mp) f32 warm start
    lp_rho: np.ndarray  # (Bp, Mp, Pp) f32
    lp_tau: np.ndarray  # (Bp, Mp, Pp) f32
    lp_weights: np.ndarray  # (Bp, Mp) f32
    lp_releases: np.ndarray  # (Bp, Mp) f32
    inv_R: np.ndarray  # (Bp,) f32
    delta_over_K: np.ndarray  # (Bp,) f32
    coflow_mask: np.ndarray  # (Bp, Mp) bool
    port_mask: np.ndarray  # (Bp, Pp) bool
    # --- f64 per-coflow vectors (ordering + results) ---------------------
    weights: np.ndarray  # (Bp, Mp) f64
    releases: np.ndarray  # (Bp, Mp) f64
    glb: np.ndarray  # (Bp, Mp) f64 — delta + rho_m / R (WSPT score base)
    # --- canonical flow table (coflow asc, largest-first within) ---------
    flow_coflow: np.ndarray  # (Bp, Fp) i64, 0 on padding
    flow_src: np.ndarray  # (Bp, Fp) i64 raw ingress i
    flow_dst: np.ndarray  # (Bp, Fp) i64 raw egress j
    flow_pi: np.ndarray  # (Bp, Fp) i32 flat ingress port (= i)
    flow_pj: np.ndarray  # (Bp, Fp) i32 flat egress port (= N + j)
    flow_size: np.ndarray  # (Bp, Fp) f64
    flow_valid: np.ndarray  # (Bp, Fp) bool
    flow_counts: np.ndarray  # (Bp, Mp) i64 — flows per coflow
    # --- per-core arrays -------------------------------------------------
    rates: np.ndarray  # (Bp, Kp) f64, 1.0 on padding
    inv_rates: np.ndarray  # (Bp, Kp) f64, PAD_LB on padding
    core_mask: np.ndarray  # (Bp, Kp) bool
    delta: np.ndarray  # (Bp,) f64
    # --- static metadata -------------------------------------------------
    num_instances: int = dataclasses.field(metadata=dict(static=True))
    num_coflows: tuple = dataclasses.field(metadata=dict(static=True))
    num_ports: tuple = dataclasses.field(metadata=dict(static=True))
    num_cores: tuple = dataclasses.field(metadata=dict(static=True))
    num_flows: tuple = dataclasses.field(metadata=dict(static=True))
    sharding: Any = dataclasses.field(metadata=dict(static=True))

    # -- shapes -----------------------------------------------------------
    @property
    def pad_members(self) -> int:
        return int(self.weights.shape[0])

    @property
    def pad_coflows(self) -> int:
        return int(self.weights.shape[1])

    @property
    def pad_flat_ports(self) -> int:
        return int(self.port_mask.shape[1])

    @property
    def pad_flows(self) -> int:
        return int(self.flow_size.shape[1])

    @property
    def pad_cores(self) -> int:
        return int(self.rates.shape[1])

    # -- LP ---------------------------------------------------------------
    def lp_arrays(self) -> dict[str, np.ndarray]:
        """`solve_subgradient_batch_arrays` input dict (no copy)."""
        return dict(
            Y0=self.lp_Y0, p_rho=self.lp_rho, p_tau=self.lp_tau,
            weights=self.lp_weights, releases=self.lp_releases,
            inv_R=self.inv_R, delta_over_K=self.delta_over_K,
            coflow_mask=self.coflow_mask, port_mask=self.port_mask,
        )

    @property
    def has_lp_arrays(self) -> bool:
        """False when built with ``with_lp_arrays=False`` (the post-LP
        pipeline's mode: masks are kept, the O(B*Mp^2) warm starts and
        O(B*Mp*Pp) port statistics are not packed)."""
        return self.lp_Y0.shape[1] == self.pad_coflows

    def solve_lp(self, iters: int = 3000) -> lp_mod.LPSolutionBatch:
        """Ordering-LP solve of the whole bucket, array-in/array-out."""
        if not self.has_lp_arrays:
            raise RuntimeError(
                "this EnsembleBatch was built with with_lp_arrays=False "
                "(post-LP pipeline mode); rebuild with the default to "
                "solve the ordering LP from it"
            )
        return lp_mod.solve_subgradient_batch_arrays(
            self.lp_arrays(), iters=iters, sharding=self.sharding
        )

    # -- ordering ---------------------------------------------------------
    def pad_orders(self, orders: Sequence[np.ndarray]) -> np.ndarray:
        """(Bp, Mp) padded order array from per-instance permutations
        (padded coflow ids appended in id order, padded members identity)."""
        Bp, Mp = self.weights.shape
        out = np.tile(np.arange(Mp, dtype=np.int64), (Bp, 1))
        for b, o in enumerate(orders):
            M = self.num_coflows[b]
            out[b, :M] = o
            out[b, M:] = np.arange(M, Mp)
        return out

    # -- flows ------------------------------------------------------------
    def permute_flows(self, orders: np.ndarray) -> np.ndarray:
        """Stable flow permutation realizing a global coflow order.

        ``orders`` is (Bp, Mp).  Returns ``perm`` (Bp, Fp) such that the
        canonical flow table gathered through ``perm`` lists flows exactly
        as `repro.pipeline.batch_alloc.flow_sequence` would emit them:
        coflows along the order, largest-first within each coflow (the
        canonical intra-coflow order, preserved by the stable sort).
        """
        Bp, Mp = orders.shape
        pos = np.empty_like(orders)
        np.put_along_axis(
            pos, orders, np.broadcast_to(np.arange(Mp), (Bp, Mp)), axis=1
        )
        key = np.take_along_axis(pos, self.flow_coflow, axis=1)
        key = np.where(self.flow_valid, key, Mp)
        return np.argsort(key, axis=1, kind="stable")

    def prefix_ends(self, orders: np.ndarray) -> np.ndarray:
        """(Bp, Mp) running flow count after each order position."""
        counts = np.take_along_axis(self.flow_counts, orders, axis=1)
        return np.cumsum(counts, axis=1)

    # -- member expansion -------------------------------------------------
    def expand_members(
        self, reps: int
    ) -> tuple["EnsembleBatch", np.ndarray, np.ndarray]:
        """Tile every real member ``reps`` times along the member axis.

        The member-expansion primitive behind candidate-search refinement
        (`repro.pipeline.refine`): expanded row ``b * reps + c`` is copy
        (candidate slot) ``c`` of instance ``b`` — candidate-major within
        instance, so downstream stages see ``B * reps`` ordinary members
        and never learn that rows share problem data.  Only the
        ``num_instances`` real rows are tiled (padding rows are NOT
        interleaved — stages assume rows ``0..num_instances-1`` are real);
        when the batch carries a sharding, the tail re-pads to a multiple
        of the ``data`` axis by repeating an existing fully-masked row.

        Returns ``(expanded, instance_of, candidate_of)`` where the two
        (B*reps,) index maps send an expanded row to its source instance
        and candidate slot (see `expansion_maps`).  This is a pure gather
        of an existing build, not a re-pack from instances, so
        ``ensemble.build`` is intentionally NOT counted — the one-build-per-
        ensemble contract still counts constructions from host data.
        """
        reps = int(reps)
        if reps < 1:
            raise ValueError(f"reps must be >= 1, got {reps}")
        B = self.num_instances
        Bp = self.pad_members
        idx = np.repeat(np.arange(B, dtype=np.int64), reps)
        new_B = B * reps
        new_Bp = new_B
        if self.sharding is not None:
            q = int(self.sharding.mesh.shape["data"])
            new_Bp = max(_round_up(max(new_B, 1), q), new_B)
        if new_Bp > new_B:
            # A shard-count remainder implies B was rounded up too, so a
            # fully-masked template row exists to clone into the tail.
            assert Bp > B, "sharded batch without a masked padding row"
            idx = np.concatenate(
                [idx, np.full(new_Bp - new_B, Bp - 1, dtype=np.int64)]
            )

        def rep(t: tuple) -> tuple:
            return tuple(x for x in t for _ in range(reps))

        kw = {}
        for f in dataclasses.fields(self):
            if f.metadata.get("static"):
                kw[f.name] = getattr(self, f.name)
            else:
                kw[f.name] = np.asarray(getattr(self, f.name))[idx]
        kw.update(
            num_instances=new_B,
            num_coflows=rep(self.num_coflows),
            num_ports=rep(self.num_ports),
            num_cores=rep(self.num_cores),
            num_flows=rep(self.num_flows),
            sharding=self.sharding,
        )
        return EnsembleBatch(**kw), *expansion_maps(B, reps)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AllocationBatch:
    """Batched result of Algorithm 1 Lines 3–15 over one `EnsembleBatch`.

    The flow axis is in **allocation order** (global coflow order,
    largest-first within coflow) — the canonical table gathered through
    ``perm`` — which is also the circuit stage's priority order, so the
    calendar consumes these arrays with no further sorting.
    """

    order: np.ndarray  # (Bp, Mp) i64 — the global order used
    perm: np.ndarray  # (Bp, Fp) i64 canonical -> ordered gather
    coflow: np.ndarray  # (Bp, Fp) i64
    src: np.ndarray  # (Bp, Fp) i64 raw ingress
    dst: np.ndarray  # (Bp, Fp) i64 raw egress
    size: np.ndarray  # (Bp, Fp) f64
    valid: np.ndarray  # (Bp, Fp) bool
    core: np.ndarray  # (Bp, Fp) i64 — assigned core per flow
    rho_ports: np.ndarray  # (Bp, Kp, Pp) f64 final prefix port loads
    tau_ports: np.ndarray  # (Bp, Kp, Pp) f64 final prefix port counts
    prefix_lb: np.ndarray  # (Bp, Mp) f64 per order position
    ends: np.ndarray  # (Bp, Mp) i64 running flow count per order position

    def materialize(self, ensemble: EnsembleBatch) -> list[Allocation]:
        """Per-instance `Allocation`s (host side, end-of-pipeline only) —
        field-for-field what `repro.core.allocation.allocate` returns."""
        out = []
        for b in range(ensemble.num_instances):
            F = ensemble.num_flows[b]
            K = ensemble.num_cores[b]
            P = 2 * ensemble.num_ports[b]
            M = ensemble.num_coflows[b]
            out.append(
                Allocation(
                    coflow=self.coflow[b, :F],
                    src=self.src[b, :F],
                    dst=self.dst[b, :F],
                    size=self.size[b, :F],
                    core=self.core[b, :F],
                    rho_ports=self.rho_ports[b, :K, :P],
                    tau_ports=self.tau_ports[b, :K, :P],
                    prefix_lb=self.prefix_lb[b, :M],
                )
            )
        return out


def expansion_maps(
    num_instances: int, reps: int
) -> tuple[np.ndarray, np.ndarray]:
    """Row-index maps of `EnsembleBatch.expand_members`'s layout.

    Expanded row ``r`` (for ``r < num_instances * reps``) holds candidate
    slot ``candidate_of[r]`` of instance ``instance_of[r]`` — the inverse
    of ``row = instance * reps + candidate``.
    """
    instance_of = np.repeat(
        np.arange(num_instances, dtype=np.int64), reps
    )
    candidate_of = np.tile(
        np.arange(reps, dtype=np.int64), num_instances
    )
    return instance_of, candidate_of


def build_ensemble_batch(
    instances: Sequence[CoflowInstance],
    *,
    pad_coflows: int | None = None,
    pad_ports: int | None = None,
    pad_flows: int | None = None,
    pad_cores: int | None = None,
    mesh=None,
    warm_start_orders: Sequence[np.ndarray | None] | None = None,
    with_lp_arrays: bool = True,
) -> EnsembleBatch:
    """Build the unified padded pytree for one shape bucket — **once**.

    ``pad_*`` default to the ensemble maxima (a bucketed caller passes the
    bucket shape so equal-shaped buckets share compiled programs).  With
    ``mesh`` the member axis pads up to a multiple of the mesh's ``data``
    axis and every jitted stage places its inputs with the recorded
    `NamedSharding`; padded members are fully masked no-ops.
    ``with_lp_arrays=False`` skips the LP solver's O(B*Mp^2) warm starts
    and O(B*Mp*Pp) port statistics (keeping the cheap masks) — the mode
    `Pipeline.run_batch` uses when LP solutions are solved upstream.
    """
    count("ensemble.build")

    instances = list(instances)
    B = len(instances)
    Ms = tuple(inst.num_coflows for inst in instances)
    Ns = tuple(inst.num_ports for inst in instances)
    Ks = tuple(inst.num_cores for inst in instances)
    Mp = pad_coflows if pad_coflows is not None else max(Ms, default=0)
    Pp = pad_ports if pad_ports is not None else max(
        (2 * n for n in Ns), default=0
    )
    Kp = pad_cores if pad_cores is not None else max(Ks, default=1)
    Kp = max(Kp, 1)

    sharding = None
    Bp = B
    if mesh is not None:
        from repro.launch.mesh import data_axis_size, data_sharding

        sharding = data_sharding(mesh)
        Bp = max(_round_up(max(B, 1), data_axis_size(mesh)), B)

    # LP arrays: the exact `pack_lp_arrays` layout, member-padded with
    # all-masked zero rows (inv_R = 0 keeps every padded term finite).
    if with_lp_arrays:
        lp_arr = lp_mod.pack_lp_arrays(
            instances, pad_coflows=Mp, pad_ports=Pp,
            warm_start_orders=warm_start_orders, pad_members=Bp,
        )
    else:
        # Post-LP mode: keep the masks (ordering needs them), drop the
        # heavy solver inputs (zero-width so `has_lp_arrays` is False).
        coflow_mask = np.zeros((Bp, Mp), dtype=bool)
        port_mask = np.zeros((Bp, Pp), dtype=bool)
        for b, inst in enumerate(instances):
            coflow_mask[b, : inst.num_coflows] = True
            port_mask[b, : 2 * inst.num_ports] = True
        lp_arr = dict(
            Y0=np.zeros((Bp, 0, 0), dtype=np.float32),
            p_rho=np.zeros((Bp, 0, 0), dtype=np.float32),
            p_tau=np.zeros((Bp, 0, 0), dtype=np.float32),
            weights=np.zeros((Bp, 0), dtype=np.float32),
            releases=np.zeros((Bp, 0), dtype=np.float32),
            inv_R=np.zeros(Bp, dtype=np.float32),
            delta_over_K=np.zeros(Bp, dtype=np.float32),
            coflow_mask=coflow_mask,
            port_mask=port_mask,
        )

    # Canonical flow tables: coflow id ascending, largest-first within.
    seqs = []
    for inst in instances:
        ms, is_, js, ds = [], [], [], []
        for m in range(inst.num_coflows):
            i_idx, j_idx, sizes = flows_of(
                inst.demands[m], largest_first=True
            )
            ms.append(np.full(i_idx.shape[0], m, dtype=np.int64))
            is_.append(i_idx)
            js.append(j_idx)
            ds.append(sizes)
        cat = (
            lambda parts, dt: np.concatenate(parts).astype(dt)
            if parts else np.zeros(0, dtype=dt)
        )
        seqs.append(
            (
                cat(ms, np.int64), cat(is_, np.int64), cat(js, np.int64),
                cat(ds, np.float64),
            )
        )
    Fs = tuple(s[0].shape[0] for s in seqs)
    Fp = pad_flows if pad_flows is not None else max(Fs, default=0)

    weights = np.zeros((Bp, Mp))
    releases = np.zeros((Bp, Mp))
    glb = np.zeros((Bp, Mp))
    flow_coflow = np.zeros((Bp, Fp), dtype=np.int64)
    flow_src = np.zeros((Bp, Fp), dtype=np.int64)
    flow_dst = np.zeros((Bp, Fp), dtype=np.int64)
    flow_pi = np.zeros((Bp, Fp), dtype=np.int32)
    flow_pj = np.zeros((Bp, Fp), dtype=np.int32)
    flow_size = np.zeros((Bp, Fp))
    flow_valid = np.zeros((Bp, Fp), dtype=bool)
    flow_counts = np.zeros((Bp, Mp), dtype=np.int64)
    rates = np.ones((Bp, Kp))
    inv_rates = np.full((Bp, Kp), PAD_LB)
    core_mask = np.zeros((Bp, Kp), dtype=bool)
    delta = np.zeros(Bp)
    for b, inst in enumerate(instances):
        M, N, K, F = Ms[b], Ns[b], Ks[b], Fs[b]
        weights[b, :M] = inst.weights
        releases[b, :M] = inst.releases
        glb[b, :M] = inst.global_lower_bound()
        ms, i_idx, j_idx, sizes = seqs[b]
        flow_coflow[b, :F] = ms
        flow_src[b, :F] = i_idx
        flow_dst[b, :F] = j_idx
        flow_pi[b, :F] = i_idx
        flow_pj[b, :F] = N + j_idx
        flow_size[b, :F] = sizes
        flow_valid[b, :F] = True
        if F:
            flow_counts[b, :M] = np.bincount(ms, minlength=M)
        rates[b, :K] = inst.rates
        inv_rates[b, :K] = 1.0 / inst.rates
        core_mask[b, :K] = True
        delta[b] = inst.delta

    return EnsembleBatch(
        lp_Y0=lp_arr["Y0"], lp_rho=lp_arr["p_rho"], lp_tau=lp_arr["p_tau"],
        lp_weights=lp_arr["weights"], lp_releases=lp_arr["releases"],
        inv_R=lp_arr["inv_R"], delta_over_K=lp_arr["delta_over_K"],
        coflow_mask=lp_arr["coflow_mask"], port_mask=lp_arr["port_mask"],
        weights=weights, releases=releases, glb=glb,
        flow_coflow=flow_coflow, flow_src=flow_src, flow_dst=flow_dst,
        flow_pi=flow_pi, flow_pj=flow_pj, flow_size=flow_size,
        flow_valid=flow_valid, flow_counts=flow_counts,
        rates=rates, inv_rates=inv_rates, core_mask=core_mask, delta=delta,
        num_instances=B, num_coflows=Ms, num_ports=Ns, num_cores=Ks,
        num_flows=Fs, sharding=sharding,
    )


# ---------------------------------------------------------------------------
# Resident slot pool: one EnsembleBatch updated in place across epochs
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SlotPoolBatch:
    """A long-lived `EnsembleBatch` whose coflow axis is a slot pool.

    The streaming service's device-resident epoch state: **one** batch
    padded to the pool capacity ``slots`` on the coflow axis, with the
    flow axis managed as a flat arena of extents (one contiguous extent
    per occupied slot, capacity fixed at admission, grown in
    ``flow_quantum`` buckets).  `update_slots` / `free_slots` scatter
    residual demands, weights, releases and masks **in place** — frozen
    `EnsembleBatch` fields cannot be rebound, but their array *contents*
    are mutable, which is exactly the controlled exemption from the
    build-once contract that the ``slot.scatter`` counter tracks.

    Why shapes stay fixed: every epoch re-solve consumes the same
    (slots, flow_capacity, ports, cores)-shaped pytree, so the jitted
    allocation scan and circuit calendar compile once per arena capacity
    instead of once per epoch shape — the epoch compile cache is the
    small ladder of geometrically-grown flow capacities.

    Slot rows are **slot-indexed**, not dense-indexed; parity with the
    dense rebuild path holds because the batched allocation scan
    consumes only (port, size, validity) in permuted order — see
    `repro.streaming.service` for the dense<->slot order mapping.
    """

    batch: EnsembleBatch
    member: int  # row the primitives write (0; sharded tails stay masked)
    flow_quantum: int
    flow_start: np.ndarray  # (S,) i64 arena offset per slot, -1 = free
    flow_cap: np.ndarray  # (S,) i64 extent capacity per slot
    aggregate_rate: float
    delta: float

    @property
    def slots(self) -> int:
        return self.batch.pad_coflows

    @property
    def flow_capacity(self) -> int:
        return self.batch.pad_flows

    def occupied(self) -> np.ndarray:
        """(S,) bool — slots currently holding a coflow."""
        return self.flow_start >= 0


def build_slot_pool_batch(
    slots: int,
    num_ports: int,
    rates: np.ndarray,
    delta: float,
    *,
    flow_quantum: int = 64,
    mesh=None,
) -> SlotPoolBatch:
    """Construct an empty resident pool (counts as ONE build).

    The underlying `EnsembleBatch` is built from a zero-demand template
    instance with ``slots`` coflows — correct masks, port/core arrays and
    LP-array shapes — then every slot is marked free.  All later epoch
    state enters through `update_slots` / `free_slots`.
    """
    if slots <= 0:
        raise ValueError(f"slots must be positive, got {slots}")
    if flow_quantum <= 0:
        raise ValueError(f"flow_quantum must be positive, got {flow_quantum}")
    rates = np.asarray(rates, dtype=np.float64)
    template = CoflowInstance(
        demands=np.zeros((slots, num_ports, num_ports)),
        weights=np.ones(slots),  # placeholder: every slot starts masked
        releases=np.zeros(slots),
        rates=rates.copy(),
        delta=float(delta),
    )
    batch = build_ensemble_batch(
        [template], pad_flows=flow_quantum, mesh=mesh, with_lp_arrays=True
    )
    batch.coflow_mask[0, :] = False  # every slot starts free
    batch.weights[0, :] = 0.0
    batch.lp_weights[0, :] = 0.0
    batch.glb[0, :] = 0.0
    return SlotPoolBatch(
        batch=batch,
        member=0,
        flow_quantum=int(flow_quantum),
        flow_start=np.full(slots, -1, dtype=np.int64),
        flow_cap=np.zeros(slots, dtype=np.int64),
        aggregate_rate=float(rates.sum()),
        delta=float(delta),
    )


def _arena_gaps(pool: SlotPoolBatch) -> list[tuple[int, int]]:
    """Free arena intervals [start, stop) in address order."""
    occ = np.nonzero(pool.flow_start >= 0)[0]
    ivals = sorted(
        (int(pool.flow_start[s]), int(pool.flow_cap[s])) for s in occ
    )
    gaps, cursor = [], 0
    for start, cap in ivals:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = start + cap
    if cursor < pool.flow_capacity:
        gaps.append((cursor, pool.flow_capacity))
    return gaps


def _compact_arena(pool: SlotPoolBatch) -> None:
    """Left-pack every occupied extent (address order preserved).

    Flow arena addresses carry no meaning downstream — the allocation
    permutation orders flows by slot priority, ties by address, and a
    slot's flows stay contiguous in one extent — so compaction moves
    extents without touching any schedule output.
    """
    b, r = pool.batch, pool.member
    flow_arrays = (
        b.flow_coflow, b.flow_src, b.flow_dst, b.flow_pi, b.flow_pj,
        b.flow_size, b.flow_valid,
    )
    occ = np.nonzero(pool.flow_start >= 0)[0]
    cursor = 0
    for s in sorted(occ, key=lambda s: int(pool.flow_start[s])):
        start, cap = int(pool.flow_start[s]), int(pool.flow_cap[s])
        if start != cursor:  # moving left over a gap: no overlap hazard
            for arr in flow_arrays:
                arr[r, cursor:cursor + cap] = arr[r, start:start + cap]
                arr[r, max(start, cursor + cap):start + cap] = 0
        pool.flow_start[s] = cursor
        cursor += cap


def _grow_arena(pool: SlotPoolBatch, need: int) -> None:
    """Geometric flow-capacity growth: a new (bigger) padded flow shape.

    Doubling (rounded to the quantum) keeps the number of distinct arena
    shapes — and therefore jitted-stage recompiles — logarithmic in the
    total flow volume; ``slot.grow`` counts the ladder steps.
    """
    count("slot.grow")
    b = pool.batch
    new_cap = _round_up(max(need, 2 * pool.flow_capacity), pool.flow_quantum)

    def widen(arr: np.ndarray) -> np.ndarray:
        out = np.zeros(arr.shape[:1] + (new_cap,), dtype=arr.dtype)
        out[:, : arr.shape[1]] = arr
        return out

    pool.batch = dataclasses.replace(
        b,
        flow_coflow=widen(b.flow_coflow), flow_src=widen(b.flow_src),
        flow_dst=widen(b.flow_dst), flow_pi=widen(b.flow_pi),
        flow_pj=widen(b.flow_pj), flow_size=widen(b.flow_size),
        flow_valid=widen(b.flow_valid),
    )


def _reserve_extent(pool: SlotPoolBatch, slot: int, count: int) -> int:
    """Arena offset for `count` flows of `slot`: first-fit, then compact,
    then grow.  The extent capacity is fixed until the slot is freed (or
    outgrown — residuals only shrink in the streaming service, so a
    regrow mid-occupancy means the caller changed the coflow)."""
    cap = max(int(count), 1)
    if pool.flow_start[slot] >= 0:
        if pool.flow_cap[slot] >= cap:
            return int(pool.flow_start[slot])
        _release_extent(pool, slot)
    for lo, hi in _arena_gaps(pool):
        if hi - lo >= cap:
            pool.flow_start[slot] = lo
            pool.flow_cap[slot] = cap
            return lo
    used = int(pool.flow_cap[pool.flow_start >= 0].sum())
    if pool.flow_capacity - used >= cap:
        _compact_arena(pool)
    else:
        _compact_arena(pool)
        _grow_arena(pool, used + cap)
    lo = int(pool.flow_cap[pool.flow_start >= 0].sum())
    pool.flow_start[slot] = lo
    pool.flow_cap[slot] = cap
    return lo


def _release_extent(pool: SlotPoolBatch, slot: int) -> None:
    b, r = pool.batch, pool.member
    start, cap = int(pool.flow_start[slot]), int(pool.flow_cap[slot])
    if start >= 0:
        for arr in (
            b.flow_coflow, b.flow_src, b.flow_dst, b.flow_pi, b.flow_pj,
            b.flow_size, b.flow_valid,
        ):
            arr[r, start:start + cap] = 0
    pool.flow_start[slot] = -1
    pool.flow_cap[slot] = 0


def update_slots(
    pool: SlotPoolBatch,
    slots: np.ndarray,
    demands: np.ndarray,
    weights: np.ndarray,
    releases: np.ndarray,
) -> None:
    """Scatter per-slot coflow state into the resident batch, in place.

    ``demands`` is (n, N, N) residual demand per updated slot; weights
    and releases are (n,).  Recomputes each slot's canonical flow list
    (largest-first — `flows_of`), port statistics and global lower bound
    and writes them into the resident arrays: **no rebuild**, the one
    sanctioned mutation of a frozen `EnsembleBatch` (counted by
    ``slot.scatter``).  Slots whose flow count exceeds their extent
    re-reserve (first-fit / compact / geometric grow).
    """
    count("slot.scatter")
    slots = np.asarray(slots, dtype=np.int64)
    demands = np.asarray(demands, dtype=np.float64)
    b, r = pool.batch, pool.member
    for n, s in enumerate(slots):
        s = int(s)
        i_idx, j_idx, sizes = flows_of(demands[n], largest_first=True)
        F = int(i_idx.shape[0])
        start = _reserve_extent(pool, s, F)
        b = pool.batch  # _reserve_extent may have regrown the arena
        cap = int(pool.flow_cap[s])
        b.flow_coflow[r, start:start + F] = s
        b.flow_src[r, start:start + F] = i_idx
        b.flow_dst[r, start:start + F] = j_idx
        b.flow_pi[r, start:start + F] = i_idx
        b.flow_pj[r, start:start + F] = b.num_ports[r] + j_idx
        b.flow_size[r, start:start + F] = sizes
        b.flow_valid[r, start:start + F] = True
        b.flow_coflow[r, start + F:start + cap] = 0
        b.flow_src[r, start + F:start + cap] = 0
        b.flow_dst[r, start + F:start + cap] = 0
        b.flow_pi[r, start + F:start + cap] = 0
        b.flow_pj[r, start + F:start + cap] = 0
        b.flow_size[r, start + F:start + cap] = 0.0
        b.flow_valid[r, start + F:start + cap] = False
        b.flow_counts[r, s] = F
        rho, tau = port_stats(demands[n])
        b.lp_rho[r, s, :] = rho[0].astype(np.float32)
        b.lp_tau[r, s, :] = tau[0].astype(np.float32)
        b.glb[r, s] = pool.delta + rho[0].max() / pool.aggregate_rate
    b.weights[r, slots] = weights
    b.releases[r, slots] = releases
    b.lp_weights[r, slots] = np.asarray(weights, dtype=np.float32)
    b.lp_releases[r, slots] = np.asarray(releases, dtype=np.float32)
    b.coflow_mask[r, slots] = True


def set_slot_releases(
    pool: SlotPoolBatch, slots: np.ndarray, releases: np.ndarray
) -> None:
    """Cheap vectorized release refresh (the per-epoch ``max(arrival,
    now)`` clamp) — no flow or port-stat rescatter."""
    b, r = pool.batch, pool.member
    slots = np.asarray(slots, dtype=np.int64)
    b.releases[r, slots] = releases
    b.lp_releases[r, slots] = np.asarray(releases, dtype=np.float32)


def free_slots(pool: SlotPoolBatch, slots: np.ndarray) -> None:
    """Release slots back to the pool: masks cleared, extents zeroed.

    Zeroing (not just masking) is deliberate: slot reuse must never leak
    a previous tenant's demands into a later epoch, and the stale-leak
    tests diff the raw arrays to enforce it.
    """
    count("slot.scatter")
    slots = np.asarray(slots, dtype=np.int64)
    b, r = pool.batch, pool.member
    for s in slots:
        _release_extent(pool, int(s))
    b.flow_counts[r, slots] = 0
    b.coflow_mask[r, slots] = False
    b.weights[r, slots] = 0.0
    b.releases[r, slots] = 0.0
    b.glb[r, slots] = 0.0
    b.lp_weights[r, slots] = 0.0
    b.lp_releases[r, slots] = 0.0
    b.lp_rho[r, slots, :] = 0.0
    b.lp_tau[r, slots, :] = 0.0
