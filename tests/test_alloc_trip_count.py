"""The allocation scan steps only as far as the longest member's valid
flows.

`_scan_all` runs a loop whose trip count is a runtime scalar, the largest
valid-flow count over the ensemble's members (valid flows lead every row
after `EnsembleBatch.permute_flows`).  Contracts under test:

  * **oracle parity past the trip count** — on a resident slot pool whose
    flow arena holds free extents and finished flows inside live extents,
    the allocation, circuit schedules and CCTs are bit-identical to the
    NumPy `allocate` / `schedule_core` oracles, and the resident stream's
    epochs to the rebuild driver's;
  * **counters** — ``alloc.steps`` is the largest valid count and
    ``alloc.step_slots`` the padded flow length a full scan would step;
  * **unequal members** — an expanded ensemble (refinement's candidate
    rows) whose members have different valid counts matches the oracle
    row by row;
  * **nothing valid** — an all-invalid ensemble with a nonzero flow axis
    runs zero steps and places nothing;
  * **fills** — stepping the whole padded axis gives the same outputs as
    stepping only the valid prefix.
"""

import numpy as np
import pytest

import jax

from repro.core.allocation import allocate
from repro.core.coflow import CoflowInstance, flows_of
from repro.core.scheduler import _schedule_all_cores
from repro.core.validate import ccts_from_schedules
from repro.experiments import stream
from repro.pipeline import batch_alloc
from repro.pipeline import ensemble_batch as eb
from repro.pipeline.batch_alloc import NO_CORE, allocate_batch_arrays
from repro.pipeline.batch_circuit import schedule_batch_arrays
from repro.pipeline.exact64 import to_bits
from repro.trace import collect
from repro.traffic import poisson_arrivals, with_releases
from repro.traffic.instances import random_instance

RATES = np.array([10.0, 20.0, 15.0])
DELTA = 1.5
N = 5


def _inst(M, seed, release_span=0.0):
    return random_instance(
        num_coflows=M, num_ports=N, num_cores=RATES.shape[0], seed=seed,
        release_span=release_span,
    )


def _gapped_pool():
    """A pool whose arena has free extents, a shrunk extent with finished
    flows in its tail, and an empty tail after a geometric growth."""
    pool = eb.build_slot_pool_batch(8, N, RATES, DELTA, flow_quantum=8)
    first = _inst(5, seed=11, release_span=20.0)
    eb.update_slots(
        pool, np.arange(5), first.demands, first.weights, first.releases
    )
    eb.free_slots(pool, np.array([1, 3]))  # free extents between tenants
    resid = first.demands[2:3].copy()  # half of slot 2's flows finish
    i_idx, j_idx, _ = flows_of(resid[0], largest_first=True)
    resid[0, i_idx[::2], j_idx[::2]] = 0.0
    eb.update_slots(
        pool, np.array([2]), resid, first.weights[2:3], first.releases[2:3]
    )
    late = _inst(2, seed=12, release_span=20.0)
    eb.update_slots(
        pool, np.array([6, 7]), late.demands, late.weights, late.releases
    )
    return pool


def _slot_instance(pool):
    """The pool's slot space as one plain instance (free slots empty)."""
    b = pool.batch
    S = b.pad_coflows
    demands = np.zeros((S, N, N))
    for s in np.nonzero(pool.flow_start >= 0)[0]:
        sl = slice(int(pool.flow_start[s]),
                   int(pool.flow_start[s]) + int(b.flow_counts[0, s]))
        demands[s, b.flow_src[0, sl], b.flow_dst[0, sl]] = b.flow_size[0, sl]
    # Weights steer neither allocation nor calendar; free slots get 1.
    weights = np.where(b.coflow_mask[0], b.weights[0], 1.0)
    return CoflowInstance(
        demands=demands, weights=weights,
        releases=np.asarray(b.releases[0]).copy(), rates=RATES.copy(),
        delta=DELTA,
    )


def _slot_order(pool, seed):
    """Live slots in a random priority order, free slots at the tail (as
    the resident epoch driver lays them out)."""
    live = np.nonzero(pool.batch.coflow_mask[0])[0]
    free = np.nonzero(~pool.batch.coflow_mask[0])[0]
    rng = np.random.default_rng(seed)
    return np.concatenate([rng.permutation(live), free]).astype(np.int64)


@pytest.mark.parametrize("discipline", ["greedy", "reserving"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gapped_pool_matches_numpy_oracles(discipline, seed):
    pool = _gapped_pool()
    b = pool.batch
    Fp = b.flow_size.shape[1]
    n_valid = int(b.flow_valid[0].sum())
    assert 0 < n_valid < Fp  # the arena really has empty slots to skip

    order = _slot_order(pool, seed)
    with collect() as tally:
        alloc = allocate_batch_arrays(b, order[None, :])
    assert tally.counts["alloc.steps"] == n_valid
    assert tally.counts["alloc.step_slots"] == Fp

    inst = _slot_instance(pool)
    ref = allocate(inst, order)
    F = ref.num_flows()
    assert F == n_valid
    assert alloc.valid[0, :F].all() and not alloc.valid[0, F:].any()
    for name in ("coflow", "src", "dst", "size", "core"):
        assert np.array_equal(getattr(alloc, name)[0, :F],
                              getattr(ref, name)), name
    assert (alloc.core[0, F:] == NO_CORE).all()
    K = RATES.shape[0]
    assert np.array_equal(alloc.rho_ports[0, :K, :2 * N], ref.rho_ports)
    assert np.array_equal(alloc.tau_ports[0, :K, :2 * N], ref.tau_ports)
    assert np.array_equal(alloc.prefix_lb[0], ref.prefix_lb)

    schedules, ccts = schedule_batch_arrays(b, alloc, discipline)[0]
    want = _schedule_all_cores(inst, ref, order, discipline=discipline)
    assert np.array_equal(
        ccts, ccts_from_schedules(inst.num_coflows, want)
    )
    for got, w in zip(schedules, want):
        assert np.array_equal(got.coflow, w.coflow)
        assert np.array_equal(got.establish, w.establish)
        assert np.array_equal(got.complete, w.complete)


def test_resident_stream_steps_only_valid_flows_and_matches_rebuild():
    """Preemption and a small pool leave finished flows and free extents
    in the resident arena; its epochs step exactly the rebuild driver's
    valid flows, over a longer padded axis, with bit-identical results."""
    inst = random_instance(num_coflows=12, num_ports=4, num_cores=3, seed=5)
    inst = with_releases(
        inst, poisson_arrivals(12, mean_interarrival_ms=4.0, seed=5)
    )
    kw = dict(
        lp_method="batch", lp_iters=300, n_batches=5, pool_size=5,
        preempt=True, warm_start=False, validate=True,
    )
    reb = stream(inst, epoch_mode="rebuild", **kw)
    res = stream(inst, epoch_mode="resident", **kw)
    assert np.array_equal(res.admission, reb.admission)
    assert np.array_equal(res.finish, reb.finish)
    assert len(res.epochs) == len(reb.epochs) > 1
    skipped = 0
    for er, eb_ in zip(res.epochs, reb.epochs):
        assert np.array_equal(er.order, eb_.order)
        assert np.array_equal(er.ccts, eb_.ccts)
        steps = er.counts["alloc.steps"]
        assert steps == eb_.counts["alloc.steps"]
        assert steps == eb_.allocation.num_flows()
        assert steps <= er.counts["alloc.step_slots"]
        skipped += er.counts["alloc.step_slots"] - steps
    assert skipped > 0


def test_unequal_members_match_oracle_and_count_longest():
    instances = [_inst(M, seed=s) for M, s in ((3, 21), (7, 22), (5, 23))]
    batch = eb.build_ensemble_batch(instances, with_lp_arrays=False)
    expanded, instance_of, _ = batch.expand_members(2)
    rng = np.random.default_rng(4)
    orders = [rng.permutation(instances[i].num_coflows) for i in instance_of]
    counts = [int((instances[i].demands > 0).sum()) for i in instance_of]
    assert len(set(counts)) > 1
    with collect() as tally:
        alloc = allocate_batch_arrays(expanded, expanded.pad_orders(orders))
    assert tally.counts["alloc.steps"] == max(counts)
    assert tally.counts["alloc.step_slots"] == expanded.flow_size.shape[1]
    for r, (i, order) in enumerate(zip(instance_of, orders)):
        ref = allocate(instances[i], order)
        F = ref.num_flows()
        assert F == counts[r]
        assert np.array_equal(alloc.core[r, :F], ref.core)
        assert (alloc.core[r, F:] == NO_CORE).all()
        M = instances[i].num_coflows
        assert np.array_equal(alloc.prefix_lb[r, :M], ref.prefix_lb)


@pytest.mark.parametrize("source", ["freed_pool", "zero_demands"])
def test_all_invalid_ensemble_runs_zero_steps(source):
    if source == "freed_pool":
        pool = _gapped_pool()
        eb.free_slots(pool, np.nonzero(pool.flow_start >= 0)[0])
        batch = pool.batch
    else:
        empty = CoflowInstance(
            demands=np.zeros((3, N, N)), weights=np.ones(3),
            releases=np.zeros(3), rates=RATES.copy(), delta=DELTA,
        )
        batch = eb.build_ensemble_batch([empty, empty], pad_flows=8,
                                        with_lp_arrays=False)
    Bp, Fp = batch.flow_size.shape
    assert Fp > 0 and not batch.flow_valid.any()
    orders = np.tile(np.arange(batch.pad_coflows), (Bp, 1))
    with collect() as tally:
        alloc = allocate_batch_arrays(batch, orders)
    assert tally.counts["alloc.steps"] == 0
    assert tally.counts["alloc.step_slots"] == Fp
    assert (alloc.core == NO_CORE).all()
    assert not alloc.rho_ports.any() and not alloc.tau_ports.any()
    assert not alloc.prefix_lb.any()


def test_stepping_the_whole_axis_changes_no_output():
    """The fills past the trip count are what the no-op steps return."""
    pool = _gapped_pool()
    b = pool.batch
    order = _slot_order(pool, 3)
    perm = b.permute_flows(order[None, :])
    take = lambda a: np.take_along_axis(a, perm, axis=1)  # noqa: E731
    valid = take(b.flow_valid)
    lb0 = np.where(b.core_mask, 0.0, eb.PAD_LB)
    zeros = np.zeros(b.lp_rho.shape[:1] + (b.pad_cores, b.pad_flat_ports),
                     dtype=np.int64)
    with jax.enable_x64():
        args = (
            take(b.flow_pi).astype(np.int32), take(b.flow_pj).astype(np.int32),
            to_bits(take(b.flow_size)), valid, to_bits(b.inv_rates),
            to_bits(b.delta), to_bits(lb0), b.core_mask, zeros, zeros,
        )
        n_valid, Fp = int(valid.sum()), valid.shape[1]
        short = [np.asarray(x) for x in
                 batch_alloc._scan_all(np.int32(n_valid), *args)]
        full = [np.asarray(x) for x in
                batch_alloc._scan_all(np.int32(Fp), *args)]
    for s, f in zip(short, full):
        assert np.array_equal(s, f)
