"""Tests for the unified `EnsembleBatch` pytree and the array pipeline.

Covers the one-build-per-ensemble contract (the stage-boundary
``ensemble.build`` counter), the canonical-flow-table permutation against the
host-side `flow_sequence` oracle, batched ordering parity for all three
order stages, the direct LP-batch -> ordering feed, the stage_cache
ensemble-fingerprint guard, and degenerate (M=0 / empty) ensembles
through bucketing, the LP phase and the full pipeline.
"""


import numpy as np
import pytest

from repro import pipeline
from repro.core import lp
from repro.core.coflow import CoflowInstance
from repro.core.ordering import fifo_order, wspt_order
from repro.pipeline import ensemble_batch as eb
from repro.pipeline.batch_alloc import allocate_batch_arrays, flow_sequence
from repro.pipeline.batch_circuit import schedule_batch, schedule_batch_arrays
from repro.trace import collect
from repro.traffic.instances import random_instance

GRID = [(5, 3, 2, 0), (8, 4, 3, 1), (10, 4, 4, 2), (6, 5, 2, 3)]


def _grid_instances():
    return [
        random_instance(num_coflows=M, num_ports=N, num_cores=K, seed=seed)
        for M, N, K, seed in GRID
    ]


@pytest.fixture(scope="module")
def grid_with_lp():
    instances = _grid_instances()
    return instances, [lp.solve_exact(inst) for inst in instances]


# ------------------------------------------------------------- build counter
def test_run_batch_builds_exactly_one_ensemble_batch(grid_with_lp):
    """All five schemes over one stage_cache pack the ensemble ONCE: the
    padded pytree is the single host->array boundary of the whole sweep
    (no per-stage re-padding), asserted via the build counter."""
    instances, sols = grid_with_lp
    cache: dict = {}
    with collect() as tally:
        for scheme in pipeline.PAPER_SCHEMES:
            pipeline.get_pipeline(scheme).run_batch(
                instances, lp_solutions=sols, stage_cache=cache,
                require_batch=True,
            )
        assert tally.counts["ensemble.build"] == 1
        # A rerun over the same cache (e.g. certify's reserving pass)
        # reuses the cached pytree: still zero additional builds.
        pipeline.get_pipeline("ours", discipline="reserving").run_batch(
            instances, lp_solutions=sols, stage_cache=cache
        )
        assert tally.counts["ensemble.build"] == 1


def test_run_batch_without_cache_builds_once(grid_with_lp):
    instances, sols = grid_with_lp
    with collect() as tally:
        pipeline.get_pipeline("ours").run_batch(instances, lp_solutions=sols)
    assert tally.counts["ensemble.build"] == 1


# ------------------------------------------------------ canonical flow table
def test_permute_flows_matches_flow_sequence_oracle():
    instances = _grid_instances()
    rng = np.random.default_rng(7)
    orders = [rng.permutation(inst.num_coflows) for inst in instances]
    batch = eb.build_ensemble_batch(instances)
    padded = batch.pad_orders(orders)
    perm = batch.permute_flows(padded)
    ends = batch.prefix_ends(padded)
    for b, (inst, order) in enumerate(zip(instances, orders)):
        mc, si, sj, sz, e = flow_sequence(inst, order)
        F, M = batch.num_flows[b], inst.num_coflows
        take = lambda a: np.take_along_axis(a, perm, axis=1)[b, :F]
        assert np.array_equal(take(batch.flow_coflow), mc)
        assert np.array_equal(take(batch.flow_src), si)
        assert np.array_equal(take(batch.flow_dst), sj)
        assert np.array_equal(take(batch.flow_size), sz)
        assert np.array_equal(ends[b, :M], e)


# ------------------------------------------------------------ order parity
def test_order_batch_parity_all_stages(grid_with_lp):
    instances, sols = grid_with_lp
    batch = eb.build_ensemble_batch(instances)
    comp = np.zeros(batch.weights.shape)
    for b, sol in enumerate(sols):
        comp[b, : instances[b].num_coflows] = sol.completion
    from repro.pipeline.stages import FifoOrder, LPOrder, WsptOrder

    got_lp = LPOrder().order_batch(batch, comp)
    got_w = WsptOrder().order_batch(batch)
    got_f = FifoOrder().order_batch(batch)
    for b, (inst, sol) in enumerate(zip(instances, sols)):
        M = inst.num_coflows
        assert np.array_equal(got_lp[b, :M], sol.order())
        assert np.array_equal(got_w[b, :M], wspt_order(inst))
        assert np.array_equal(got_f[b, :M], fifo_order(inst))
    assert LPOrder().order_batch(batch, None) is None  # must solve itself


def test_lp_solution_batch_feeds_ordering_directly():
    """EnsembleBatch.solve_lp -> LPSolutionBatch.order_batch with no
    unpadding in between, consistent with the per-instance solutions."""
    instances = _grid_instances()
    batch = eb.build_ensemble_batch(instances)
    lp_batch = batch.solve_lp(iters=150)
    orders = lp_batch.order_batch(batch.coflow_mask)
    sols = lp_batch.unpack([inst.num_coflows for inst in instances])
    for b, (inst, sol) in enumerate(zip(instances, sols)):
        M = inst.num_coflows
        assert np.array_equal(orders[b, :M], sol.order())
        # padded tail: the padded ids, stably in id order
        assert np.array_equal(
            np.sort(orders[b, M:]), np.arange(M, batch.pad_coflows)
        )


# --------------------------------------------------------- circuit arrays
@pytest.mark.parametrize("discipline", ["reserving", "greedy"])
def test_schedule_batch_arrays_matches_list_oracle(discipline, grid_with_lp):
    instances, sols = grid_with_lp
    orders = [sol.order() for sol in sols]
    batch = eb.build_ensemble_batch(instances)
    alloc_batch = allocate_batch_arrays(batch, batch.pad_orders(orders))
    allocs = alloc_batch.materialize(batch)
    ref = schedule_batch(instances, allocs, orders, discipline=discipline)
    got = schedule_batch_arrays(batch, alloc_batch, discipline=discipline)
    for (rs, rc), (gs, gc) in zip(ref, got):
        assert np.array_equal(rc, gc)
        for a, b in zip(rs, gs):
            assert np.array_equal(a.coflow, b.coflow)
            assert np.array_equal(a.establish, b.establish)
            assert np.array_equal(a.complete, b.complete)
            assert a.rate == b.rate and a.delta == b.delta


# ------------------------------------------------------- fingerprint guard
def test_stage_cache_rejects_cross_ensemble_reuse(grid_with_lp):
    instances, sols = grid_with_lp
    cache: dict = {}
    pipe = pipeline.get_pipeline("ours")
    pipe.run_batch(instances, lp_solutions=sols, stage_cache=cache)
    # Same ensemble again: fine (this is the sharing the cache exists for).
    pipe.run_batch(instances, lp_solutions=sols, stage_cache=cache)
    other = _grid_instances()
    other_sols = [lp.solve_exact(inst) for inst in other]
    with pytest.raises(ValueError, match="different ensembles"):
        pipe.run_batch(other, lp_solutions=other_sols, stage_cache=cache)
    # Same instances but different LP solutions: also a different ensemble.
    resolved = [lp.solve_exact(inst) for inst in instances]
    with pytest.raises(ValueError, match="different ensembles"):
        pipe.run_batch(instances, lp_solutions=resolved, stage_cache=cache)


def test_run_batch_mesh_must_match_cached_ensemble(grid_with_lp):
    """A cached EnsembleBatch carries its sharding; a later run_batch over
    the same cache with a different mesh must raise, not silently run
    with the cached (differently-sharded) batch."""
    from repro.launch.mesh import make_local_mesh

    instances, sols = grid_with_lp
    cache: dict = {}
    pipe = pipeline.get_pipeline("ours")
    pipe.run_batch(instances, lp_solutions=sols, stage_cache=cache)
    with pytest.raises(ValueError, match="mesh"):
        pipe.run_batch(
            instances, lp_solutions=sols, stage_cache=cache,
            mesh=make_local_mesh(),
        )
    # Consistent meshes across a cache are fine.
    mesh = make_local_mesh()
    cache2: dict = {}
    pipe.run_batch(
        instances, lp_solutions=sols, stage_cache=cache2, mesh=mesh
    )
    pipe.run_batch(
        instances, lp_solutions=sols, stage_cache=cache2, mesh=mesh
    )


def test_post_lp_build_skips_lp_arrays(grid_with_lp):
    """run_batch's internal build skips the heavy LP solver inputs (its
    LP is solved upstream); such a batch refuses to solve the LP."""
    instances, sols = grid_with_lp
    cache: dict = {}
    pipeline.get_pipeline("ours").run_batch(
        instances, lp_solutions=sols, stage_cache=cache
    )
    from repro.pipeline.pipeline import _ENSEMBLE_KEY

    cached = cache[_ENSEMBLE_KEY]
    assert not cached.has_lp_arrays
    assert cached.lp_rho.shape[1] == 0  # no (Bp, Mp, Pp) dead weight
    with pytest.raises(RuntimeError, match="with_lp_arrays"):
        cached.solve_lp(iters=10)
    # The default build keeps them (the LP phase's mode).
    assert eb.build_ensemble_batch(instances).has_lp_arrays


# ------------------------------------------------------ degenerate ensembles
def _empty_coflow_instance(num_ports=3):
    return CoflowInstance(
        demands=np.zeros((0, num_ports, num_ports)),
        weights=np.zeros(0),
        releases=np.zeros(0),
        rates=np.array([10.0, 20.0]),
        delta=1.0,
    )


def test_bucket_shape_empty_axis_regression():
    """An M=0 instance rounds to a 0-coflow bucket under a numeric
    quantum — it must NOT collide with the 'collapse to ensemble max'
    sentinel and silently inherit the ensemble maximum."""
    from repro.experiments import build_buckets

    ens = [
        _empty_coflow_instance(),
        random_instance(num_coflows=6, num_ports=3, seed=0),
    ]
    buckets = build_buckets(ens, m_quantum=8, p_quantum=8)
    by_m = {b.num_coflows: b for b in buckets}
    assert set(by_m) == {0, 8}
    assert by_m[0].indices == (0,)
    assert by_m[8].indices == (1,)
    # Collapse mode still pads everyone to the ensemble maxima.
    (one,) = build_buckets(ens, m_quantum=None, p_quantum=None)
    assert one.num_coflows == 6 and len(one) == 2


def test_degenerate_ensembles_end_to_end():
    from repro.experiments import solve_ensemble_lp, sweep

    # Entirely empty ensemble.
    assert solve_ensemble_lp([]) == []
    res = sweep([], lp_iters=50)
    assert len(res) == 0 and res.rows() == []
    # Ensemble containing an M=0 member.
    ens = [
        _empty_coflow_instance(),
        random_instance(num_coflows=6, num_ports=3, seed=0),
    ]
    sols = solve_ensemble_lp(ens, iters=50)
    assert sols[0].completion.shape == (0,)
    assert sols[0].objective == 0.0
    assert sols[1].completion.shape == (6,)
    results = pipeline.get_pipeline("ours").run_batch(
        ens, lp_solutions=sols
    )
    assert results[0].ccts.shape == (0,)
    assert results[0].total_weighted_cct == 0.0
    assert results[1].total_weighted_cct > 0


# ----------------------------------------------------------- pytree basics
def test_ensemble_batch_is_a_pytree():
    import jax

    instances = _grid_instances()[:2]
    batch = eb.build_ensemble_batch(instances)
    leaves = jax.tree.leaves(batch)
    assert leaves and all(hasattr(x, "shape") for x in leaves)
    # tree_map preserves the static metadata (instance sizes, sharding).
    mapped = jax.tree.map(lambda x: x, batch)
    assert mapped.num_coflows == batch.num_coflows
    assert mapped.num_instances == batch.num_instances


def test_allocation_batch_prefix_lb_matches_oracle(grid_with_lp):
    from repro.core.allocation import allocate

    instances, sols = grid_with_lp
    orders = [sol.order() for sol in sols]
    batch = eb.build_ensemble_batch(instances)
    ab = allocate_batch_arrays(batch, batch.pad_orders(orders))
    for b, (inst, order) in enumerate(zip(instances, orders)):
        ref = allocate(inst, order)
        M = inst.num_coflows
        assert np.array_equal(ab.prefix_lb[b, :M], ref.prefix_lb)
        assert np.array_equal(
            ab.core[b, : batch.num_flows[b]], ref.core
        )
