"""Batched candidate-search refinement tests (ISSUE acceptance criteria).

The refinement stage turns order search into extra `EnsembleBatch` member
rows: one batched alloc+circuit pass scores all instances × candidates
per round.  Because the batched stages are bit-identical to the
per-instance NumPy oracles and the selection rule is shared
(`select_candidate`), the batched search must pick **identical winners,
swap for swap** against the sequential oracles:

  * member expansion — `expand_members` / `expansion_maps` gather every
    array field candidate-major, repeat the static meta, keep the padded
    tail masked, and never re-pack the ensemble (BUILD_COUNT);
  * fuzz parity — mixed shapes, K ∈ {1..4}, both disciplines: refined
    orders, objectives and evaluation counts bit-identical to
    `refine_sequential` over `evaluate_order`;
  * adjacent-neighborhood oracle — a one-round full adjacent sweep
    equals `refine_round_best`'s winner exactly;
  * guarantee — refined schedules never get worse, and OURS+LS stays
    within the paper's (8K+1) bound against the exact LP;
  * pipeline + cache keying — OURS+LS through `run_batch` (loop-backend
    fallback parity, ``require_batch`` semantics) and `sweep(refine=...)`
    cache cells keyed by the refine config.
"""

import dataclasses

import numpy as np
import pytest

from repro import pipeline
from repro.core import lp
from repro.core.localsearch import (
    TOL,
    evaluate_order,
    refine_round_best,
    select_candidate,
)
from repro.core.ordering import wspt_order
from repro.pipeline import ensemble_batch as eb
from repro.pipeline.refine import (
    RefineSpec,
    as_refine_spec,
    generate_candidates,
    refine_batch_arrays,
    refine_key,
    refine_sequential,
)
from repro.trace import collect
from repro.traffic.instances import random_instance

# Mixed shapes spanning K=1..4, with and without releases.
MIXED = [
    (5, 3, 1, 0),
    (9, 4, 2, 1),
    (12, 5, 3, 2),
    (7, 4, 4, 3),
    (10, 6, 2, 4),
    (6, 3, 3, 5),
]

DISCIPLINES = ("greedy", "reserving")


def _mixed_instances():
    return [
        random_instance(
            num_coflows=M, num_ports=N, num_cores=K, seed=seed,
            release_span=12.0 * (seed % 2),
        )
        for M, N, K, seed in MIXED
    ]


# --------------------------------------------------------- selection rule
class TestSelectCandidate:
    def test_keeps_incumbent_without_real_improvement(self):
        assert select_candidate(np.array([10.0, 10.0 - TOL / 2])) == 0
        assert select_candidate(np.array([10.0, 10.0, 11.0])) == 0

    def test_accepts_strict_improvement(self):
        assert select_candidate(np.array([10.0, 9.0])) == 1

    def test_lowest_index_wins_ties(self):
        # Slots 2 and 3 tie at the minimum (within TOL): slot 2 wins.
        objs = np.array([10.0, 9.5, 9.0, 9.0 + TOL / 2, 9.2])
        assert select_candidate(objs) == 2


# -------------------------------------------------------- member expansion
class TestExpandMembers:
    def test_expansion_maps(self):
        inst_of, cand_of = eb.expansion_maps(3, 2)
        assert inst_of.tolist() == [0, 0, 1, 1, 2, 2]
        assert cand_of.tolist() == [0, 1, 0, 1, 0, 1]

    def test_expand_gathers_rows_candidate_major(self):
        instances = _mixed_instances()[:3]
        batch = eb.build_ensemble_batch(instances)
        k = 3
        exp, inst_of, cand_of = batch.expand_members(k)
        assert exp.num_instances == k * batch.num_instances
        assert exp.num_coflows == tuple(
            np.repeat(batch.num_coflows, k).tolist()
        )
        for f in dataclasses.fields(eb.EnsembleBatch):
            if f.metadata.get("static"):
                continue
            src = np.asarray(getattr(batch, f.name))
            got = np.asarray(getattr(exp, f.name))
            for row, (b, c) in enumerate(zip(inst_of, cand_of)):
                assert np.array_equal(got[row], src[b]), (f.name, b, c)

    def test_expand_does_not_rebuild(self):
        batch = eb.build_ensemble_batch(_mixed_instances()[:2])
        with collect() as tally:
            batch.expand_members(4)
        assert tally.counts.get("ensemble.build", 0) == 0

    def test_expanded_pad_tail_masked(self):
        batch = eb.build_ensemble_batch(_mixed_instances()[:3])
        exp, _, _ = batch.expand_members(2)
        B = exp.num_instances
        assert not exp.coflow_mask[B:].any()
        assert not exp.flow_valid[B:].any()

    def test_expand_reps_one_is_identity(self):
        batch = eb.build_ensemble_batch(_mixed_instances()[:2])
        exp, inst_of, cand_of = batch.expand_members(1)
        assert inst_of.tolist() == [0, 1] and cand_of.tolist() == [0, 0]
        B = batch.num_instances
        for f in dataclasses.fields(eb.EnsembleBatch):
            if f.metadata.get("static"):
                continue
            a = np.asarray(getattr(batch, f.name))[:B]
            b = np.asarray(getattr(exp, f.name))[:B]
            assert np.array_equal(a, b), f.name


# ------------------------------------------------------------- spec/config
class TestRefineSpecCoercion:
    def test_true_is_default_spec(self):
        assert as_refine_spec(True) == RefineSpec()

    def test_dict_round_trip(self):
        spec = as_refine_spec({"rounds": 3, "candidates": 4})
        assert (spec.rounds, spec.candidates) == (3, 4)

    @pytest.mark.parametrize(
        "bad",
        [
            {"rounds": 0},
            {"candidates": 0},
            {"elites": 1},
            {"generators": ()},
            {"generators": ("adjacent", "nope")},
        ],
    )
    def test_invalid_specs_rejected(self, bad):
        with pytest.raises(ValueError):
            as_refine_spec(bad)

    def test_non_spec_rejected(self):
        with pytest.raises(TypeError):
            as_refine_spec(7)

    def test_refine_key_canonical(self):
        k1 = refine_key(RefineSpec())
        k2 = refine_key(RefineSpec())
        assert k1 == k2 and isinstance(k1, tuple)
        assert refine_key(RefineSpec(rounds=5)) != k1

    def test_generate_candidates_deterministic(self):
        order = np.arange(8, dtype=np.int64)[::-1].copy()
        spec = RefineSpec(candidates=6)
        a, ca = generate_candidates(order, spec, 1, 2, [])
        b, cb = generate_candidates(order, spec, 1, 2, [])
        assert ca == cb
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
        for c in a:  # every candidate is a permutation of the incumbent
            assert np.array_equal(np.sort(c), np.arange(8))


# ------------------------------------------------------- batched vs oracle
class TestBatchedSequentialParity:
    @pytest.mark.parametrize("discipline", DISCIPLINES)
    def test_fuzz_winners_bit_identical(self, discipline):
        instances = _mixed_instances()
        orders = [wspt_order(inst) for inst in instances]
        batch = eb.build_ensemble_batch(instances)
        spec = RefineSpec(rounds=3, candidates=5, seed=11)
        out = refine_batch_arrays(
            batch, batch.pad_orders(orders), spec, discipline=discipline
        )
        assert out.batched
        seq_evals = 0
        for b, inst in enumerate(instances):
            M = inst.num_coflows
            o2, cur, base, _r, e = refine_sequential(
                orders[b], spec,
                lambda o, inst=inst: evaluate_order(inst, o, discipline),
            )
            seq_evals += e
            assert np.array_equal(out.orders[b, :M], o2), b
            assert out.objective[b] == cur, b
            assert out.base_objective[b] == base, b
        assert out.evaluations == seq_evals

    def test_never_worse_and_improvement_flag(self):
        instances = _mixed_instances()
        orders = [wspt_order(inst) for inst in instances]
        batch = eb.build_ensemble_batch(instances)
        out = refine_batch_arrays(batch, batch.pad_orders(orders), True)
        assert (out.objective <= out.base_objective + TOL).all()
        assert np.array_equal(
            out.improved, out.objective < out.base_objective
        )

    def test_adjacent_round_matches_refine_round_best(self):
        # One round, candidates = M, adjacent-only: the batched search
        # scores exactly the full adjacent-swap neighborhood — winner must
        # be bit-identical to the per-instance oracle's.
        for M, N, K, seed in MIXED[:4]:
            inst = random_instance(
                num_coflows=M, num_ports=N, num_cores=K, seed=seed
            )
            order = wspt_order(inst)
            spec = RefineSpec(
                rounds=1, candidates=M, generators=("adjacent",)
            )
            batch = eb.build_ensemble_batch([inst])
            out = refine_batch_arrays(
                batch, batch.pad_orders([order]), spec
            )
            w, worder, objs = refine_round_best(inst, order)
            assert np.array_equal(out.orders[0, :M], worder), seed
            assert out.objective[0] == objs[w], seed
            assert out.base_objective[0] == objs[0], seed

    def test_empty_ensemble(self):
        batch = eb.build_ensemble_batch([])
        out = refine_batch_arrays(
            batch, np.zeros((0, 0), dtype=np.int64), True
        )
        assert out.objective.size == 0 and out.evaluations == 0


# --------------------------------------------------------------- pipeline
class TestPipelineRefine:
    @pytest.fixture(scope="class")
    def mixed_with_lp(self):
        instances = _mixed_instances()
        return instances, [lp.solve_exact(inst) for inst in instances]

    def test_ours_ls_registered_with_refine(self):
        assert "ours_ls" in pipeline.list_schemes()
        spec = pipeline.get_scheme("ours_ls")
        assert isinstance(spec.refine, RefineSpec)

    def test_refined_never_worse_than_ours(self, mixed_with_lp):
        instances, sols = mixed_with_lp
        cache: dict = {}
        base = pipeline.get_pipeline("ours").run_batch(
            instances, lp_solutions=sols, stage_cache=cache,
            require_batch=True,
        )
        refined = pipeline.get_pipeline("ours_ls").run_batch(
            instances, lp_solutions=sols, stage_cache=cache,
            require_batch=True,
        )
        for a, b in zip(refined, base):
            assert a.total_weighted_cct <= b.total_weighted_cct + TOL

    def test_refine_false_disables_spec_refine(self, mixed_with_lp):
        instances, sols = mixed_with_lp
        off = pipeline.get_pipeline("ours_ls").run_batch(
            instances, lp_solutions=sols, refine=False, require_batch=True
        )
        base = pipeline.get_pipeline("ours").run_batch(
            instances, lp_solutions=sols, require_batch=True
        )
        for a, b in zip(off, base):
            assert np.array_equal(a.ccts, b.ccts)

    def test_loop_backend_sequential_fallback_matches(self, mixed_with_lp):
        # The loop circuit backend forces refine_sequential inside
        # run_batch; its results must be bit-identical to the batched
        # search, and require_batch must flag the fallback.
        instances, sols = mixed_with_lp
        loop_pipe = pipeline.get_pipeline("ours_ls", circuit_backend="loop")
        got = loop_pipe.run_batch(instances, lp_solutions=sols)
        ref = pipeline.get_pipeline("ours_ls").run_batch(
            instances, lp_solutions=sols, require_batch=True
        )
        for a, b in zip(got, ref):
            assert np.array_equal(a.order, b.order)
            assert np.array_equal(a.ccts, b.ccts)
        with pytest.raises(RuntimeError, match="sequential refinement"):
            loop_pipe.run_batch(
                instances, lp_solutions=sols, require_batch=True
            )

    def test_stage_cache_shares_orders_not_refinement(self, mixed_with_lp):
        instances, sols = mixed_with_lp
        cache: dict = {}
        pipeline.get_pipeline("ours").run_batch(
            instances, lp_solutions=sols, stage_cache=cache
        )
        pipeline.get_pipeline("ours_ls").run_batch(
            instances, lp_solutions=sols, stage_cache=cache
        )
        order_keys = [
            k for k in cache
            if isinstance(k, tuple) and k and k[0] == "order"
        ]
        refine_keys = [
            k for k in cache
            if isinstance(k, tuple) and k and k[0] == "refine"
        ]
        # One shared ordering pass; refinement cached under its own key.
        assert len(order_keys) == 1
        assert len(refine_keys) == 1

    def test_bound_preserved_within_8k_plus_1(self):
        # Refinement only ever accepts improving orders, so OURS+LS keeps
        # the paper's guarantee: total weighted CCT <= (8K+1) * exact LP.
        for M, N, K, seed in MIXED[:4]:
            inst = random_instance(
                num_coflows=M, num_ports=N, num_cores=K, seed=seed,
                release_span=12.0 * (seed % 2),
            )
            sol = lp.solve_exact(inst)
            res = pipeline.get_pipeline("ours_ls").run_batch(
                [inst], lp_solutions=[sol], require_batch=True
            )[0]
            bound = 8 * K + (1 if inst.releases.max() > 0 else 0)
            assert res.total_weighted_cct <= bound * sol.objective + 1e-6


# -------------------------------------------------------------- sweep keys
class TestSweepRefineKeying:
    def _ens(self):
        return [
            random_instance(
                num_coflows=8 + s, num_ports=4, num_cores=2, seed=70 + s
            )
            for s in range(2)
        ]

    _KW = dict(schemes=("ours",), lp_method="exact", validate=False)

    def test_refine_config_joins_cell_key(self, tmp_path):
        from repro.experiments import sweep

        ens = self._ens()
        sweep(ens, cache=str(tmp_path), **self._KW)
        # Refined cells are distinct from unrefined ones...
        r1 = sweep(
            ens, cache=str(tmp_path), refine={"rounds": 1}, **self._KW
        )
        assert r1.cache_stats["hits"] == 0
        # ...and from differently-configured refinements.
        r2 = sweep(
            ens, cache=str(tmp_path), refine={"rounds": 2}, **self._KW
        )
        assert r2.cache_stats["hits"] == 0
        # Identical refine config replays from cache alone.
        r3 = sweep(
            ens, cache=str(tmp_path), refine={"rounds": 2}, **self._KW
        )
        assert r3.cache_stats["computed"] == 0

    def test_ours_ls_cells_distinct_from_ours(self, tmp_path):
        from repro.experiments import sweep

        ens = self._ens()
        sweep(ens, cache=str(tmp_path), **self._KW)
        res = sweep(
            ens, cache=str(tmp_path),
            **{**self._KW, "schemes": ("ours", "ours_ls")},
        )
        # The ours column replays; the spec-pinned-refine scheme computes.
        assert res.cache_stats["hits"] == 2
        assert res.cache_stats["computed"] == 2
        rows = res.rows()
        for row in rows:
            if row["scheme"] == "ours_ls":
                base = [
                    r["total_weighted_cct"] for r in rows
                    if r["scheme"] == "ours"
                    and r["instance"] == row["instance"]
                ]
                assert row["total_weighted_cct"] <= base[0] + TOL


# -------------------------------------------------- adaptive stale budgets
class TestStopAfterStale:
    """`stop_after_stale=n` freezes an instance only after n CONSECUTIVE
    non-improving rounds (counter reset on improvement); None keeps the
    historical freeze-on-first-stale rule.  Both refine paths must apply
    the same freeze rule, and frozen instances must stop spending
    evaluations."""

    def _setup(self):
        instances = _mixed_instances()[:4]
        orders = [wspt_order(inst) for inst in instances]
        batch = eb.build_ensemble_batch(instances)
        return instances, orders, batch

    def test_validation(self):
        with pytest.raises(ValueError):
            as_refine_spec(RefineSpec(stop_after_stale=0))
        with pytest.raises(ValueError):
            as_refine_spec({"stop_after_stale": -1})
        assert as_refine_spec(
            RefineSpec(stop_after_stale=3)
        ).stop_after_stale == 3
        assert as_refine_spec(True).stop_after_stale is None

    def test_refine_key_includes_stale_budget(self):
        assert refine_key(RefineSpec(stop_after_stale=2)) != refine_key(
            RefineSpec()
        )

    @pytest.mark.parametrize("stale", [1, 2, 3, None])
    def test_batched_matches_sequential_oracle(self, stale):
        instances, orders, batch = self._setup()
        spec = RefineSpec(
            rounds=6, candidates=5, seed=17, stop_after_stale=stale
        )
        out = refine_batch_arrays(batch, batch.pad_orders(orders), spec)
        seq_evals = 0
        for b, inst in enumerate(instances):
            M = inst.num_coflows
            o2, cur, base, _r, e = refine_sequential(
                orders[b], spec,
                lambda o, inst=inst: evaluate_order(inst, o),
            )
            seq_evals += e
            assert np.array_equal(out.orders[b, :M], o2), (stale, b)
            assert out.objective[b] == cur, (stale, b)
            assert out.base_objective[b] == base, (stale, b)
        assert out.evaluations == seq_evals

    def test_none_matches_historical_stale_one(self):
        instances, orders, batch = self._setup()
        kw = dict(rounds=5, candidates=4, seed=3)
        a = refine_batch_arrays(
            batch, batch.pad_orders(orders), RefineSpec(**kw)
        )
        b = refine_batch_arrays(
            batch, batch.pad_orders(orders),
            RefineSpec(stop_after_stale=1, **kw),
        )
        assert np.array_equal(a.orders, b.orders)
        assert np.array_equal(a.objective, b.objective)
        assert a.evaluations == b.evaluations

    def test_freeze_shrinks_evaluation_budget(self):
        instances, orders, batch = self._setup()
        B = len(instances)
        kw = dict(rounds=6, candidates=5, seed=17)
        full_budget = 6 * 5 * B
        evals = {}
        for stale in (1, 3):
            out = refine_batch_arrays(
                batch, batch.pad_orders(orders),
                RefineSpec(stop_after_stale=stale, **kw),
            )
            evals[stale] = out.evaluations
        # Freezing stuck instances spends less than the full budget, and
        # a tighter stale limit never spends more than a looser one.
        assert evals[1] < full_budget
        assert evals[1] <= evals[3] <= full_budget

    def test_stale_counter_resets_on_improvement(self):
        # An instance that improves, stalls once, then improves again
        # must not freeze under stop_after_stale=2 — equivalently, the
        # n=2 search can only refine further than n=1, never less.
        instances, orders, batch = self._setup()
        kw = dict(rounds=8, candidates=4, seed=5)
        tight = refine_batch_arrays(
            batch, batch.pad_orders(orders),
            RefineSpec(stop_after_stale=1, **kw),
        )
        loose = refine_batch_arrays(
            batch, batch.pad_orders(orders),
            RefineSpec(stop_after_stale=2, **kw),
        )
        assert (loose.objective <= tight.objective + TOL).all()
        assert loose.evaluations >= tight.evaluations
