"""Bit-parity fuzz tests for the ensemble-batched circuit stage.

`repro.pipeline.batch_circuit.schedule_batch` must reproduce the NumPy
event loop (`schedule_core` via `_schedule_all_cores`) **bit for bit** on
both disciplines: establishment/completion times, schedule array layouts,
and the derived CCT vectors — across mixed shapes, zero and arbitrary
release times, zero-duration flows, empty cores and single-flow cores.
On top sits a `run_batch` end-to-end CCT parity grid over every
registered scheme (batched LP-ordered pipelines included).
"""

import dataclasses

import numpy as np
import pytest

from repro import pipeline
from repro.core import lp
from repro.core.allocation import Allocation, allocate
from repro.core.circuit import NOT_SCHEDULED
from repro.core.ordering import wspt_order
from repro.core.scheduler import _schedule_all_cores
from repro.core.validate import ccts_from_schedules, validate_schedule
from repro.pipeline import batch_circuit as bc
from repro.pipeline.batch_circuit import event_bound, schedule_batch
from repro.traffic.instances import random_instance

DISCIPLINES = ["reserving", "greedy"]

_SCHED_FIELDS = ("coflow", "src", "dst", "size", "establish", "complete")


def _assert_schedules_identical(got, ref, ctx):
    assert len(got) == len(ref), ctx
    for k, (a, b) in enumerate(zip(got, ref)):
        for f in _SCHED_FIELDS:
            x, y = getattr(a, f), getattr(b, f)
            assert x.dtype == y.dtype and x.shape == y.shape, (ctx, k, f)
            assert np.array_equal(x, y), (ctx, k, f)
        assert a.rate == b.rate and a.delta == b.delta, (ctx, k)


def _use_engine(monkeypatch, engine):
    """Run the calendar executor ``engine`` whatever the platform."""
    monkeypatch.setattr(bc, "_engine", lambda: engine)


def _batch_vs_loop(instances, discipline):
    orders = [wspt_order(inst) for inst in instances]
    allocs = [allocate(inst, o) for inst, o in zip(instances, orders)]
    got = schedule_batch(instances, allocs, orders, discipline=discipline)
    assert len(got) == len(instances)
    for inst, alloc, order, (schedules, ccts) in zip(
        instances, allocs, orders, got
    ):
        ref = _schedule_all_cores(
            inst, alloc, order, discipline=discipline
        )
        _assert_schedules_identical(schedules, ref, discipline)
        assert np.array_equal(
            ccts, ccts_from_schedules(inst.num_coflows, ref)
        )
        validate_schedule(inst, schedules)


# Both calendar executors are oracle-checked on the same seed grid: the
# host calendar ("wide", the CPU path) and the device calendar
# ("kernel", the TPU/GPU path; its round through the jnp pair oracle).
FUZZ_CASES = [(s, e) for e in ("wide", "kernel") for s in range(6)]


@pytest.mark.parametrize("discipline", DISCIPLINES)
@pytest.mark.parametrize("seed,engine", FUZZ_CASES)
def test_fuzz_mixed_shapes_and_releases(
    discipline, seed, engine, monkeypatch
):
    """Random mixed-shape ensembles: every member pads flows, ports and
    cores differently; half the seeds use arbitrary release times."""
    _use_engine(monkeypatch, engine)
    rng = np.random.default_rng(seed)
    instances = [
        random_instance(
            num_coflows=int(rng.integers(2, 14)),
            num_ports=int(rng.integers(2, 8)),
            num_cores=int(rng.integers(1, 5)),
            delta=float(rng.choice([0.0, 2.0, 8.0])),
            density=float(rng.uniform(0.15, 0.8)),
            release_span=float(rng.choice([0.0, 25.0])),
            seed=1000 * seed + i,
        )
        for i in range(4)
    ]
    _batch_vs_loop(instances, discipline)


def _table(pairs, rel, dur):
    """A member table of flows on ``pairs`` ((src, dst) each), in
    priority order, with per-flow releases and durations."""
    src, dst = np.asarray(pairs, dtype=np.int64).T
    return dict(
        src=src, dst=dst, rel=np.asarray(rel, np.float64),
        dur=np.asarray(dur, np.float64),
    )


def _slab_release_inversion():
    # Pair (0, 1): the first-priority flow is released last, so the head
    # is deeper than the pair's first flow until t = 9; pair (2, 1) shares
    # the egress port and contends with both.
    return [
        _table([(0, 1), (0, 1), (0, 1), (2, 1), (0, 3)],
               [9.0, 0.0, 4.0, 1.0, 0.0], [2.0, 5.0, 3.0, 4.0, 6.0]),
        _table([(1, 0), (1, 0), (2, 2)], [7.0, 0.0, 0.0], [1.0, 1.5, 2.5]),
    ], 4, 8


def _slab_pair_run(n):
    def make():
        # ``n`` flows on pair (1, 2), with releases out of priority order,
        # and a second member with the same pair lighter.
        rel = np.arange(n, dtype=np.float64)[::-1] % 3
        return [
            _table([(1, 2)] * n + [(0, 2), (1, 0)], np.r_[rel, 0.0, 2.0],
                   np.r_[np.arange(1, n + 1) * 0.5, 3.0, 1.0]),
            _table([(1, 2)] * 2, [0.0, 0.0], [1.0, 1.0]),
        ], 3, 8 if n <= 8 else 16
    return make


def _slab_zero_duration_chain():
    # A chain of zero-duration flows on one pair starts at one instant,
    # round after round, and the pair's next real flow starts with them.
    return [
        _table([(0, 1)] * 5 + [(0, 2), (3, 1)],
               [0.0, 0.0, 2.0, 2.0, 2.0, 0.0, 2.0],
               [0.0, 0.0, 0.0, 0.0, 4.0, 0.0, 1.0]),
    ], 4, 8


SLAB_CASES = {
    "release_inversion": _slab_release_inversion,
    "fills_depth": _slab_pair_run(8),
    "depth_plus_one": _slab_pair_run(9),
    "zero_duration_chain": _slab_zero_duration_chain,
}


def _slab_vs_oracles(tabs, num_ports, discipline):
    """Run member tables through the kernel engine's slab calendar and the
    wide engine, check both against `schedule_core` bit for bit, and
    return the slab depth the kernel engine used."""
    from repro.core.circuit import schedule_core
    from repro.trace import collect

    labels = [f"member {g}" for g in range(len(tabs))]
    with collect() as tally:
        kernel = bc._execute_members(
            tabs, num_ports, discipline, "kernel", labels
        )
    wide = bc._execute_members(tabs, num_ports, discipline, "wide", labels)
    for g, tab in enumerate(tabs):
        F = tab["src"].shape[0]
        # Each flow its own coflow, released at its own time; rate 1 and
        # no delta, so the oracle's durations are ``dur`` exactly.
        ref = schedule_core(
            np.arange(F), tab["src"], tab["dst"], tab["dur"], np.arange(F),
            tab["rel"], num_ports, 1.0, 0.0, discipline=discipline,
        )
        for name, (est, comp) in (("kernel", kernel), ("wide", wide)):
            assert np.array_equal(est[g, :F], ref.establish), (name, g)
            assert np.array_equal(comp[g, :F], ref.complete), (name, g)
    return tally.counts["calendar.slab_depth"]


@pytest.mark.parametrize("discipline", DISCIPLINES)
@pytest.mark.parametrize("case", sorted(SLAB_CASES))
def test_kernel_slab_edge_cases(discipline, case):
    """The kernel engine's pair-major slab: heads deeper than a pair's
    first flow, a pair that fills the slab's depth and one that takes the
    next power of two, and zero-duration chains on one pair."""
    tabs, num_ports, depth = SLAB_CASES[case]()
    assert _slab_vs_oracles(tabs, num_ports, discipline) == depth


@pytest.mark.parametrize("discipline", DISCIPLINES)
def test_kernel_slab_busy_phantoms(discipline, monkeypatch):
    """In-flight phantom rows (``busy``) at the head of a member's table
    ride in the slab like any flow: the kernel engine matches the wide
    engine through `schedule_batch_arrays`, and `schedule_core` on the
    member tables with the phantoms in."""
    from repro.pipeline import get_pipeline
    from repro.pipeline.ensemble_batch import build_ensemble_batch

    inst = random_instance(
        num_coflows=6, num_ports=4, num_cores=2, density=0.6,
        release_span=0.0, seed=21,
    )
    ensemble = build_ensemble_batch([inst], with_lp_arrays=False)
    pipe = get_pipeline("wspt_order")
    orders = pipe.order_stage.order_batch(ensemble)
    alloc = pipe.allocate_stage.allocate_batch_arrays(ensemble, orders)
    busy = {
        (0, k): dict(
            src=np.array([0, 2]), dst=np.array([1, 3]),
            rel=np.zeros(2), dur=np.array([3.0 + k, 0.5]),
        )
        for k in range(2)
    }
    _use_engine(monkeypatch, "kernel")
    got = bc.schedule_batch_arrays(ensemble, alloc, discipline, busy=busy)
    _use_engine(monkeypatch, "wide")
    want = bc.schedule_batch_arrays(ensemble, alloc, discipline, busy=busy)
    (scheds, ccts), = got
    (ref_scheds, ref_ccts), = want
    assert np.array_equal(ccts, ref_ccts)
    _assert_schedules_identical(scheds, ref_scheds, "busy")
    tabs = []
    for k in range(2):
        idx = np.nonzero(alloc.valid[0] & (alloc.core[0] == k))[0]
        if idx.size:
            tabs.append(bc._member_table(ensemble, alloc, 0, k, idx, busy))
    assert tabs
    _slab_vs_oracles(tabs, inst.num_ports, discipline)


@pytest.mark.parametrize("engine", ["wide", "kernel"])
@pytest.mark.parametrize("discipline", DISCIPLINES)
def test_single_flow_and_empty_cores(discipline, engine, monkeypatch):
    """F=1 instances on K=3 cores: two cores stay empty, and the empty
    CoreSchedules must match the oracle's F=0 fast path field for field."""
    _use_engine(monkeypatch, engine)
    demands = np.zeros((1, 3, 3))
    demands[0, 1, 2] = 7.0
    inst = dataclasses.replace(
        random_instance(num_coflows=1, num_ports=3, num_cores=3, seed=0),
        demands=demands,
    )
    order = np.array([0])
    alloc = allocate(inst, order)
    (schedules, ccts), = schedule_batch(
        [inst], [alloc], [order], discipline=discipline
    )
    ref = _schedule_all_cores(inst, alloc, order, discipline=discipline)
    _assert_schedules_identical(schedules, ref, "F=1")
    assert sum(len(cs.coflow) for cs in schedules) == 1
    assert np.array_equal(ccts, ccts_from_schedules(1, ref))


def _raw_alloc(coflow, src, dst, size, core, K, N):
    z = np.zeros((K, 2 * N))
    return Allocation(
        coflow=np.asarray(coflow, dtype=np.int64),
        src=np.asarray(src, dtype=np.int64),
        dst=np.asarray(dst, dtype=np.int64),
        size=np.asarray(size, dtype=np.float64),
        core=np.asarray(core, dtype=np.int64),
        rho_ports=z,
        tau_ports=z.copy(),
        prefix_lb=np.zeros(int(np.max(coflow)) + 1),
    )


@pytest.mark.parametrize("engine", ["wide", "kernel"])
@pytest.mark.parametrize("discipline", DISCIPLINES)
def test_zero_duration_flows(discipline, engine, monkeypatch):
    """size=0 + delta=0 subflows (dur == 0) chain same-port starts at one
    instant in the NumPy loop; the padded calendar must do exactly the
    same instead of stalling or spreading them across events."""
    _use_engine(monkeypatch, engine)
    N, K = 4, 2
    inst = dataclasses.replace(
        random_instance(num_coflows=3, num_ports=N, num_cores=K, seed=1),
        delta=0.0,
    )
    alloc = _raw_alloc(
        coflow=[0, 0, 1, 2, 2],
        src=[0, 0, 1, 0, 3],
        dst=[1, 1, 2, 1, 3],
        size=[0.0, 0.0, 5.0, 0.0, 2.0],
        core=[0, 0, 0, 0, 1],
        K=K, N=N,
    )
    order = np.arange(3)
    (schedules, ccts), = schedule_batch(
        [inst], [alloc], [order], discipline=discipline
    )
    ref = _schedule_all_cores(inst, alloc, order, discipline=discipline)
    _assert_schedules_identical(schedules, ref, "dur=0")
    assert (schedules[0].establish >= 0).all()
    assert np.array_equal(ccts, ccts_from_schedules(3, ref))


def test_empty_ensemble_and_mismatch():
    assert schedule_batch([], [], []) == []
    inst = random_instance(num_coflows=3, num_ports=3, num_cores=2, seed=0)
    with pytest.raises(ValueError, match="length mismatch"):
        schedule_batch([inst], [], [])
    with pytest.raises(ValueError, match="unknown discipline"):
        schedule_batch([], [], [], discipline="nope")


def test_event_bound_is_static_and_generous():
    # 3F + 4: F start rounds + 2F + 1 distinct event values, plus slack.
    assert event_bound(0) == 4
    assert event_bound(100) == 304


# ------------------------------------------------- end-to-end parity grid
GRID = [(5, 3, 2, 0), (8, 4, 3, 1), (6, 5, 4, 2)]


@pytest.fixture(scope="module")
def grid_with_lp():
    instances = [
        random_instance(
            num_coflows=M, num_ports=N, num_cores=K, seed=seed,
            release_span=15.0 * (seed % 2),
        )
        for M, N, K, seed in GRID
    ]
    # The EPS fluid scheme models packet switching: it requires delta == 0,
    # so the grid carries a zero-delta shadow ensemble for it.
    zero = [dataclasses.replace(i, delta=0.0) for i in instances]
    return (
        instances, [lp.solve_exact(i) for i in instances],
        zero, [lp.solve_exact(i) for i in zero],
    )


@pytest.mark.parametrize("scheme", sorted(pipeline.list_schemes()))
@pytest.mark.parametrize("discipline", DISCIPLINES)
def test_run_batch_cct_parity_all_schemes(scheme, discipline, grid_with_lp):
    """`run_batch` (batched alloc + batched circuit where available) must
    reproduce the per-instance `Pipeline.run` CCTs bit for bit for every
    registered scheme."""
    instances, sols, zero, zero_sols = grid_with_lp
    if pipeline.get_scheme(scheme).circuit == "fluid":
        instances, sols = zero, zero_sols
    pipe = pipeline.get_pipeline(scheme, discipline=discipline)
    batch = pipe.run_batch(instances, lp_solutions=sols, require_batch=True)
    for inst, sol, got in zip(instances, sols, batch):
        ref = pipe.run(inst, lp_solution=sol)
        assert np.array_equal(got.ccts, ref.ccts), scheme
        assert got.total_weighted_cct == ref.total_weighted_cct, scheme


@pytest.mark.parametrize("discipline", DISCIPLINES)
def test_circuit_loop_backend_falls_back_and_matches(discipline, grid_with_lp):
    """circuit_backend="loop" runs the per-instance oracle inside
    run_batch (identical results), and require_batch flags the fallback."""
    instances, sols, _, _ = grid_with_lp
    pipe = pipeline.get_pipeline(
        "ours", discipline=discipline, circuit_backend="loop"
    )
    batch = pipe.run_batch(instances, lp_solutions=sols)
    ref = pipeline.get_pipeline("ours", discipline=discipline).run_batch(
        instances, lp_solutions=sols, require_batch=True
    )
    for a, b in zip(batch, ref):
        assert np.array_equal(a.ccts, b.ccts)
        _assert_schedules_identical(
            a.core_schedules, b.core_schedules, "loop-backend"
        )
    with pytest.raises(RuntimeError, match="circuit loop"):
        pipe.run_batch(instances, lp_solutions=sols, require_batch=True)


def test_unknown_circuit_backend_rejected():
    with pytest.raises(ValueError, match="unknown circuit backend"):
        pipeline.build_pipeline(
            pipeline.get_scheme("ours"), circuit_backend="nope"
        )


def test_not_scheduled_guard_regression():
    """cct_per_coflow must refuse schedules with NOT_SCHEDULED flows
    rather than silently clamping them to 0 in the max."""
    from repro.core.circuit import CoreSchedule

    cs = CoreSchedule(
        coflow=np.array([0, 1]),
        src=np.array([0, 1]),
        dst=np.array([1, 2]),
        size=np.array([1.0, 2.0]),
        establish=np.array([0.0, NOT_SCHEDULED]),
        complete=np.array([1.5, NOT_SCHEDULED]),
        rate=2.0,
        delta=0.5,
    )
    with pytest.raises(ValueError, match="NOT_SCHEDULED"):
        cs.cct_per_coflow(2)
    cs.complete[1] = 3.0
    cs.establish[1] = 0.5
    out = cs.cct_per_coflow(2)
    assert np.array_equal(out, [1.5, 3.0])


# ------------------------------------------------- engine selection
def test_check_engine_auto_env_and_explicit(monkeypatch):
    """The platform picks the calendar: the device calendar on TPU and
    GPU, the host calendar on a CPU."""
    for backend, want in (("cpu", "wide"), ("tpu", "kernel"), ("gpu", "kernel")):
        monkeypatch.setattr(bc.jax, "default_backend", lambda b=backend: b)
        assert bc._engine() == want


def test_kernel_fallback_warns_once(monkeypatch):
    """On backends without a native Pallas lowering the device calendar
    must say (once) that its round runs through the jnp oracle."""
    import warnings

    if bc.jax.default_backend() != "cpu":
        pytest.skip("fallback only happens on interpret-mode backends")
    monkeypatch.setattr(bc, "_KERNEL_FALLBACK_WARNED", False)
    _use_engine(monkeypatch, "kernel")
    inst = random_instance(num_coflows=3, num_ports=3, num_cores=2, seed=7)
    order = wspt_order(inst)
    alloc = allocate(inst, order)
    with pytest.warns(RuntimeWarning, match="jnp pair oracle"):
        schedule_batch([inst], [alloc], [order])
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        schedule_batch([inst], [alloc], [order])


@pytest.mark.parametrize("discipline", DISCIPLINES)
def test_kernel_engine_forced_pallas_parity(discipline, monkeypatch):
    """The full calendar with the Pallas pair_resolve round forced on
    (interpret mode on CPU) stays bit-identical to the oracle — the same
    program that runs compiled on TPU/GPU."""
    _use_engine(monkeypatch, "kernel")
    monkeypatch.setattr(bc, "_PAIR_KERNEL_INTERPRET", True)
    inst = random_instance(
        num_coflows=4, num_ports=3, num_cores=2, seed=11, release_span=10.0
    )
    _batch_vs_loop([inst], discipline)


@pytest.mark.parametrize("discipline", DISCIPLINES)
def test_run_batch_kernel_engine_parity(discipline, grid_with_lp, monkeypatch):
    """Pipeline.run_batch on the device calendar reproduces the host
    calendar's CCTs and schedules bit for bit."""
    instances, sols, _, _ = grid_with_lp
    pipe = pipeline.get_pipeline("ours", discipline=discipline)
    _use_engine(monkeypatch, "kernel")
    batch = pipe.run_batch(instances, lp_solutions=sols, require_batch=True)
    _use_engine(monkeypatch, "wide")
    ref = pipe.run_batch(instances, lp_solutions=sols, require_batch=True)
    for a, b in zip(batch, ref):
        assert np.array_equal(a.ccts, b.ccts)
        _assert_schedules_identical(
            a.core_schedules, b.core_schedules, "kernel-engine"
        )


def test_lower_calendar_engines():
    """lower_calendar lowers the device calendar's XLA program on every
    platform (the HLO feeds the roofline report) and refuses an empty
    bucket."""
    from repro.pipeline.batch_circuit import lower_calendar, member_tables

    inst = random_instance(num_coflows=4, num_ports=3, num_cores=2, seed=3)
    order = wspt_order(inst)
    alloc = allocate(inst, order)
    tabs = [
        t for t in member_tables(inst, alloc, order) if t["coflow"].shape[0]
    ]
    text = lower_calendar(tabs, inst.num_ports, "greedy").as_text()
    assert "while" in text
    with pytest.raises(ValueError, match="at least one member"):
        lower_calendar([], inst.num_ports, "greedy")
