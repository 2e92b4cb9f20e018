"""The paper's Sec. V-A setting as an ensemble through `sweep()`.

A small Sec. V-A-like ensemble (six instances sampled from the trace's
stand-in at 4 ports and 12 coflows, K=3 at rates 10/20/30, zero
releases, unequal flow counts) runs through the normal path with the
kernel calendar (its Pallas round in interpret mode):

  * every member equals its per-member NumPy oracles bit for bit: the
    LP against `lp.solve_subgradient`, the allocation against
    `allocate`, the calendar against `_schedule_all_cores`, the CCTs;
  * every member's weighted CCT is within 8K of its exact LP;
  * the ensemble counters hold exactly (LP buckets and members, the
    scan's members and member-steps, the pair cells and the kernel's
    padded pair slots);
  * the LP's ``lp.*`` spans sit inside ``sweep.lp``, in the tally and on
    the profiler's clock.
"""

import glob

import numpy as np
import pytest

import jax

from repro.core import lp
from repro.core.allocation import allocate
from repro.core.scheduler import _schedule_all_cores
from repro.experiments import sweep
from repro.experiments.ensemble import build_buckets
from repro.pipeline import batch_circuit as bc
from repro.traffic.instances import sample_instance

MEMBERS, N, M, ITERS = 6, 4, 12, 300
KW = dict(
    schemes=("ours",), lp_method="batch", lp_iters=ITERS, m_quantum=1,
    p_quantum=1, discipline="greedy", cache=None,
)
LP_SPANS = ("lp.pack", "lp.wait", "lp.unpack")


@pytest.fixture(scope="module")
def ensemble():
    insts = [sample_instance(num_ports=N, num_coflows=M, seed=i)
             for i in range(MEMBERS)]
    assert len({int((x.demands > 0).sum()) for x in insts}) > 1
    assert all(x.num_cores == 3 and not x.releases.any() for x in insts)
    return insts


@pytest.fixture(scope="module")
def swept(ensemble):
    mp = pytest.MonkeyPatch()
    mp.setattr(bc, "_engine", lambda: "kernel")
    mp.setattr(bc, "_PAIR_KERNEL_INTERPRET", True)
    try:
        yield sweep(ensemble, **KW)
    finally:
        mp.undo()


def test_members_match_their_oracles_bit_for_bit(ensemble, swept):
    for b, (inst, rec) in enumerate(zip(ensemble, swept.records)):
        sol = lp.solve_subgradient(inst, iters=ITERS)
        assert np.array_equal(rec.lp.completion, sol.completion), b
        assert rec.lp.objective == sol.objective, b
        res = rec.results["ours"]
        assert np.array_equal(res.order, sol.order()), b
        want = allocate(inst, sol.order())
        for f in ("coflow", "src", "dst", "size", "core"):
            assert np.array_equal(getattr(res.allocation, f),
                                  getattr(want, f)), (b, f)
        scheds = _schedule_all_cores(inst, want, sol.order(),
                                     discipline="greedy")
        for got, ref in zip(res.core_schedules, scheds):
            for f in ("coflow", "src", "dst", "size", "establish",
                      "complete"):
                assert np.array_equal(getattr(got, f), getattr(ref, f)), (
                    b, f)
        ccts = np.zeros(M)
        for s in scheds:
            np.maximum.at(ccts, s.coflow, s.complete)
        assert np.array_equal(res.ccts, ccts), b


def test_every_member_within_8k_of_its_exact_lp(ensemble, swept):
    for inst, rec in zip(ensemble, swept.records):
        wcct = float(np.dot(inst.weights, rec.results["ours"].ccts))
        exact = lp.solve_exact(inst).objective
        assert exact <= rec.lp.objective * (1 + 1e-6)
        assert wcct <= 8 * inst.num_cores * exact


def test_ensemble_counters_hold_exactly(ensemble, swept):
    c = swept.counts
    assert c["lp.buckets"] == len(build_buckets(ensemble, 1, 1)) == 1
    assert c["lp.members"] == MEMBERS
    assert c["lp.member_slots"] == MEMBERS
    assert c["alloc.members"] == MEMBERS
    assert c["alloc.member_steps"] == sum(
        int((x.demands > 0).sum()) for x in ensemble)
    assert c["alloc.steps"] == max(
        int((x.demands > 0).sum()) for x in ensemble)
    members = sum(
        1 for rec in swept.records
        for s in rec.results["ours"].core_schedules if s.coflow.size)
    assert c["calendar.members"] == members
    # N=4 pairs in the kernel's (8, 128) sublane- and lane-padded block.
    assert c["calendar.pair_cells"] == members * N * N
    assert c["calendar.pair_slots"] == members * 8 * 128


def test_lp_spans_sit_inside_sweep_lp(ensemble, swept, tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    s = swept.spans
    assert all(s[k] > 0 for k in LP_SPANS)
    assert sum(s[k] for k in LP_SPANS) <= s["sweep.lp"]
    monkeypatch.setattr(bc, "_engine", lambda: "wide")
    with jax.profiler.trace(str(tmp_path)):
        sweep(ensemble, **KW)
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    found = []
    for plane in ProfileData.from_file(path[-1]).planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            outer = [e for e in events if e[0] == "repro.sweep.lp"]
            ours = [e for e in events
                    if e[0] in {"repro." + k for k in LP_SPANS}]
            if ours:
                assert len(outer) == 1, line.name
                _, o0, o1 = outer[0]
                assert all(o0 <= a and b <= o1 for _, a, b in ours)
                found += [n for n, _, _ in ours]
    assert sorted(found) == sorted("repro." + k for k in LP_SPANS)
