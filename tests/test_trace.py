"""Host spans and counters (`repro.trace`) and what the program records
with them.

  * the helper: nested spans count inclusively, `collect` hands its
    totals to the enclosing tally, and with no tally installed spans and
    counts record nothing;
  * streams (resident and rebuild, host and device calendars):
    every epoch carries its leaf spans, which tile the epoch
    (``stream.epoch``), ``lp_wall_s`` is the ``stream.lp`` span, and
    collecting or tracing changes no output;
  * both calendars (the device one with its Pallas round in interpret
    mode) count their lockstep rounds and each member's rounds exactly,
    under both disciplines, on hand-made tables;
  * the spans sit on the profiler's clock, inside a caller's annotation;
  * ``host_reads`` sees every device -> host read of a stream.
"""

import glob
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import trace
from repro.experiments import stream, sweep
from repro.pipeline import batch_circuit as bc
from repro.traffic import poisson_arrivals, with_releases
from repro.traffic.instances import random_instance

#: Leaf spans of every epoch that solves an LP; the calendar's execution
#: adds ``calendar.wide`` (host calendar) or ``calendar.wait`` and
#: ``calendar.unpack`` (device calendar).
EPOCH_LEAVES = {
    "stream.advance", "stream.admit", "stream.scatter", "stream.lp",
    "stream.order", "alloc.prepare", "alloc.wait", "alloc.unpack",
    "calendar.pack", "calendar.readback", "stream.validate", "stream.record",
}
ENGINE_LEAVES = {
    "wide": {"calendar.wide"},
    "kernel": {"calendar.wait", "calendar.unpack"},
}


def _trace_instance(seed=3, M=10, N=6, K=3):
    inst = random_instance(num_coflows=M, num_ports=N, num_cores=K, seed=seed)
    return with_releases(
        inst, poisson_arrivals(M, mean_interarrival_ms=4.0, seed=seed)
    )


def _stream_kw(mode, engine, monkeypatch):
    """A small stream's options, with ``engine`` the calendar it runs."""
    monkeypatch.setattr(bc, "_engine", lambda: engine)
    return dict(
        lp_method="batch", lp_iters=200, n_batches=4, pool_size=4,
        epoch_mode=mode,
    )


# --------------------------------------------------------------- the helper
def test_nested_spans_count_inclusively():
    with trace.collect() as tally:
        with trace.span("outer") as outer:
            with trace.span("inner") as inner:
                trace.count("things", 2)
            with trace.span("inner") as again:
                trace.count("things")
    assert tally.spans["inner"] == inner.seconds + again.seconds
    assert tally.spans["outer"] == outer.seconds
    assert outer.seconds >= inner.seconds + again.seconds
    assert tally.counts == {"things": 3}


def test_collect_adds_to_the_enclosing_tally():
    with trace.collect() as outer:
        trace.count("a")
        with trace.collect() as inner:
            trace.count("a", 4)
            with trace.span("s"):
                pass
        assert inner.counts == {"a": 4}
        assert outer.counts == {"a": 5}
    assert outer.spans["s"] == inner.spans["s"]


def test_without_a_tally_spans_and_counts_record_nothing():
    with trace.span("alone") as s:
        trace.count("alone")
    assert s.seconds >= 0.0
    with trace.collect() as tally:
        pass
    assert tally.spans == {} and tally.counts == {}


def test_to_host_counts_device_arrays_only():
    with trace.collect() as tally:
        a, b = trace.to_host(jnp.arange(3), np.arange(2))
        c = trace.to_host(jnp.ones(2))
    assert isinstance(a, np.ndarray) and isinstance(c, np.ndarray)
    assert np.array_equal(a, [0, 1, 2]) and np.array_equal(b, [0, 1])
    assert tally.counts == {"host_reads": 2}


# ----------------------------------------------------------------- streams
@pytest.mark.parametrize("engine", ["wide", "kernel"])
@pytest.mark.parametrize("mode", ["resident", "rebuild"])
def test_stream_epochs_carry_tiling_leaf_spans(mode, engine, monkeypatch):
    inst = _trace_instance()
    res = stream(inst, **_stream_kw(mode, engine, monkeypatch))
    assert res.num_resolves >= 3
    leaves = EPOCH_LEAVES | ENGINE_LEAVES[engine]
    leaf_s = epoch_s = 0.0
    for e in res.epochs:
        assert set(e.spans) == leaves | {"stream.epoch"}, e.index
        assert e.lp_wall_s == e.spans["stream.lp"]
        assert e.counts["host_reads"] > 0
        assert e.counts["calendar.members"] > 0
        leaf_s += sum(v for k, v in e.spans.items() if k in leaves)
        epoch_s += e.spans["stream.epoch"]
        # wall_s keeps its boundaries: the decision, after admission.
        assert e.wall_s <= e.spans["stream.epoch"]
    assert leaf_s <= epoch_s
    assert leaf_s >= 0.95 * epoch_s
    assert res.lp_time_s == sum(e.lp_wall_s for e in res.epochs)
    # Call totals: every epoch, plus the pool build and the settlement.
    for name in ("host_reads", "calendar.rounds"):
        assert res.counts[name] == sum(e.counts[name] for e in res.epochs)
    assert res.counts["ensemble.build"] == (
        1 if mode == "resident" else res.num_resolves
    )
    assert "stream.finish" in res.spans


def test_collecting_and_tracing_change_no_output(tmp_path, monkeypatch):
    inst = _trace_instance(seed=5)
    kw = _stream_kw("resident", "wide", monkeypatch)
    plain = stream(inst, **kw)
    with trace.collect() as outer, jax.profiler.trace(str(tmp_path)):
        traced = stream(inst, **kw)
    assert np.array_equal(plain.finish, traced.finish)
    assert np.array_equal(plain.admission, traced.admission)
    for a, b in zip(plain.epochs, traced.epochs, strict=True):
        assert np.array_equal(a.order, b.order)
        assert np.array_equal(a.ccts, b.ccts)
    # The caller's tally saw the whole call.
    assert outer.counts == traced.counts
    assert outer.spans == traced.spans


def test_sweep_records_lp_span_and_stage_counters():
    inst = _trace_instance(seed=7, M=8)
    res = sweep(
        [inst], schemes=("ours",), lp_method="batch", lp_iters=200,
        cache=None,
    )
    assert res.lp_time_s == res.spans["sweep.lp"]
    for name in ("alloc.prepare", "alloc.wait", "alloc.unpack",
                 "calendar.pack", "calendar.readback", "pipeline.build"):
        assert name in res.spans
    assert res.counts["ensemble.build"] == 1
    assert res.counts["calendar.members"] > 0
    assert (res.counts["calendar.member_rounds"]
            <= res.counts["calendar.member_slots"])


# -------------------------------------------------------- calendar rounds
def _tab(src, dst, dur):
    n = len(src)
    return dict(
        src=np.asarray(src, np.int64), dst=np.asarray(dst, np.int64),
        rel=np.zeros(n), dur=np.asarray(dur, np.float64),
    )


# Hand counts (every release 0), per discipline: a chain of three flows
# on one port pair starts one flow a round (3 rounds); one flow (1
# round); two flows on disjoint port pairs start together (1 round) —
# the same under both disciplines.  In CROSS, flow 0 on (0, 0) blocks
# flow 1 on (0, 1) and flow 3 on (1, 0) until t = 2: greedy backfills
# flow 2 on (1, 1) at t = 0 in a second round at that instant and starts
# flows 1 and 3 together at t = 2 (3 rounds); reserving holds flow 2 for
# flow 1, the first claimer on egress 1, and flow 3 for flow 2, the first
# claimer on ingress 1, so one flow starts a round, at 0, 2, 3 and 4 (4
# rounds).  Each table comes with its hand-computed establishment times
# per discipline.
def _both(est):
    return {"greedy": est, "reserving": est}


CHAIN = (_tab([0, 0, 0], [1, 1, 1], [1.0, 2.0, 1.0]), _both([0.0, 1.0, 3.0]))
ONE = (_tab([1], [0], [3.0]), _both([0.0]))
DISJOINT = (_tab([0, 1], [0, 1], [1.0, 1.0]), _both([0.0, 0.0]))
CROSS = (
    _tab([0, 0, 1, 1], [0, 1, 1, 0], [2.0, 1.0, 1.0, 1.0]),
    {"greedy": [0.0, 2.0, 0.0, 2.0], "reserving": [0.0, 2.0, 3.0, 4.0]},
)


def _run_members(monkeypatch, members, engine, discipline):
    monkeypatch.setattr(bc, "_PAIR_KERNEL_INTERPRET", True)
    tabs = [t for t, _ in members]
    labels = [f"member {g}" for g in range(len(tabs))]
    with trace.collect() as tally:
        est, comp = bc._execute_members(tabs, 2, discipline, engine, labels)
    for g, (tab, want) in enumerate(members):
        want = want[discipline]
        F = len(want)
        assert np.array_equal(est[g, :F], want), g
        assert np.array_equal(comp[g, :F], np.asarray(want) + tab["dur"])
    return tally.counts


@pytest.mark.parametrize("discipline", ["greedy", "reserving"])
@pytest.mark.parametrize("engine", ["kernel", "wide"])
@pytest.mark.parametrize("members,counts", [
    # {discipline: (rounds, member_rounds)}
    ([CHAIN], {"greedy": (3, 3), "reserving": (3, 3)}),
    ([ONE], {"greedy": (1, 1), "reserving": (1, 1)}),
    ([DISJOINT], {"greedy": (1, 1), "reserving": (1, 1)}),
    ([CHAIN, ONE, DISJOINT], {"greedy": (3, 5), "reserving": (3, 5)}),
    ([CROSS], {"greedy": (3, 3), "reserving": (4, 4)}),
])
def test_calendar_counts_rounds_by_hand(
    monkeypatch, engine, discipline, members, counts
):
    rounds, member_rounds = counts[discipline]
    c = _run_members(monkeypatch, members, engine, discipline)
    assert c["calendar.rounds"] == rounds
    assert c["calendar.member_rounds"] == member_rounds
    assert c["calendar.members"] == len(members)
    assert c["calendar.member_slots"] == len(members) * rounds


# ------------------------------------------------------- profiler's clock
def test_spans_nest_inside_a_callers_annotation_on_the_profile(
    tmp_path, monkeypatch
):
    from jax.profiler import ProfileData

    inst = _trace_instance(seed=9, M=6)
    kw = _stream_kw("resident", "wide", monkeypatch)
    stream(inst, **kw)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.outer"):
            res = stream(inst, **kw)
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    data = ProfileData.from_file(path[-1])
    found = []
    for plane in data.planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            outer = [e for e in events if e[0] == "test.outer"]
            ours = [e for e in events if e[0].startswith("repro.stream.")]
            if ours:
                assert plane.name.startswith("/host:"), plane.name
                assert len(outer) == 1, line.name
                _, o0, o1 = outer[0]
                assert all(o0 <= s and e <= o1 for _, s, e in ours)
                found += [n for n, _, _ in ours]
    assert found.count("repro.stream.epoch") == res.num_resolves
    assert found.count("repro.stream.lp") == res.num_resolves


# -------------------------------------------------------------- host reads
def test_host_reads_sees_every_device_read(monkeypatch):
    """Every device -> host read of a stream goes through `trace.to_host`.

    Under ``transfer_guard_device_to_host("disallow")`` any other read
    raises on an accelerator.  The CPU backend hands NumPy its buffers in
    place and never trips the guard, so there the test sees only scalar
    reads (``ArrayImpl._value``: ``bool``, ``float``, ``int``) outside the
    helper, and the count: per epoch, the LP's completions and objective
    and the warm-start flag, the scan's four outputs and the calendar's
    six."""
    from jax._src.array import ArrayImpl

    value = ArrayImpl._value
    helper = trace.to_host.__code__
    outside = []

    def watched(self):
        f = sys._getframe(1)
        while f is not None and f.f_code is not helper:
            f = f.f_back
        if f is None:
            outside.append(self.shape)
        return value.fget(self)

    inst = _trace_instance(seed=11)
    kw = _stream_kw("resident", "kernel", monkeypatch)
    stream(inst, **kw)  # compile first
    monkeypatch.setattr(ArrayImpl, "_value", property(watched))
    with jax.transfer_guard_device_to_host("disallow"):
        res = stream(inst, **kw)
    assert outside == []
    assert [e.counts["host_reads"] for e in res.epochs] == [
        3 + 4 + 6
    ] * res.num_resolves
    assert res.counts["host_reads"] == 13 * res.num_resolves
    if jax.default_backend() != "cpu":
        with jax.transfer_guard_device_to_host("disallow"):
            with pytest.raises(Exception, match="[Dd]isallowed"):
                np.asarray(jnp.arange(3) + 1)
