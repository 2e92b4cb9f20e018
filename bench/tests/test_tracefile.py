"""The trace reduction: busy union, idle share, per-program sums and idle
gaps named by span, on a hand-made trace with known answers and on a
small trace recorded on a TPU v5e (`record_trace.py`)."""

import json
from pathlib import Path

import pytest

from harness import tracefile

DATA = Path(__file__).resolve().parent / "data"


def _ev(name, s, e, dev="TPU:0"):
    return (dev, name, float(s), float(e))


HAND = {
    # Window 0..100 ns; ops overlap at 10..30 and 20..40, then 60..70.
    "spans": [("host", "bench.window", 0, 100), ("host", "bench.sweep", 5, 80),
              ("host", "bench.reference", 80, 100)],
    "ops": [_ev("fusion.1", 10, 30), _ev("pair_resolve.2", 20, 40),
            _ev("while.3", 60, 75), _ev("fusion.1", 60, 70)],
    "modules": [_ev("jit__scan_all(3)", 10, 40),
                _ev("jit__run_calendar_pairs_impl(7)", 60, 70)],
}


def test_hand_made_trace():
    r = tracefile.reduce(HAND)
    assert r["window_s"] == pytest.approx(100e-9)
    # 10..40 and 60..70: the loop op at 60..75 holds fusion.1 and is not
    # itself counted.
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["program_s"] == pytest.approx(
        {"jit__scan_all": 30e-9, "jit__run_calendar_pairs_impl": 10e-9})
    assert dict(r["device_ops"]) == pytest.approx({
        "jit__scan_all/fusion.1": 20e-9, "jit__scan_all/pair_resolve.2": 20e-9,
        "jit__run_calendar_pairs_impl/fusion.1": 10e-9})
    gaps = {n: s for n, s in r["idle_gaps"]}
    assert gaps == pytest.approx({
        "bench.sweep before any program (x1)": 10e-9,
        "bench.sweep after jit__scan_all (x1)": 20e-9,
        "bench.reference after jit__run_calendar_pairs_impl (x1)": 30e-9,
    })
    busy_plus_idle = r["busy_s"] + sum(gaps.values())
    assert busy_plus_idle == pytest.approx(r["window_s"])


def test_two_devices_average():
    ev = dict(HAND, ops=HAND["ops"] + [_ev("fusion.9", 0, 100, "TPU:1")])
    r = tracefile.reduce(ev)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((40e-9 + 100e-9) / 2)


def test_no_window_or_no_device_gives_nothing():
    assert tracefile.reduce(dict(HAND, spans=[])) is None
    assert tracefile.reduce(dict(HAND, ops=[])) is None


@pytest.mark.parametrize("path", sorted(DATA.glob("trace_*.json")),
                         ids=lambda p: p.stem)
def test_recorded_trace(path):
    ev = json.loads(path.read_text())
    r = tracefile.reduce(ev)
    assert r is not None
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = sum(s for _, s in r["idle_gaps"])
    assert r["busy_s"] + idle == pytest.approx(r["window_s"], rel=1e-6)
    assert any(n.startswith("jit__") for n in r["program_s"])


def test_gaps_inside_a_program_are_named_in_it():
    ev = {"spans": [("host", "bench.window", 0, 100),
                    ("host", "bench.stream", 0, 100)],
          "ops": [_ev("fusion.1", 10, 20), _ev("fusion.2", 40, 50)],
          "modules": [_ev("jit__scan_all(3)", 5, 60)]}
    gaps = {n: s for n, s in tracefile.reduce(ev)["idle_gaps"]}
    assert gaps == pytest.approx({
        "bench.stream in jit__scan_all (x2)": 30e-9,
        "bench.stream after jit__scan_all (x1)": 50e-9,
    })
