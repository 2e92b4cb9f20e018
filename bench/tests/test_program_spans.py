"""A traced run reports the per-layer metrics read from the program's own
spans and counters (`EpochRecord.spans` / ``counts``, `SweepResult.spans`
/ ``counts``), on the CPU at a tiny size; the device metrics need a chip
and are left out there."""

import time

import pytest

FROM_PROGRAM = {
    "fb48_k4.stream": ["bookkeeping_ms.stream", "order_ms.stream",
                       "alloc_host_ms.stream", "calendar_host_ms.stream",
                       "calendar_rounds.stream", "host_reads.stream"],
    "fb150_k2.sweep": ["calendar_rounds.fb150_k2.sweep",
                       "lockstep_idle.fb150_k2.sweep"],
    "fb48_k4.sweep": ["calendar_rounds.fb48_k4.sweep",
                      "lockstep_idle.fb48_k4.sweep"],
}


@pytest.mark.parametrize("cell", sorted(FROM_PROGRAM))
def test_traced_run_reports_program_metrics(cell, tiny_config):
    from harness import registry
    from harness.cell import run_cell

    out = run_cell(registry.benchmark(), cell, 9, 0.5, True,
                   time.perf_counter(), require_tpu=False, cache_dir=None,
                   config=tiny_config(cell.split(".")[0]),
                   log=lambda s: None)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    for name in FROM_PROGRAM[cell]:
        assert got[name]["value"] >= 0.0, name
    # Every cell runs the calendar; the stream also reads the device back
    # and keeps its books on the host.
    rounds = [n for n in FROM_PROGRAM[cell] if n.startswith("calendar_r")]
    assert got[rounds[0]]["value"] > 0.0
    if cell == "fb48_k4.stream":
        assert got["host_reads.stream"]["value"] > 0.0
        assert got["bookkeeping_ms.stream"]["value"] > 0.0
