"""`scan_steps.stream` reads the allocation loop's `alloc.steps` counter:
a traced stream on the CPU at a tiny size reports it, it averages over
epochs, and it is left out where the program does not count the steps."""

import time
from types import SimpleNamespace

from harness import registry


def test_traced_stream_reports_scan_steps(tiny_config):
    from harness.cell import run_cell

    cell = "fb48_k4.stream"
    out = run_cell(registry.benchmark(), cell, 2**31 + 7, 0.5, True,
                   time.perf_counter(), require_tpu=False, cache_dir=None,
                   config=tiny_config("fb48_k4"), log=lambda s: None)
    assert out["correct"], out["checks"]
    assert out["metrics"]["scan_steps.stream"]["value"] > 0.0


def test_scan_steps_per_epoch_and_absent_counter():
    read = registry.metric("scan_steps.stream").read
    epoch = lambda **c: SimpleNamespace(counts=c)  # noqa: E731
    ctx = SimpleNamespace(outs=[SimpleNamespace(epochs=[
        epoch(**{"alloc.steps": 30, "alloc.step_slots": 64}),
        epoch(**{"alloc.steps": 50, "alloc.step_slots": 64}),
    ])])
    assert read(ctx) == 40.0
    parent = SimpleNamespace(outs=[SimpleNamespace(
        epochs=[epoch(host_reads=13)])])
    assert read(parent) is None
    assert read(SimpleNamespace(outs=[])) is None
