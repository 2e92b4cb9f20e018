"""A run whose timed path is broken underneath must come out not correct.

Each fault is planted in the program for the length of one run of the
harness on the CPU (the harness's look for a chip skipped), at a tiny
size, once for each fault a cell can have:

  * an answer altered where it is produced: one flow's completion moved
    in the calendar's output;
  * half of the batch left out: half of every calendar member's flows
    are never scheduled, and come back as if done at time 0;
  * a step that returns its state unchanged: the ordering LP's solver
    hands back its warm start;
  * an order altered where it is produced: the first two coflows of
    every order built from the LP's completion times swapped (in the
    stream only `order_mismatch` can see it, since the reference
    replays the program's orders).

The exchange between chips does not exist in a one-chip cell.
"""

import time

import numpy as np
import pytest

CELLS = ["fb150_k2.sweep", "fb48_k4.stream", "fb48_k4.sweep"]


def _altered(orig):
    def run(tabs, *a, **k):
        est, comp = orig(tabs, *a, **k)
        comp = np.array(comp)
        comp[0, 0] += 1.0
        return est, comp
    return run


def _half_left_out(orig):
    def run(tabs, *a, **k):
        keep = [t["src"].shape[0] - t["src"].shape[0] // 2 for t in tabs]
        cut = [{key: (v[:n] if isinstance(v, np.ndarray) and v.ndim == 1
                      and v.shape[0] == t["src"].shape[0] else v)
                for key, v in t.items()} for t, n in zip(tabs, keep)]
        est, comp = orig(cut, *a, **k)
        est, comp = np.array(est), np.array(comp)
        for g, n in enumerate(keep):
            est[g, n:] = 0.0
            comp[g, n:] = 0.0
        return est, comp
    return run


def _order_altered(orig):
    def order(key, mask):
        out = np.array(orig(key, mask))
        two = np.asarray(mask).sum(axis=1) >= 2
        out[two, 0], out[two, 1] = out[two, 1], out[two, 0]
        return out
    return order


def _lp_unchanged(orig):
    def run(*a, iters, **k):
        return orig(*a, iters=1, **k)
    return run


FAULTS = {
    "answer_altered": ("repro.pipeline.batch_circuit", "_execute_members",
                       _altered),
    "half_left_out": ("repro.pipeline.batch_circuit", "_execute_members",
                      _half_left_out),
    "state_unchanged": ("repro.core.lp", "_subgradient_run_batch",
                        _lp_unchanged),
    "order_altered": ("repro.pipeline.stages", "_masked_stable_order",
                      _order_altered),
}


def _run(cell, config):
    from harness import registry
    from harness.cell import run_cell

    return run_cell(registry.benchmark(), cell, 9, 0.5, False,
                    time.perf_counter(), require_tpu=False, cache_dir=None,
                    config=config, log=lambda s: None)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tiny_config):
    assert _run(cell, tiny_config(cell.split(".")[0]))["correct"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, tiny_config, monkeypatch):
    import importlib

    module, name, plant = FAULTS[fault]
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, name, plant(getattr(mod, name)))
    try:
        out = _run(cell, tiny_config(cell.split(".")[0]))
    except (RuntimeError, ValueError, AssertionError):
        return  # the program itself refused the broken path
    assert not out["correct"], out["checks"]
