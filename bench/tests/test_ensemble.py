"""The Sec. V-A ensemble cell (`paper_k3.ensemble`): its generator gives
the program's own Sec. V-A instances, a sound run is correct, and a fault
planted in one member other than the first comes out not correct, so the
check reads every member.

Faults, each in member `TARGET` alone (the harness run on the CPU, at a
tiny size, the look for a chip skipped, and the program's own schedule
validation off, so that only the benchmark's check can see them):

  * an answer altered: one flow's completion moved in that member's first
    calendar;
  * half of a calendar's flows left out: that calendar schedules only its
    first half, and the rest come back as if done at time 0;
  * the LP's warm start: that member's LP solution is the one-iteration
    solve (its warm start), the others' are sound;
  * an order altered: the first two coflows of that member's order built
    from the LP's completion times swapped.
"""

import copy
import time

import numpy as np
import pytest

from harness import gen, registry

CELL = "paper_k3.ensemble"
TARGET = 2


def _tiny():
    c = copy.deepcopy(registry.config("paper_k3"))
    c.update(num_ports=4, num_coflows=12, members=3, lp_iters=200)
    return c


def _run(config):
    from harness.cell import run_cell

    return run_cell(registry.benchmark(), CELL, 2**31 + 9, 0.5, False,
                    time.perf_counter(), require_tpu=False, cache_dir=None,
                    config=config, log=lambda s: None)


def test_member_i_is_the_programs_sec_va_instance():
    from repro.traffic.instances import paper_default_instance

    config = registry.config("paper_k3")
    members = registry.generator("paper_va").members(config)
    stacked = gen.config_instance(config)
    assert len(members) == config["members"] == 64
    assert stacked.num_flows == 46478
    M = config["num_coflows"]
    for i, m in enumerate(members):
        want = paper_default_instance(seed=i)
        for got in (m, stacked.subset(np.arange(M * i, M * (i + 1)))):
            assert np.array_equal(got.demands, want.demands), i
            assert np.array_equal(got.weights, want.weights), i
            assert np.array_equal(got.releases, want.releases), i
            assert np.array_equal(got.rates, want.rates)
            assert got.delta == want.delta


def test_sound_run_is_correct():
    out = _run(_tiny())
    assert out["correct"], out["checks"]
    assert out["checks"]["bound_ratio"]["limit"] == 24.0
    assert set(out["metrics"]) == {"setup_s", "sweep_flows_per_s.fb48_k4"}
    assert out["metrics"]["sweep_flows_per_s.fb48_k4"]["value"] > 0


def test_control_fails_and_program_passes():
    from readings import readings

    rows = readings(CELL, [5], [5], require_tpu=False, cache=False,
                    config=_tiny(), log=lambda s: 0)
    prog, ctrl = rows[0]["program"], rows[0]["control"]
    exact = [k for k in prog if k.endswith("_mismatch") or k == "violations"]
    assert all(prog[k] == 0 for k in exact), prog
    assert any(ctrl[k] > 0 for k in exact), ctrl


def _target(labels):
    """Calendar rows of member `TARGET`."""
    return [g for g, s in enumerate(labels)
            if s.startswith(f"instance {TARGET},")]


def _altered(orig):
    def run(tabs, *a, **k):
        est, comp = orig(tabs, *a, **k)
        comp = np.array(comp)
        comp[_target(k["labels"])[0], 0] += 1.0
        return est, comp
    return run


def _half_left_out(orig):
    def run(tabs, *a, **k):
        g = _target(k["labels"])[0]
        F = tabs[g]["src"].shape[0]
        n = F - F // 2
        tabs = list(tabs)
        tabs[g] = {key: (v[:n] if isinstance(v, np.ndarray) and v.ndim == 1
                         and v.shape[0] == F else v)
                   for key, v in tabs[g].items()}
        est, comp = orig(tabs, *a, **k)
        est, comp = np.array(est), np.array(comp)
        est[g, n:F] = 0.0
        comp[g, n:F] = 0.0
        return est, comp
    return run


def _lp_unchanged(orig):
    def run(*a, iters, **k):
        sound = orig(*a, iters=iters, **k)
        start = orig(*a, iters=1, **k)
        return tuple(x.at[TARGET].set(y[TARGET]) for x, y in
                     zip(sound[:3], start[:3])) + tuple(sound[3:])
    return run


def _order_altered(orig):
    def order(key, mask):
        out = np.array(orig(key, mask))
        out[TARGET, [0, 1]] = out[TARGET, [1, 0]]
        return out
    return order


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


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_one_later_member_is_not_correct(fault, monkeypatch):
    import importlib

    module, name, plant = FAULTS[fault]
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, name, plant(getattr(mod, name)))
    # The program's own schedule validation would refuse the calendar
    # faults first; with it off only the benchmark's check can see them.
    monkeypatch.setattr("repro.pipeline.pipeline.validate_schedule",
                        lambda *a, **k: None)
    out = _run(_tiny())
    assert not out["correct"], out["checks"]
