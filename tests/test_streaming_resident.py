"""Device-resident streaming epoch tests.

`stream(epoch_mode="resident")` drives LP warm-start -> order -> alloc
-> circuit off ONE slot-pool `EnsembleBatch` through a fused, jitted
epoch step instead of rebuilding the ensemble every epoch.  Contracts:

  * **Mode parity** — with warm-starts off, the resident driver's every
    epoch (order, projected CCTs, LP objective) and the realized
    admission/finish vectors are bit-identical to `epoch_mode="rebuild"`
    (warm resident may differ from rebuild-warm by f32 reduction noise,
    so the bit-parity grid pins ``warm_start=False``).
  * **Replay parity** — one arrival batch + preemption off is the
    offline problem: both drivers must reproduce `Pipeline.run_batch`
    (with the same batched subgradient LP) bit for bit.
  * **Compile stability** — a warmed-up resident stream re-run must add
    ZERO entries to the fused epoch step's compile cache, and builds
    exactly one `EnsembleBatch` per stream (the slot-pool build-once
    exemption).
  * **(8K+1) bound** — warm resident runs stay within the paper bound
    against the exact ordering-LP lower bound.
"""

import numpy as np
import pytest

from repro.core import lp
from repro.experiments import stream
from repro.pipeline import batch_alloc, get_pipeline
from repro.traffic import poisson_arrivals, with_releases
from repro.traffic.instances import random_instance


def _bound(instance) -> float:
    return 8.0 * instance.num_cores + (
        1.0 if (instance.releases > 0).any() else 0.0
    )


def _trace(M, N, K, seed, mean_ms=4.0):
    inst = random_instance(
        num_coflows=M, num_ports=N, num_cores=K, seed=seed
    )
    return with_releases(
        inst, poisson_arrivals(M, mean_interarrival_ms=mean_ms, seed=seed)
    )


# (num_coflows, num_ports, num_cores, n_batches, pool_size, preempt)
PARITY_GRID = [
    (8, 5, 2, 3, None, True),
    (10, 6, 3, 4, 4, True),
    (9, 5, 2, None, 3, False),
    (12, 4, 4, 5, 6, True),
]


@pytest.mark.parametrize("M,N,K,n_batches,pool,preempt", PARITY_GRID)
def test_resident_epochs_bit_identical_to_rebuild(
    M, N, K, n_batches, pool, preempt
):
    inst = _trace(M, N, K, seed=31 + M)
    kw = dict(
        lp_method="batch", lp_iters=300, n_batches=n_batches,
        pool_size=pool, preempt=preempt, warm_start=False, validate=False,
    )
    reb = stream(inst, epoch_mode="rebuild", **kw)
    res = stream(inst, epoch_mode="resident", **kw)
    assert reb.epoch_mode == "rebuild" and res.epoch_mode == "resident"
    assert res.num_resolves == reb.num_resolves
    assert np.array_equal(res.admission, reb.admission)
    assert np.array_equal(res.finish, reb.finish)
    for er, eb_ in zip(res.epochs, reb.epochs):
        assert er.time == eb_.time
        assert np.array_equal(er.actives, eb_.actives)
        assert np.array_equal(er.order, eb_.order)
        assert np.array_equal(er.ccts, eb_.ccts)
        assert er.lp_objective == eb_.lp_objective


@pytest.mark.parametrize("mode", ["rebuild", "resident"])
@pytest.mark.parametrize("M,N,K,span,seed", [
    (6, 4, 2, 25.0, 0),
    (8, 5, 3, 0.0, 1),
    (5, 3, 4, 40.0, 2),
])
def test_single_batch_replay_matches_offline(mode, M, N, K, span, seed):
    """One batch + no preemption == the offline batched pipeline."""
    inst = random_instance(
        num_coflows=M, num_ports=N, num_cores=K,
        seed=seed + 13 * M, release_span=span,
    )
    pipe = get_pipeline("ours", lp_method="batch", lp_iters=800)
    sols = lp.solve_subgradient_batch([inst], iters=800)
    off = pipe.run_batch([inst], lp_solutions=sols)[0]

    res = stream(
        inst, lp_method="batch", lp_iters=800, n_batches=1,
        preempt=False, epoch_mode=mode,
    )
    assert res.epoch_mode == mode
    assert res.num_resolves == 1
    e0 = res.epochs[0]
    assert np.array_equal(e0.order, off.order)
    assert np.array_equal(e0.ccts, off.ccts)
    assert res.realized_weighted_cct == float(
        np.dot(inst.weights, off.ccts)
    )


def test_resident_stream_does_not_retrace_after_warmup():
    inst = _trace(10, 5, 2, seed=7)
    probe = getattr(batch_alloc._scan_all, "_cache_size", None)
    if probe is None:
        pytest.skip("jit cache-size probe unavailable on this jax")
    kw = dict(
        lp_method="batch", lp_iters=200, n_batches=4,
        warm_start=True, validate=False, epoch_mode="resident",
    )
    stream(inst, **kw)  # warm-up: populates every epoch bucket
    before = probe()
    res = stream(inst, **kw)
    assert res.epoch_mode == "resident"
    assert probe() - before == 0


def test_resident_stream_builds_exactly_one_batch():
    inst = _trace(9, 4, 3, seed=11)
    res = stream(
        inst, lp_method="batch", lp_iters=200, n_batches=3,
        validate=False, epoch_mode="resident",
    )
    assert res.num_resolves >= 2
    # Build-once: ONE EnsembleBatch for the whole stream, all epoch
    # state flowing through counted in-place slot scatters.
    assert res.counts["ensemble.build"] == 1
    assert res.counts["slot.scatter"] > 0


def test_epoch_mode_validation():
    inst = _trace(4, 3, 1, seed=3)
    with pytest.raises(ValueError):
        stream(inst, epoch_mode="fused")
    with pytest.raises(ValueError):
        stream(inst, lp_method="exact", epoch_mode="resident")
    # auto resolves per lp_method and is never recorded verbatim.
    res = stream(inst, lp_method="exact", n_batches=1, preempt=False)
    assert res.epoch_mode == "rebuild"
    res = stream(
        inst, lp_method="batch", lp_iters=100, n_batches=1, preempt=False
    )
    assert res.epoch_mode == "resident"


def test_warm_resident_respects_bound():
    for seed in (3, 5):
        inst = random_instance(
            num_coflows=10, num_ports=4, num_cores=3,
            seed=seed, release_span=60.0,
        )
        lb = lp.solve_exact(inst).objective
        # validate=True exercises the dense-remap validation path of the
        # resident driver on every epoch.
        res = stream(
            inst, lp_method="batch", lp_iters=200, n_batches=4,
            warm_start=True, validate=True, epoch_mode="resident",
        )
        assert res.epoch_mode == "resident"
        assert res.warm_resolves >= 1
        assert res.realized_weighted_cct <= _bound(inst) * lb * (1 + 1e-9)
