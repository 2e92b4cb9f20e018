"""The allocation-scan Pallas kernel against the jnp loop, bit for bit.

`repro.kernels.alloc_scan` runs `_scan_all`'s recurrence as one Pallas
call on a TPU; elsewhere the jnp loop runs, and it is the kernel's
oracle.  Here the kernel runs in the Pallas interpreter on the CPU, and
its per-step core choices (``ks``), real-core lb maxima (``lbs``) and
final port stats (``rho``, ``tau``) must equal the loop's on:

  * members with unequal flow counts (refinement's candidate rows);
  * a gapped slot pool, whose arena is longer than its valid flows;
  * an all-invalid ensemble (zero steps) and the whole padded axis;
  * ``include_tau=False`` (delta 0);
  * more members than one block (the member axis padded to a multiple);
  * flows over several chunks, not a multiple of the chunk;
  * padded cores (``PAD_LB``) beside real ones.

``alloc.kernel_calls`` counts one per call on the kernel path and nothing
on the loop's.
"""

import numpy as np
import pytest

import jax

from repro.core.allocation import allocate
from repro.core.coflow import CoflowInstance, flows_of
from repro.kernels.alloc_scan import member_block
from repro.kernels.alloc_scan.kernel import CHUNK
from repro.pipeline import batch_alloc
from repro.pipeline import ensemble_batch as eb
from repro.pipeline.exact64 import to_bits
from repro.trace import collect
from repro.traffic.instances import random_instance, scaled_trace_instance

RATES = np.array([10.0, 20.0, 15.0])
DELTA = 1.5


def _inst(M, N=5, K=3, seed=0):
    return random_instance(num_coflows=M, num_ports=N, num_cores=K, seed=seed)


def _gapped_pool():
    """A slot pool with free extents and finished flows in its arena."""
    N = 5
    pool = eb.build_slot_pool_batch(8, N, RATES, DELTA, flow_quantum=8)
    first = random_instance(num_coflows=5, num_ports=N, num_cores=3, seed=11,
                            release_span=20.0)
    eb.update_slots(
        pool, np.arange(5), first.demands, first.weights, first.releases
    )
    eb.free_slots(pool, np.array([1, 3]))
    resid = first.demands[2:3].copy()
    i_idx, j_idx, _ = flows_of(resid[0], largest_first=True)
    resid[0, i_idx[::2], j_idx[::2]] = 0.0
    eb.update_slots(
        pool, np.array([2]), resid, first.weights[2:3], first.releases[2:3]
    )
    return pool.batch


def _empty(M, N):
    return CoflowInstance(
        demands=np.zeros((M, N, N)), weights=np.ones(M),
        releases=np.zeros(M), rates=RATES.copy(), delta=DELTA,
    )


def _case(name):
    """``(batch, orders, include_tau, steps)``; ``steps`` None is the
    longest member's valid count, as `allocate_batch_arrays` steps."""
    rng = np.random.default_rng(7)

    def ensemble(instances, **kw):
        b = eb.build_ensemble_batch(instances, with_lp_arrays=False, **kw)
        orders = b.pad_orders(
            [rng.permutation(i.num_coflows) for i in instances]
        )
        return b, orders

    if name == "unequal_members":
        instances = [_inst(M, seed=s) for M, s in ((3, 21), (7, 22), (5, 23))]
        b = eb.build_ensemble_batch(instances, with_lp_arrays=False)
        b, instance_of, _ = b.expand_members(2)
        orders = b.pad_orders(
            [rng.permutation(instances[i].num_coflows) for i in instance_of]
        )
        return b, orders, True, None
    if name == "gapped_pool":
        b = _gapped_pool()
        live = np.nonzero(b.coflow_mask[0])[0]
        free = np.nonzero(~b.coflow_mask[0])[0]
        order = np.concatenate([rng.permutation(live), free])
        return b, order[None, :], True, None
    if name == "all_invalid":
        b, orders = ensemble([_empty(3, 4), _empty(3, 4)], pad_flows=8)
        return b, orders, True, None
    if name == "whole_axis":
        b, orders = ensemble([_inst(4, seed=31)], pad_flows=160)
        return b, orders, True, b.pad_flows
    if name == "no_tau":
        b, orders = ensemble([_inst(6, N=6, K=4, seed=4)])
        return b, orders, False, None
    if name == "ten_members":
        b, orders = ensemble([_inst(4, N=4, K=2, seed=40 + s)
                              for s in range(10)])
        return b, orders, True, None
    if name == "several_chunks":
        b, orders = ensemble([scaled_trace_instance(24, 12, seed=3)])
        return b, orders, True, None
    if name == "padded_cores":
        b, orders = ensemble([_inst(5, N=4, K=2, seed=50),
                              _inst(5, N=4, K=4, seed=51)])
        return b, orders, True, None
    raise AssertionError(name)


def _scan_args(b, orders, include_tau, steps):
    """`_scan_all`'s operands as `allocate_batch_arrays` builds them."""
    perm = b.permute_flows(orders)
    take = lambda a: np.take_along_axis(a, perm, axis=1)  # noqa: E731
    valid = take(b.flow_valid)
    delta = b.delta if include_tau else np.zeros_like(b.delta)
    lb0 = np.where(b.core_mask, 0.0, eb.PAD_LB)
    zeros = np.zeros((valid.shape[0], b.pad_cores, b.pad_flat_ports),
                     dtype=np.int64)
    if steps is None:
        steps = int(valid.sum(axis=1).max(initial=0))
    return (
        np.int32(steps), take(b.flow_pi).astype(np.int32),
        take(b.flow_pj).astype(np.int32), to_bits(take(b.flow_size)), valid,
        to_bits(b.inv_rates), to_bits(delta), to_bits(lb0), b.core_mask,
        zeros, zeros,
    )


CASES = ["unequal_members", "gapped_pool", "all_invalid", "whole_axis",
         "no_tau", "ten_members", "several_chunks", "padded_cores"]


@pytest.mark.parametrize("name", CASES)
def test_kernel_matches_loop_bit_for_bit(name):
    b, orders, include_tau, steps = _case(name)
    args = _scan_args(b, orders, include_tau, steps)
    B, F = args[4].shape
    if name == "all_invalid":
        assert args[0] == 0 and F > 0
    if name == "gapped_pool":
        assert 0 < args[0] < F
    if name == "ten_members":
        assert B % member_block(B) != 0
    if name == "several_chunks":
        assert args[0] > 2 * CHUNK and F % CHUNK != 0
    if name == "padded_cores":
        assert not b.core_mask.all() and b.core_mask.any(axis=1).all()
    if name == "no_tau":
        assert not include_tau and b.delta.any()
    with jax.enable_x64():
        want = batch_alloc._scan_all(*args)
        got = batch_alloc._scan_all_interpreted(
            *args, kernel=True, interpret=True
        )
        for label, w, g in zip(("ks", "lbs", "rho", "tau"), want, got):
            w, g = np.asarray(w), np.asarray(g)
            assert w.dtype == g.dtype and w.shape == g.shape, label
            bad = np.argwhere(w != g)
            assert bad.size == 0, (label, bad[:3])


def test_kernel_calls_count_the_kernel_path(monkeypatch):
    instances = [_inst(6, seed=61), _inst(4, seed=62)]
    b = eb.build_ensemble_batch(instances, with_lp_arrays=False)
    orders = [np.random.default_rng(s).permutation(i.num_coflows)
              for s, i in enumerate(instances)]
    with collect() as loop_tally:
        loop = batch_alloc.allocate_batch_arrays(b, b.pad_orders(orders))
    assert loop_tally.counts.get("alloc.kernel_calls", 0) == 0
    monkeypatch.setattr(batch_alloc, "_SCAN_KERNEL_INTERPRET", True)
    with collect() as tally:
        kern = batch_alloc.allocate_batch_arrays(b, b.pad_orders(orders))
        batch_alloc.allocate_batch_arrays(b, b.pad_orders(orders),
                                          include_tau=False)
    assert tally.counts["alloc.kernel_calls"] == 2
    for name in ("core", "rho_ports", "tau_ports", "prefix_lb"):
        assert np.array_equal(getattr(kern, name), getattr(loop, name)), name
    for r, (inst, order) in enumerate(zip(instances, orders)):
        ref = allocate(inst, order)
        F = ref.num_flows()
        assert np.array_equal(kern.core[r, :F], ref.core)
        assert np.array_equal(kern.prefix_lb[r, :inst.num_coflows],
                              ref.prefix_lb)
