"""In-place slot-pool `EnsembleBatch` primitive tests.

The resident pool is the ONE sanctioned exemption from the build-once
contract: a single `EnsembleBatch` padded to the pool capacity whose
array *contents* are scatter-updated in place by `update_slots` /
`free_slots` (counted as ``slot.scatter``), with per-slot flow
extents managed inside a fixed-capacity arena that grows geometrically
(``slot.grow`` — the epoch compile-cache bucket ladder).

Contracts under test:

  * scatter fidelity — a populated slot holds exactly the canonical
    flow table (`flows_of`, largest-first), port statistics and global
    lower bound of its coflow, and the demand matrix round-trips
    through the arena bit for bit;
  * no stale leaks — freeing and reusing a slot leaves ZERO residue of
    the previous tenant in ANY array: a pool that saw tenant X, freed
    it, and admitted tenant Y is raw-array-identical to a pool that
    only ever saw Y;
  * empty pools — a fully-freed pool schedules nothing (no valid
    flows, all-zero ccts, empty core schedules);
  * arena lifecycle — extent reuse on shrinking residuals, compaction
    + geometric growth that preserves existing tenants, and the
    build-once / scatter counters;
  * sharded parity — `update_slots` on a forced-8-device mesh build is
    bit-identical to the single-device build (subprocess: XLA_FLAGS
    must precede jax init).
"""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.coflow import flows_of, port_stats
from repro.pipeline import ensemble_batch as eb
from repro.pipeline.batch_alloc import allocate_batch_arrays
from repro.pipeline.batch_circuit import schedule_batch_arrays
from repro.trace import collect
from repro.traffic.instances import random_instance

RATES = np.array([10.0, 20.0])
DELTA = 1.5


def _pool(slots=6, num_ports=5, flow_quantum=8, **kw):
    return eb.build_slot_pool_batch(
        slots, num_ports, RATES, DELTA, flow_quantum=flow_quantum, **kw
    )


def _inst(M=3, N=5, seed=0):
    return random_instance(
        num_coflows=M, num_ports=N, num_cores=2, seed=seed
    )


def _slot_demand(pool, slot, num_ports):
    """Reconstruct a slot's demand matrix from the resident flow table."""
    b, r = pool.batch, pool.member
    start = int(pool.flow_start[slot])
    F = int(b.flow_counts[r, slot])
    dem = np.zeros((num_ports, num_ports))
    sl = slice(start, start + F)
    dem[b.flow_src[r, sl], b.flow_dst[r, sl]] = b.flow_size[r, sl]
    return dem


class TestScatterFidelity:
    def test_demands_round_trip_through_arena(self):
        inst = _inst(seed=1)
        pool = _pool()
        slots = np.array([0, 2, 5])
        eb.update_slots(
            pool, slots, inst.demands, inst.weights, inst.releases
        )
        b = pool.batch  # update_slots may regrow: always re-fetch
        for n, s in enumerate(slots):
            assert np.array_equal(
                _slot_demand(pool, int(s), 5), inst.demands[n]
            )
            # Flow table is the canonical largest-first list.
            i_idx, j_idx, sizes = flows_of(
                inst.demands[n], largest_first=True
            )
            sl = slice(
                int(pool.flow_start[s]),
                int(pool.flow_start[s]) + len(sizes),
            )
            assert np.array_equal(b.flow_src[0, sl], i_idx)
            assert np.array_equal(b.flow_dst[0, sl], j_idx)
            assert np.array_equal(b.flow_size[0, sl], sizes)
            assert b.flow_valid[0, sl].all()
            assert (b.flow_coflow[0, sl] == s).all()
            # Port stats + per-slot lower bound match the oracle math.
            rho, tau = port_stats(inst.demands[n])
            assert np.array_equal(
                b.lp_rho[0, s], rho[0].astype(np.float32)
            )
            assert np.array_equal(
                b.lp_tau[0, s], tau[0].astype(np.float32)
            )
            assert b.glb[0, s] == DELTA + rho[0].max() / RATES.sum()
        assert np.array_equal(b.weights[0, slots], inst.weights)
        assert np.array_equal(b.releases[0, slots], inst.releases)
        assert b.coflow_mask[0, slots].all()
        # Untouched slots stay free and masked.
        others = np.setdiff1d(np.arange(6), slots)
        assert not b.coflow_mask[0, others].any()
        assert (pool.flow_start[others] == -1).all()

    def test_build_counts_once_and_scatters_count(self):
        with collect() as tally:
            pool = _pool()
            assert tally.counts["ensemble.build"] == 1
            inst = _inst(seed=2)
            eb.update_slots(
                pool, np.array([1, 3, 4]), inst.demands, inst.weights,
                inst.releases,
            )
            eb.free_slots(pool, np.array([3]))
        assert tally.counts["ensemble.build"] == 1  # still ONE build
        assert tally.counts["slot.scatter"] == 2

    def test_pool_validation(self):
        with pytest.raises(ValueError):
            _pool(slots=0)
        with pytest.raises(ValueError):
            _pool(flow_quantum=0)


class TestStaleLeaks:
    def _assert_batches_identical(self, pa, pb):
        for f in dataclasses.fields(eb.EnsembleBatch):
            if f.metadata.get("static"):
                continue
            a = np.asarray(getattr(pa.batch, f.name))
            b = np.asarray(getattr(pb.batch, f.name))
            assert np.array_equal(a, b), f.name
        assert np.array_equal(pa.flow_start, pb.flow_start)
        assert np.array_equal(pa.flow_cap, pb.flow_cap)

    def test_slot_reuse_leaves_no_residue(self):
        """free + readmit == never-saw-the-first-tenant, raw arrays."""
        x, y = _inst(seed=3), _inst(seed=4)
        bystander = _inst(M=1, seed=5)

        pool_a = _pool()
        # Bystander pins slot 0 so the arena layout is nontrivial.
        eb.update_slots(
            pool_a, np.array([0]), bystander.demands,
            bystander.weights, bystander.releases,
        )
        eb.update_slots(
            pool_a, np.array([2, 3, 4]), x.demands, x.weights, x.releases
        )
        eb.free_slots(pool_a, np.array([2, 3, 4]))
        eb.update_slots(
            pool_a, np.array([2, 3, 4]), y.demands, y.weights, y.releases
        )

        pool_b = _pool()
        eb.update_slots(
            pool_b, np.array([0]), bystander.demands,
            bystander.weights, bystander.releases,
        )
        eb.update_slots(
            pool_b, np.array([2, 3, 4]), y.demands, y.weights, y.releases
        )
        self._assert_batches_identical(pool_a, pool_b)

    def test_free_zeroes_every_per_slot_field(self):
        inst = _inst(seed=6)
        pool = _pool()
        slots = np.array([1, 2, 3])
        eb.update_slots(
            pool, slots, inst.demands, inst.weights, inst.releases
        )
        eb.free_slots(pool, slots)
        b = pool.batch
        assert not b.coflow_mask[0].any()
        assert not b.flow_valid[0].any()
        for arr in (
            b.weights, b.releases, b.glb, b.lp_weights, b.lp_releases,
            b.flow_size, b.flow_counts,
        ):
            assert not np.asarray(arr[0]).any()
        assert not b.lp_rho[0].any() and not b.lp_tau[0].any()
        assert (pool.flow_start == -1).all()
        assert (pool.flow_cap == 0).all()


class TestEmptyPool:
    def test_fully_freed_pool_schedules_nothing(self):
        inst = _inst(seed=7)
        pool = _pool()
        slots = np.array([0, 1, 2])
        eb.update_slots(
            pool, slots, inst.demands, inst.weights, inst.releases
        )
        eb.free_slots(pool, slots)
        b = pool.batch
        orders = np.arange(b.pad_coflows, dtype=np.int64)[None, :]
        alloc = allocate_batch_arrays(b, orders)
        pairs = schedule_batch_arrays(b, alloc, "greedy")
        schedules, ccts = pairs[0]
        assert not np.asarray(alloc.valid[0]).any()
        assert not np.asarray(ccts).any()
        for cs in schedules:
            assert cs.coflow.size == 0


class TestArenaLifecycle:
    def test_shrinking_residual_reuses_extent_in_place(self):
        inst = _inst(M=1, seed=8)
        pool = _pool()
        eb.update_slots(
            pool, np.array([2]), inst.demands, inst.weights, inst.releases
        )
        start, cap = int(pool.flow_start[2]), int(pool.flow_cap[2])
        # Drop half the flows (a preemption residual) and rescatter.
        resid = inst.demands.copy()
        i_idx, j_idx, _ = flows_of(resid[0], largest_first=True)
        resid[0, i_idx[::2], j_idx[::2]] = 0.0
        with collect() as tally:
            eb.update_slots(
                pool, np.array([2]), resid, inst.weights, inst.releases
            )
        b = pool.batch
        assert int(pool.flow_start[2]) == start  # same extent
        assert int(pool.flow_cap[2]) == cap
        assert tally.counts.get("slot.grow", 0) == 0
        F = int(b.flow_counts[0, 2])
        assert not b.flow_valid[0, start + F:start + cap].any()
        assert not b.flow_size[0, start + F:start + cap].any()
        assert np.array_equal(_slot_demand(pool, 2, 5), resid[0])

    def test_growth_is_geometric_and_preserves_tenants(self):
        # quantum 4 but instances carry ~N^2 flows each: the arena must
        # grow, and each growth at least doubles capacity.
        pool = _pool(flow_quantum=4)
        caps = [pool.flow_capacity]
        insts = [_inst(M=1, N=5, seed=10 + s) for s in range(4)]
        with collect() as tally:
            for s, inst in enumerate(insts):
                eb.update_slots(
                    pool, np.array([s]), inst.demands, inst.weights,
                    inst.releases,
                )
                caps.append(pool.flow_capacity)
        assert tally.counts["slot.grow"] > 0
        for a, b in zip(caps, caps[1:]):
            assert b == a or b >= 2 * a  # geometric ladder
            assert b % 4 == 0  # quantized
        # Growth/compaction never corrupted earlier tenants.
        for s, inst in enumerate(insts):
            assert np.array_equal(_slot_demand(pool, s, 5), inst.demands[0])

    def test_compaction_packs_before_growing(self):
        # Fill two slots, free the first (leaving a leading gap), then
        # admit a tenant that fits total-free but not any single gap:
        # the arena must compact instead of growing.
        pool = _pool(slots=4, num_ports=4, flow_quantum=10)
        a, b_, c = (_inst(M=1, N=4, seed=20 + s) for s in range(3))
        for s, inst in ((0, a), (1, b_)):
            eb.update_slots(
                pool, np.array([s]), inst.demands, inst.weights,
                inst.releases,
            )
        cap0 = pool.flow_capacity
        eb.free_slots(pool, np.array([0]))
        with collect() as tally:
            eb.update_slots(
                pool, np.array([2]), c.demands, c.weights, c.releases
            )
        free_total = cap0 - int(
            pool.flow_cap[pool.flow_start >= 0].sum()
        )
        if free_total >= 0 and pool.flow_capacity == cap0:
            assert tally.counts.get("slot.grow", 0) == 0
        # Surviving tenants intact either way.
        assert np.array_equal(_slot_demand(pool, 1, 4), b_.demands[0])
        assert np.array_equal(_slot_demand(pool, 2, 4), c.demands[0])


_SHARD_SCRIPT = r"""
import dataclasses
import numpy as np
import jax

assert len(jax.devices()) == 8, jax.devices()

from repro.launch.mesh import make_local_mesh
from repro.pipeline import ensemble_batch as eb
from repro.traffic.instances import random_instance

insts = [
    random_instance(num_coflows=3, num_ports=5, num_cores=2, seed=s)
    for s in (0, 1)
]
rates = np.array([10.0, 20.0])


def fill(pool):
    eb.update_slots(pool, np.array([0, 2, 4]), insts[0].demands,
                    insts[0].weights, insts[0].releases)
    eb.free_slots(pool, np.array([2]))
    eb.update_slots(pool, np.array([2, 3, 5]), insts[1].demands,
                    insts[1].weights, insts[1].releases)
    return pool


single = fill(eb.build_slot_pool_batch(6, 5, rates, 1.5, flow_quantum=8))
sharded = fill(eb.build_slot_pool_batch(6, 5, rates, 1.5, flow_quantum=8,
                                        mesh=make_local_mesh()))
assert sharded.batch.sharding is not None
assert sharded.batch.pad_members % 8 == 0

for f in dataclasses.fields(eb.EnsembleBatch):
    if f.metadata.get("static"):
        continue
    a = np.asarray(getattr(single.batch, f.name))
    b = np.asarray(getattr(sharded.batch, f.name))
    # Every array carries a leading member axis; the live member is
    # row 0 and must match the single-device build bit for bit.
    assert np.array_equal(a[0], b[0]), f.name
# Sharding pad rows never claim coflows or flows.
assert not np.asarray(sharded.batch.coflow_mask)[1:].any()
assert not np.asarray(sharded.batch.flow_valid)[1:].any()
assert np.array_equal(single.flow_start, sharded.flow_start)
assert np.array_equal(single.flow_cap, sharded.flow_cap)
print("SLOT-POOL-SHARD-OK")
"""


def test_update_slots_sharded_matches_single_device(tmp_path):
    """Forced 8-device mesh build vs single-device: bit-for-bit."""
    proc = subprocess.run(
        [sys.executable, "-c", _SHARD_SCRIPT],
        capture_output=True,
        text=True,
        timeout=900,
        # Inherit the environment: a minimal env (no HOME) can stall
        # CPython startup for minutes on some hosts.
        env={
            **os.environ,
            "PYTHONPATH": "src",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "REPRO_RESULTS": str(tmp_path),
        },
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SLOT-POOL-SHARD-OK" in proc.stdout
