"""Compile-only checks of the main path against a described TPU v5e.

The TPU compiler is installed wherever jaxlib is, and compiles for a chip
that is described rather than attached.  These tests lower and compile the
device programs of Algorithm 1 at the widths of the full Facebook trace
(526 coflows, 150 ports, ~266k flows), so a program the chip's compiler
refuses fails here, without a chip:

  * the kernel calendar with the native Pallas `pair_resolve` round under
    x64 (the calendar the platform picks on a TPU);
  * the `pair_resolve` kernel alone;
  * the allocation scan's Pallas kernel, with its exact double arithmetic
    on uint32 halves;
  * the batched subgradient LP;

and each again at the shapes of the paper's Sec. V-A ensemble (64
instances of 100 coflows on 10 ports, K=3, swept together): the calendar
over 192 member-cores, the scan over 64 members, the LP's one bucket.

Nothing runs, so these say nothing about results or times.  The topology
is described inside a module fixture (never at import), since only one
process at a time may load the TPU library: keep these tests in this one
file, which ``pytest -n N --dist loadfile`` gives to a single worker.
"""

import importlib.util
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# Full-trace widths: flat ports 2*150 -> 304 and coflows 526 -> 528 under
# the sweep's quantum of 8; 266,240 flows is the trace's demand-entry
# count rounded up to the flow quantum.
_N = 152
_FLOWS = 266_240
_MP = 528
_PP = 304


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # With the compiler installed, a topology that cannot be described is
    # a failure, not a skip.
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    # A described chip cannot read the persistent cache back; keep it off
    # while these compile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# Sec. V-A ensemble: 64 members of 100 coflows on 10 ports (rounded to 12
# by the calendar's port quantum), K=3 cores, 566-922 flows a member (922
# the scan's padded length), at most 331 on one core (336 under the flow
# quantum); the LP bucket pads coflows 100 -> 104 and flat ports 20 -> 24.
_VA_MEMBERS, _VA_CORES, _VA_N = 64, 3, 12
_VA_CORE_FLOWS, _VA_FLOWS, _VA_MP, _VA_PP = 336, 922, 104, 24


# (members, flows per member, ports, slab depth): an ensemble bucket, the
# whole trace's K=2 bucket (two members of ~133k flows, not padded to the
# quantum; 28 flows on its hottest pair, so a slab 32 deep), and the
# Sec. V-A ensemble's 192 member-cores (at most 17 flows on one pair).
@pytest.mark.parametrize("G,F,N,D", [
    pytest.param(8, 4096, _N, 8, id="8-4096"),
    pytest.param(2, 133_200, _N, 32, id="2-133200"),
    pytest.param(_VA_MEMBERS * _VA_CORES, _VA_CORE_FLOWS, _VA_N, 32,
                 id="sec_va-192-336"),
])
def test_kernel_calendar_compiles_with_native_pallas_round(
    one_chip, G, F, N, D
):
    from repro.pipeline.batch_circuit import (
        _run_calendar_pairs_donated,
        event_bound,
    )

    P = N * N
    with jax.enable_x64():
        s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
        i32, i64 = jnp.int32, jnp.int64  # times are int64 double patterns
        args = (
            s((G, D, P), i64), s((G, D, P), i64),  # rel, dur slabs
            s((G, D, P), jnp.bool_),  # pending0
            s((G, N), i64),  # free0
            s((G, D, P), i32),  # priority ids
            s((G, F), i32),  # slab cell of each pair-sorted flow
        )
        compiled = _run_calendar_pairs_donated.lower(
            *args, reserving=False, bound=event_bound(F), use_kernel=True,
            interpret=False,
        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_slab_counters_on_a_small_bucket():
    """``calendar.slab_cells`` is members x Dp x P and
    ``calendar.slab_depth`` Dp: 9 flows on one pair take Dp 16."""
    import numpy as np

    from repro.pipeline import batch_circuit as bc
    from repro.trace import collect

    n = 9
    tabs = [
        dict(src=np.zeros(n, np.int64), dst=np.ones(n, np.int64),
             rel=np.zeros(n), dur=np.ones(n)),
        dict(src=np.arange(3), dst=np.arange(3), rel=np.zeros(3),
             dur=np.ones(3)),
    ]
    with collect() as tally:
        bc._execute_members(tabs, 3, "greedy", "kernel", ["a", "b"])
    Nmax = bc._round_up(3, bc._N_QUANTUM)
    assert tally.counts["calendar.slab_depth"] == 16
    assert tally.counts["calendar.slab_cells"] == 2 * 16 * Nmax * Nmax


@pytest.mark.parametrize("G,N", [
    (8, _N), (_VA_MEMBERS * _VA_CORES, _VA_N),
])
def test_pair_resolve_kernel_compiles(one_chip, G, N):
    from repro.kernels.event_resolve.kernel import pair_resolve_pallas

    spec = _spec(one_chip, (G, N, N), jnp.float32)
    compiled = pair_resolve_pallas.lower(spec, spec).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,F,Kp,Pp", [
    (1, _FLOWS, 8, _PP), (_VA_MEMBERS, _VA_FLOWS, _VA_CORES, 20),
])
def test_allocation_scan_compiles_at_trace_width(one_chip, B, F, Kp, Pp):
    """The scan a TPU runs: the Pallas kernel (`repro.kernels.alloc_scan`),
    lowered natively inside `_scan_all` under x64."""
    from repro.pipeline.batch_alloc import _scan_all

    with jax.enable_x64():
        s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
        i64 = jnp.int64  # reals are int64 double patterns
        compiled = _scan_all.lower(
            s((), jnp.int32),  # trip count: a runtime scalar, not static
            s((B, F), jnp.int32), s((B, F), jnp.int32),  # endpoints
            s((B, F), i64), s((B, F), jnp.bool_),  # sizes, valid
            s((B, Kp), i64), s((B,), i64),  # inv_rates, delta
            s((B, Kp), i64), s((B, Kp), jnp.bool_),  # lb0, core_mask
            s((B, Kp, Pp), i64), s((B, Kp, Pp), i64),  # rho0, tau0
            kernel=True,
        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,Mp,Pp,iters", [
    (2, _MP, _PP, 1200), (_VA_MEMBERS, _VA_MP, _VA_PP, 3000),
])
def test_subgradient_lp_compiles_at_trace_width(one_chip, B, Mp, Pp, iters):
    from repro.core.lp import _subgradient_run_batch

    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    compiled = _subgradient_run_batch.lower(
        s((B, Mp, Mp)), s((B, Mp, Pp)), s((B, Mp, Pp)),  # Y0, rho, tau
        s((B, Mp)), s((B, Mp)), s((B,)), s((B,)),  # w, releases, 1/R, delta/K
        s((B, Mp), jnp.bool_), s((B, Pp), jnp.bool_),  # masks
        iters=iters,
    ).compile()
    assert compiled.memory_analysis() is not None
