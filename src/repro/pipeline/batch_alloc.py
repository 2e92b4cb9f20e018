"""Ensemble-batched inter-core allocation (Algorithm 1 Lines 3–15, JAX).

The NumPy reference `repro.core.allocation.allocate` walks one instance's
flow table in (global order, largest-first) sequence keeping per-core
per-port prefix stats, and places each flow on the core minimizing the
post-placement prefix lower bound — a Python-level loop of O(K) vector
steps per flow.  After PR 2 batched the LP phase, this loop became the
sweep bottleneck: B instances x thousands of flows, each flow a Python
iteration.

Here the identical recurrence advances a whole ensemble at once:
`allocate_batch_arrays` consumes the unified padded pytree
(`repro.pipeline.ensemble_batch.EnsembleBatch`) plus a padded (Bp, Mp)
order array, realizes the ordered flow sequence as one stable gather of
the batch's canonical flow table (no re-extraction from instances), and
advances every instance's (rho, tau, lb) state with one loop over the
flow axis, the per-flow core selection vmapped across the ensemble axis.
The loop's trip count is a runtime scalar, the longest member's valid
flow count, so a padded flow axis (the streaming slot pool's arena is
about half empty) costs no steps past it and no recompile when the count
moves.  When the batch carries a `NamedSharding` (built with
``mesh=...``), the scan's inputs are placed with it and the program runs
SPMD across the member axis.  The padding mirrors the masking scheme of
`lp_terms_batch` / `solve_subgradient_batch`:

  * padded flow steps carry ``valid=False`` and update nothing (masked
    adds of 0.0 keep the carried f64 state bit-identical); those past
    the longest member's valid flows are not stepped at all;
  * padded cores start at a large finite lower bound (`PAD_LB`) and get a
    large inverse rate, so the argmin never selects them (finite, not inf,
    to keep ``0 * inf`` NaNs out of the candidate terms);
  * padded ports are simply never indexed (flow endpoints stay within each
    instance's real 2N ports);
  * padded members (sharding round-up) have no valid flows and no real
    cores — pure no-ops.

The scan carries every size, load and bound as the int64 bit pattern of
its double (locally enabled x64) and performs the NumPy oracle's
floating-point operations, in the same order, with the exact integer
arithmetic of `repro.pipeline.exact64` (XLA:TPU only emulates f64, not
to the last bit), so core choices, prefix port stats and prefix lower
bounds are **bit-identical** to `allocate` on every backend — asserted
per scheme and per flow table by `tests/test_pipeline.py`.  `allocate_batch` is the list-in/list-out
wrapper (build one `EnsembleBatch`, run the array form, materialize
`Allocation`s) kept for oracle tests and loop-path callers.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.allocation import Allocation
from repro.core.coflow import CoflowInstance, flows_of
from repro.pipeline.ensemble_batch import (
    PAD_LB,
    AllocationBatch,
    EnsembleBatch,
    build_ensemble_batch,
)
from repro.pipeline.exact64 import NEG_INF, ONE, add, from_bits, mul, to_bits
from repro.trace import count, span, to_host

__all__ = ["allocate_batch", "allocate_batch_arrays", "flow_sequence"]

# Historical alias (the sentinel now lives with the pytree builder).
_PAD_LB = PAD_LB


def flow_sequence(
    instance: CoflowInstance, order: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Flow table of one instance in allocation order.

    Returns (coflow, src, dst, size, ends) where the first four are the
    (F,) parallel arrays `allocate` would emit (coflows along `order`,
    flows largest-first within a coflow) and ``ends[pos]`` is the running
    flow count after the coflow at order position ``pos`` — the reference
    the batched gather (`EnsembleBatch.permute_flows`) is checked against.
    """
    ms, is_, js, ds = [], [], [], []
    ends = np.zeros(instance.num_coflows, dtype=np.int64)
    n = 0
    for pos, m in enumerate(np.asarray(order)):
        i_idx, j_idx, sizes = flows_of(instance.demands[m], largest_first=True)
        ms.append(np.full(i_idx.shape[0], m, dtype=np.int64))
        is_.append(i_idx)
        js.append(j_idx)
        ds.append(sizes)
        n += i_idx.shape[0]
        ends[pos] = n

    def cat(parts, dtype):
        if not parts:
            return np.zeros(0, dtype=dtype)
        return np.concatenate(parts).astype(dtype)

    return (
        cat(ms, np.int64),
        cat(is_, np.int64),
        cat(js, np.int64),
        cat(ds, np.float64),
        ends,
    )


#: Core recorded for every flow with ``valid=False``: no core.
NO_CORE = -1


@jax.jit
def _scan_all(
    steps, pi, pj, d, valid, inv_rates, delta, lb0, core_mask, rho0, tau0
):
    """Run the allocation recurrence for the whole padded ensemble.

    Shapes: steps () int32, pi/pj (B, F) int32 flat-port endpoints,
    d (B, F) sizes, valid (B, F) bool, inv_rates/lb0/core_mask (B, Kmax),
    delta (B,), rho0/tau0 (B, Kmax, Pmax).  Every real-valued input and
    output is the int64 bit pattern of a non-negative double
    (`repro.pipeline.exact64`).  Returns per-step core choices and
    real-core lb maxima plus the final (rho, tau) port stats.

    ``valid`` must be a prefix of every row (`EnsembleBatch.permute_flows`
    sorts invalid flows last), and ``steps`` at least the longest prefix:
    the loop runs ``steps`` flow steps, not F, so the flow arena's empty
    tail costs nothing.  ``steps`` is one traced scalar for the whole
    ensemble, so the loop stays unbatched under the member vmap; members
    with fewer valid flows step their invalid tail as masked no-ops.
    Past ``steps`` the lb maxima hold each member's final one (what a
    no-op step returns), and every invalid flow's core is `NO_CORE`.
    """

    def member(rho, tau, lb, i, j, dd, v, inv_rates, delta, core_mask):
        def bump(x, k, p, by):  # x[k, p] += by, rounded as NumPy rounds
            return x.at[k, p].set(add(x[k, p], by))

        # Candidate LB on every core if this flow lands there — the
        # NumPy oracle's expression and rounding, step for step.
        li = add(mul(add(rho[:, i], dd), inv_rates),
                 mul(add(tau[:, i], ONE), delta))
        lj = add(mul(add(rho[:, j], dd), inv_rates),
                 mul(add(tau[:, j], ONE), delta))
        cand = jnp.maximum(lb, jnp.maximum(li, lj))
        k = jnp.argmin(cand)
        dv = jnp.where(v, dd, 0)
        ov = jnp.where(v, ONE, 0)
        rho = bump(bump(rho, k, i, dv), k, j, dv)
        tau = bump(bump(tau, k, i, ov), k, j, ov)
        lb = lb.at[k].set(jnp.where(v, cand[k], lb[k]))
        lb_real = jnp.max(jnp.where(core_mask, lb, NEG_INF))
        return rho, tau, lb, k.astype(jnp.int32), lb_real

    place_all = jax.vmap(member)
    # Flow-major, as `lax.scan` lays out its per-step inputs and outputs.
    xs = (pi.T, pj.T, d.T, valid.T)
    B, F = valid.shape

    def step(t, carry):
        rho, tau, lb, ks, lbs = carry
        # An unsigned ``t``, as `lax.scan` counts: no negative-index select.
        i, j, dd, v = (jax.lax.dynamic_index_in_dim(x, t, 0, False)
                       for x in xs)
        rho, tau, lb, k, lb_real = place_all(
            rho, tau, lb, i, j, dd, v, inv_rates, delta, core_mask
        )
        ks = jax.lax.dynamic_update_index_in_dim(ks, k, t, 0)
        lbs = jax.lax.dynamic_update_index_in_dim(lbs, lb_real, t, 0)
        return rho, tau, lb, ks, lbs

    init = (
        rho0, tau0, lb0,
        jnp.full((F, B), NO_CORE, jnp.int32), jnp.zeros((F, B), lb0.dtype),
    )
    rho, tau, lb, ks, lbs = jax.lax.fori_loop(
        jnp.uint32(0), steps.astype(jnp.uint32), step, init
    )
    lb_last = jnp.max(jnp.where(core_mask, lb, NEG_INF), axis=1)
    ran = (jnp.arange(F) < steps)[:, None]
    lbs = jnp.where(ran, lbs, lb_last[None, :])
    ks = jnp.where(valid.T, ks, NO_CORE)
    return ks.T, lbs.T, rho, tau


def allocate_batch_arrays(
    ensemble: EnsembleBatch,
    orders: np.ndarray,
    include_tau: bool = True,
) -> AllocationBatch:
    """Greedy allocation of a whole `EnsembleBatch` along padded orders.

    ``orders`` is the (Bp, Mp) array an ordering stage's ``order_batch``
    produces (or `EnsembleBatch.pad_orders` of per-instance permutations).
    Returns the padded `AllocationBatch`; materialize per-instance
    `Allocation`s only at the end of the pipeline.  Bit-identical to
    ``[allocate(inst, order, include_tau) for ...]`` (see module
    docstring).
    """
    with span("alloc.prepare"):
        Bp, Fp = ensemble.flow_size.shape
        perm = ensemble.permute_flows(orders)
        take = lambda a: np.take_along_axis(a, perm, axis=1)  # noqa: E731
        coflow = take(ensemble.flow_coflow)
        src = take(ensemble.flow_src)
        dst = take(ensemble.flow_dst)
        size = take(ensemble.flow_size)
        pi = take(ensemble.flow_pi)
        pj = take(ensemble.flow_pj)
        valid = take(ensemble.flow_valid)
        ends = ensemble.prefix_ends(orders)

        Kp, Pp = ensemble.pad_cores, ensemble.pad_flat_ports
        delta = (
            ensemble.delta if include_tau else np.zeros_like(ensemble.delta)
        )
        lb0 = np.where(ensemble.core_mask, 0.0, PAD_LB)
        # Valid flows lead every row, so the longest row is all the
        # scan has to step; the padded length is what a full scan steps.
        steps = int(valid.sum(axis=1).max(initial=0))
        count("alloc.steps", steps)
        count("alloc.step_slots", Fp)
        # Members step in lockstep: the member-steps that carried a flow.
        count("alloc.members", Bp)
        count("alloc.member_steps", int(valid.sum()))

    if Fp == 0:
        # Nothing to place anywhere in the ensemble: zero prefix stats.
        ks = lbs = np.zeros((Bp, 0), dtype=np.int64)
        rho = tau = np.zeros((Bp, Kp, Pp), dtype=np.int64)
    else:
        with jax.enable_x64():
            from repro.launch.mesh import place

            with span("alloc.prepare"):
                zeros_kp = np.zeros((Bp, Kp, Pp), dtype=np.int64)
                put = lambda x: place(x, ensemble.sharding)  # noqa: E731
                args = (
                    np.int32(steps),
                    put(pi.astype(np.int32)), put(pj.astype(np.int32)),
                    put(to_bits(size)), put(valid),
                    put(to_bits(ensemble.inv_rates)), put(to_bits(delta)),
                    put(to_bits(lb0)), put(ensemble.core_mask),
                    put(zeros_kp), put(zeros_kp),
                )
            with span("alloc.wait"):
                ks, lbs, rho, tau = to_host(*_scan_all(*args))
                del args  # frees the inputs' device buffers in the span

    with span("alloc.unpack"):
        # lb starts all-zero, so before any flow lands the prefix LB is 0.
        prefix_lb = np.zeros(ends.shape)
        if Fp:
            prefix_lb = np.where(
                ends > 0,
                np.take_along_axis(
                    from_bits(lbs), np.maximum(ends - 1, 0), axis=1
                ),
                0.0,
            ).astype(np.float64)
        return AllocationBatch(
            order=np.asarray(orders), perm=perm, coflow=coflow, src=src,
            dst=dst, size=size, valid=valid, core=ks.astype(np.int64),
            rho_ports=from_bits(rho), tau_ports=from_bits(tau),
            prefix_lb=prefix_lb, ends=ends,
        )


def allocate_batch(
    instances: Sequence[CoflowInstance],
    orders: Sequence[np.ndarray],
    include_tau: bool = True,
) -> list[Allocation]:
    """Greedy allocation for a whole ensemble in one vectorized program.

    List-in/list-out wrapper over the array pipeline: builds one
    `EnsembleBatch`, runs `allocate_batch_arrays`, materializes.
    Equivalent to ``[allocate(inst, order, include_tau) for ...]`` with
    bit-identical results; instances may differ in every dimension
    (M, N, K, flow count, rates, delta).
    """
    instances = list(instances)
    if len(instances) != len(orders):
        raise ValueError("instances/orders length mismatch")
    if not instances:
        return []
    # Allocation never reads the LP solver inputs; skip packing them.
    ensemble = build_ensemble_batch(instances, with_lp_arrays=False)
    batch = allocate_batch_arrays(
        ensemble, ensemble.pad_orders(orders), include_tau=include_tau
    )
    return batch.materialize(ensemble)
