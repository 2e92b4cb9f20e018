"""Pure-jnp oracles for the event_resolve kernels.

One resolution round of the *reserving* discipline for a batch of
(instance, core) members — the array form of
`repro.core.circuit.resolve_event`, which the batched event-calendar
scheduler (`repro.pipeline.batch_circuit`) executes per event: a flow
establishes at ``t`` iff it is waiting (pending and released), both its
ports are idle, and it is the first waiting flow on each of them.

Two formulations, both oracle-checked against `resolve_event`:

  * `event_resolve_ref` — flow space: (G, F) endpoint arrays, the
    first-claimer pass as a per-port segment min over flows;
  * `pair_resolve_ref` — pair space: flows of one (ingress, egress) pair
    share both ports and execute sequentially, so only each pair's head
    (first waiting flow) can ever claim or start.  The round reduces the
    (G, N, N) matrix of claiming head ids: a pair starts iff it is idle
    and its claim is minimal along both its row (first claimer on the
    ingress) and its column (first claimer on the egress).  This is the
    per-round reduction of the device calendar
    (`repro.pipeline.batch_circuit`; `repro.core.circuit.
    resolve_event_pairs` is the NumPy twin).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def event_resolve_ref(
    src: jnp.ndarray,
    dst: jnp.ndarray,
    rel: jnp.ndarray,
    free_in: jnp.ndarray,
    free_out: jnp.ndarray,
    pending: jnp.ndarray,
    t: jnp.ndarray,
) -> jnp.ndarray:
    """Start mask of one reserving round per member.

    Args:
      src/dst: (G, F) int32 port endpoints, priority order.
      rel: (G, F) f32 release times.
      free_in/free_out: (G, N) f32 port free times.
      pending: (G, F) bool.
      t: (G,) f32 decision instants.

    Returns: (G, F) bool — flows that establish at ``t`` this round.
    """
    G, F = src.shape
    t_ = t[:, None]
    waiting = pending & (rel <= t_)
    idle = (
        waiting
        & (jnp.take_along_axis(free_in, src, axis=1) <= t_)
        & (jnp.take_along_axis(free_out, dst, axis=1) <= t_)
    )
    ar = jnp.arange(F, dtype=jnp.int32)
    claim = jnp.where(waiting, ar[None, :], F).astype(jnp.int32)

    def first(ports, idx, n):
        return jnp.full((n,), F, jnp.int32).at[ports].min(idx)

    n = free_in.shape[1]
    fi = jax.vmap(lambda s, c: first(s, c, n))(src, claim)
    fj = jax.vmap(lambda d, c: first(d, c, n))(dst, claim)
    return (
        idle
        & (ar[None, :] == jnp.take_along_axis(fi, src, axis=1))
        & (ar[None, :] == jnp.take_along_axis(fj, dst, axis=1))
    )


def pair_resolve_ref(claim: jnp.ndarray, idle: jnp.ndarray) -> jnp.ndarray:
    """Start mask of one pair-space round per member.

    Args:
      claim: (G, N, N) f32 — the claiming head flow id of each
        (ingress, egress) pair, or the F sentinel where no pair head
        claims (exact integers; ids stay < 2**24).
      idle: (G, N, N) bool — the pair may start now (a waiting head whose
        two ports are both free).

    Returns: (G, N, N) bool — pairs whose head establishes this round: the
    pair is idle and its claim is the row minimum (first claimer on its
    ingress port) and the column minimum (first claimer on its egress).
    """
    rowmin = jnp.min(claim, axis=2, keepdims=True)
    colmin = jnp.min(claim, axis=1, keepdims=True)
    return idle & (claim == rowmin) & (claim == colmin)
