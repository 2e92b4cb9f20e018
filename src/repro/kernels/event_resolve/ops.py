"""Jit'd public wrappers for the event_resolve kernels.

Every operand is validated up front — a mis-shaped or mis-typed array
otherwise surfaces deep inside `pallas_call` lowering as an opaque
block-spec error.  Violations raise `EventResolveArgumentError` (a
`TypeError`) naming the offending operand and what was expected.
Validation only touches ``shape``/``dtype``, so it works identically on
NumPy arrays, device arrays and tracers (the batched calendar calls
`pair_resolve` inside a jitted `while_loop`).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.event_resolve.kernel import (
    event_resolve_pallas,
    pair_resolve_pallas,
)
from repro.kernels.event_resolve.ref import event_resolve_ref, pair_resolve_ref

__all__ = [
    "EventResolveArgumentError",
    "event_resolve",
    "event_resolve_ref",
    "pair_resolve",
    "pair_resolve_ref",
]

# dtype.kind codes: b=bool, i/u=integer, f=float.
_KIND_NAMES = {"b": "bool", "iu": "integer", "f": "float"}


class EventResolveArgumentError(TypeError):
    """An event_resolve / pair_resolve operand has the wrong shape or dtype."""


def _check(fn: str, name: str, x, kinds: str, ndim: int):
    """Array-ness, rank and dtype-kind check; returns the operand's shape."""
    if not hasattr(x, "shape") or not hasattr(x, "dtype"):
        raise EventResolveArgumentError(
            f"{fn}: operand {name!r} must be an array, got "
            f"{type(x).__name__}"
        )
    shape = tuple(x.shape)
    if len(shape) != ndim:
        raise EventResolveArgumentError(
            f"{fn}: operand {name!r} must be {ndim}-D, got shape {shape}"
        )
    if jnp.dtype(x.dtype).kind not in kinds:
        raise EventResolveArgumentError(
            f"{fn}: operand {name!r} must be {_KIND_NAMES[kinds]}, got "
            f"dtype {jnp.dtype(x.dtype).name}"
        )
    return shape


def _check_shape(fn: str, name: str, got: tuple, want: tuple, why: str):
    if got != want:
        raise EventResolveArgumentError(
            f"{fn}: operand {name!r} has shape {got}, expected {want} ({why})"
        )


def _validate_event_resolve(src, dst, rel, free_in, free_out, pending, t):
    fn = "event_resolve"
    G, F = _check(fn, "src", src, "iu", 2)
    _check_shape(fn, "dst", _check(fn, "dst", dst, "iu", 2), (G, F), "src")
    _check_shape(fn, "rel", _check(fn, "rel", rel, "f", 2), (G, F), "src")
    _check_shape(
        fn, "pending", _check(fn, "pending", pending, "b", 2), (G, F), "src"
    )
    fin = _check(fn, "free_in", free_in, "f", 2)
    if fin[0] != G:
        raise EventResolveArgumentError(
            f"{fn}: operand 'free_in' has {fin[0]} members (shape {fin}), "
            f"expected {G} (src)"
        )
    _check_shape(
        fn, "free_out", _check(fn, "free_out", free_out, "f", 2), fin,
        "free_in",
    )
    _check_shape(fn, "t", _check(fn, "t", t, "f", 1), (G,), "one per member")


def _validate_pair_resolve(claim, idle):
    fn = "pair_resolve"
    shape = _check(fn, "claim", claim, "f", 3)
    if shape[1] != shape[2]:
        raise EventResolveArgumentError(
            f"{fn}: operand 'claim' must be square over the port axes, "
            f"got shape {shape}"
        )
    _check_shape(fn, "idle", _check(fn, "idle", idle, "b", 3), shape, "claim")


def event_resolve(
    src: jnp.ndarray,
    dst: jnp.ndarray,
    rel: jnp.ndarray,
    free_in: jnp.ndarray,
    free_out: jnp.ndarray,
    pending: jnp.ndarray,
    t: jnp.ndarray,
    use_kernel: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    """Reserving-round start mask (G, F) bool; Pallas kernel or jnp oracle.

    ``interpret`` runs the kernel in the Pallas interpreter (CPU tests).
    """
    _validate_event_resolve(src, dst, rel, free_in, free_out, pending, t)
    if use_kernel:
        out = event_resolve_pallas(
            src, dst, rel, pending.astype(jnp.float32), free_in, free_out, t,
            interpret=interpret,
        )
        return out > 0.5
    return event_resolve_ref(src, dst, rel, free_in, free_out, pending, t)


def pair_resolve(
    claim: jnp.ndarray,
    idle: jnp.ndarray,
    use_kernel: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    """Start mask of one pair-space resolution round, (G, N, N) bool.

    ``claim`` carries each (ingress, egress) pair's claiming head flow id
    in f32 (exact for ids < 2**24, with F as the no-claimant sentinel);
    ``idle`` whether the pair may start now.  A pair starts iff it is idle
    and its claim is minimal along both its row (first claimer on the
    ingress port) and its column (first claimer on the egress port) —
    `repro.core.circuit.resolve_event`'s first-claimer pass reduced to
    O(N^2) pair space.  All f64 time comparisons stay outside (exact jnp
    selections in the batched calendar), so kernel and oracle agree with
    the f64 reference bit for bit.  ``use_kernel`` compiles the Pallas
    kernel for the TPU; ``interpret`` runs it in the Pallas interpreter
    instead (CPU tests).
    """
    _validate_pair_resolve(claim, idle)
    if use_kernel:
        return pair_resolve_pallas(claim, idle, interpret=interpret) > 0.5
    return pair_resolve_ref(claim, idle)
