"""Pallas TPU kernel: per-event idle / first-waiting reduction.

One resolution round of the reserving discipline for a whole batch of
(instance, core) members — the inner reduction of the batched event
calendar (`repro.pipeline.batch_circuit`).  The CPU/interpret path of the
scheduler fuses the same computation as scatter/gather jnp inside its
`while_loop`; this kernel is the TPU tiling of that round, expressed
scatter-free so it maps onto the VPU/MXU:

  * port membership as one-hot masks ``(F, N)`` built from a broadcasted
    iota against the (F, 1) endpoint column;
  * the idle test as a masked lane reduction of the port free times;
  * the first-waiting-per-port test via a strictly-lower-triangular
    ``(F, F) @ (F, N)`` matmul counting earlier claims on each port — a
    flow is blocked iff an earlier waiting flow claims one of its ports.

Grid: one program per member; each member's blocks are read from HBM
exactly once.  Validated against the jnp oracle (`ref.py`) in interpret
mode on CPU (`tests/test_kernels.py`); both kernels compile natively by
default, and interpret mode is only ever the caller's explicit choice.

Two kernels share this file:

  * `event_resolve_pallas` — the flow-space f32 prototype above, kept as
    an oracle-validated building block (each round scans O(F) flows and
    the (F, F) triangle matmul grows quadratically in flows);
  * `pair_resolve_pallas` — the production round reduction of the
    device calendar (`repro.pipeline.batch_circuit._run_calendar_pairs`),
    chosen on TPU and GPU: the host calendar's per-(ingress, egress)-pair
    layout, so one round reduces an (N, N) pair matrix instead of F
    flows.

The pair kernel's f64 story is *separation*, not emulation: CCT
bit-parity is the repo's correctness contract and every f64 time
comparison (release <= t, port-free <= t, the claim/idle masks) happens
outside the kernel as exact jnp f64 selections.  The kernel itself only
reduces small integer flow ids (min along rows and columns) carried in
f32 lanes — exact for ids < 2**24, which the calendar guards — so its
output is bit-identical to the f64 oracle by construction; no f64 tiles
or split-hi/lo arithmetic are needed.  Parity with the f64 flow-space
oracle is property-tested in `tests/test_kernels.py` (interpret mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANE, SUBLANE, pad_to, round_up

# Pad value for claim matrices: larger than any real flow id or the F
# sentinel (ids stay < 2**24), exactly representable in f32.
_CLAIM_PAD = float(1 << 30)


def _member_block(ndim: int):
    """Index map of a one-member block: grid step ``g`` -> ``(g, 0, ...)``.

    The zeros are int32 so the map lowers the same under x64 (the batched
    calendar calls the pair kernel inside ``jax.enable_x64``): Python int
    literals would trace as int64 there, and Mosaic refuses an index map
    returning mixed (i32, i64) block indices.
    """
    return lambda g: (g,) + (jnp.int32(0),) * (ndim - 1)


def _event_resolve_kernel(
    src_ref, dst_ref, rel_ref, mask_ref, free_in_ref, free_out_ref, t_ref,
    start_ref, *, f_pad: int, n_pad: int,
):
    t = t_ref[0, 0]
    src = src_ref[0]  # (Fp, 1) int32
    dst = dst_ref[0]
    ports = jax.lax.broadcasted_iota(jnp.int32, (f_pad, n_pad), 1)
    onehot_i = (src == ports).astype(jnp.float32)  # (Fp, Np)
    onehot_j = (dst == ports).astype(jnp.float32)
    waiting = mask_ref[0] * (rel_ref[0] <= t).astype(jnp.float32)  # (Fp, 1)
    free_i = jnp.sum(onehot_i * free_in_ref[...], axis=1, keepdims=True)
    free_j = jnp.sum(onehot_j * free_out_ref[...], axis=1, keepdims=True)
    idle = waiting * (free_i <= t) * (free_j <= t)
    # Earlier-claim counts per (flow, port): strict lower triangle over the
    # flow axis contracted against the claim masks.
    rows = jax.lax.broadcasted_iota(jnp.int32, (f_pad, f_pad), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (f_pad, f_pad), 1)
    tril = (rows > cols).astype(jnp.float32)
    prior_i = jax.lax.dot(
        tril, onehot_i * waiting, preferred_element_type=jnp.float32
    )
    prior_j = jax.lax.dot(
        tril, onehot_j * waiting, preferred_element_type=jnp.float32
    )
    blocked_i = jnp.sum(prior_i * onehot_i, axis=1, keepdims=True)
    blocked_j = jnp.sum(prior_j * onehot_j, axis=1, keepdims=True)
    start_ref[0] = idle * (blocked_i == 0) * (blocked_j == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def event_resolve_pallas(
    src: jnp.ndarray,
    dst: jnp.ndarray,
    rel: jnp.ndarray,
    mask: jnp.ndarray,
    free_in: jnp.ndarray,
    free_out: jnp.ndarray,
    t: jnp.ndarray,
    interpret: bool = False,
) -> jnp.ndarray:
    """(G, F) endpoints + (G, N) port state -> (G, F) f32 start mask.

    ``interpret`` runs the Pallas interpreter instead of compiling for the
    TPU (CPU tests).
    """
    G, F = src.shape
    # Lane-align both the flow axis (contracted through the (Fp, Fp)
    # triangle) and the port axis; padded flows carry mask 0 and padded
    # ports are never claimed, so both are inert.
    src_p, _ = pad_to(src.astype(jnp.int32)[:, :, None], 1, LANE, value=0)
    dst_p, _ = pad_to(dst.astype(jnp.int32)[:, :, None], 1, LANE, value=0)
    rel_p, _ = pad_to(rel.astype(jnp.float32)[:, :, None], 1, LANE)
    mask_p, _ = pad_to(mask.astype(jnp.float32)[:, :, None], 1, LANE)
    fin_p, _ = pad_to(free_in.astype(jnp.float32), 1, LANE)
    fout_p, _ = pad_to(free_out.astype(jnp.float32), 1, LANE)
    f_pad, n_pad = src_p.shape[1], fin_p.shape[1]

    start = pl.pallas_call(
        functools.partial(
            _event_resolve_kernel, f_pad=f_pad, n_pad=n_pad
        ),
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, f_pad, 1), _member_block(3)),
            pl.BlockSpec((1, f_pad, 1), _member_block(3)),
            pl.BlockSpec((1, f_pad, 1), _member_block(3)),
            pl.BlockSpec((1, f_pad, 1), _member_block(3)),
            pl.BlockSpec((1, n_pad), _member_block(2)),
            pl.BlockSpec((1, n_pad), _member_block(2)),
            pl.BlockSpec((1, 1), _member_block(2)),
        ],
        out_specs=pl.BlockSpec((1, f_pad, 1), _member_block(3)),
        out_shape=jax.ShapeDtypeStruct((G, f_pad, 1), jnp.float32),
        interpret=interpret,
        name="event_resolve",
    )(src_p, dst_p, rel_p, mask_p, fin_p, fout_p, t[:, None].astype(jnp.float32))
    return start[:, :F, 0]


def pair_block_cells(num_ports: int) -> int:
    """Cells of the block `pair_resolve_pallas` reduces for one member of
    ``num_ports`` x ``num_ports`` pairs: sublane- and lane-padded."""
    return round_up(num_ports, SUBLANE) * round_up(num_ports, LANE)


def _pair_resolve_kernel(claim_ref, idle_ref, start_ref):
    claim = claim_ref[0]  # (Ns, Nl) f32: head flow id per pair, or sentinel
    idle = idle_ref[0]
    rowmin = jnp.min(claim, axis=1, keepdims=True)  # first claimer per ingress
    colmin = jnp.min(claim, axis=0, keepdims=True)  # first claimer per egress
    start_ref[0] = (
        idle
        * (claim == rowmin).astype(jnp.float32)
        * (claim == colmin).astype(jnp.float32)
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_resolve_pallas(
    claim: jnp.ndarray,
    idle: jnp.ndarray,
    interpret: bool = False,
) -> jnp.ndarray:
    """(G, N, N) f32 pair claims + idle mask -> (G, N, N) f32 start mask.

    ``claim[g, i, j]`` is the claiming head flow id of pair (ingress i,
    egress j) — or any value >= the F sentinel where no head claims; flow
    ids are unique per member, so a pair starts iff it is idle and its
    claim equals both its row minimum and its column minimum.  Padded
    rows/columns carry ``idle == 0`` and a claim above every real id, so
    they neither start nor disturb any minimum.  ``interpret`` runs the
    Pallas interpreter instead of compiling for the TPU (CPU tests).
    """
    G, N, _ = claim.shape
    claim_p, _ = pad_to(claim.astype(jnp.float32), 1, SUBLANE, value=_CLAIM_PAD)
    claim_p, _ = pad_to(claim_p, 2, LANE, value=_CLAIM_PAD)
    idle_p, _ = pad_to(idle.astype(jnp.float32), 1, SUBLANE)
    idle_p, _ = pad_to(idle_p, 2, LANE)
    n_sub, n_lane = claim_p.shape[1], claim_p.shape[2]

    start = pl.pallas_call(
        _pair_resolve_kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, n_sub, n_lane), _member_block(3)),
            pl.BlockSpec((1, n_sub, n_lane), _member_block(3)),
        ],
        out_specs=pl.BlockSpec((1, n_sub, n_lane), _member_block(3)),
        out_shape=jax.ShapeDtypeStruct((G, n_sub, n_lane), jnp.float32),
        interpret=interpret,
        name="pair_resolve",
    )(claim_p, idle_p)
    return start[:, :N, :N]
