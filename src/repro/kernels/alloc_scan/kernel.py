"""Pallas TPU kernel: the whole allocation scan as one on-chip loop.

The allocation recurrence (`repro.pipeline.batch_alloc._scan_all`) steps
every member of an ensemble through its ordered flows: for each flow it
prices every core, picks the first cheapest, and bumps that core's port
stats and lower bound.  Written as a jnp ``fori_loop``, XLA:TPU runs each
step as ~70 separate small operations (every int64 operation is a pair of
32-bit ones, every indexed read or bump its own dynamic slice), and the
fixed cost of launching them sets the pace.  Here the loop runs inside one
kernel and the state never leaves VMEM:

  * **state on chip** — ``rho`` / ``tau`` (Kp, Bb, Pp) and ``lb`` (Kp, Bb,
    1) as uint32 hi/lo planes, held in the output blocks, whose index does
    not move along the sequential (``"arbitrary"``) grid axis over flow
    chunks; a ``"parallel"`` axis runs over blocks of Bb members (members
    on sublanes, ports on lanes, cores along the leading axis);
  * **dense, not indexed** — ``rho[:, i]`` is a masked lane reduction
    against an iota over ports, a bump is a select over the planes;
  * **exact doubles on uint32 halves** — `repro.pipeline.exact64`'s
    `add_halves` / `mul_halves`, the int64 oracle's steps on word pairs;
  * **per-step flows from the chunk block** — with one member, as scalars
    from SMEM; with several, as one lane of a VMEM block;
  * **a runtime trip count** — ``steps`` arrives by scalar prefetch;
    chunks at or past it do nothing and their index maps clamp to the last
    useful chunk (no DMA), and the last chunk's loop stops at ``steps``.

Each step writes its core choice and its real-core lb maximum into the
chunk's output block; entries past ``steps`` are left unwritten, and the
caller replaces them as the jnp loop's outputs are (`_scan_all`).  The
jnp loop is the oracle: the kernel equals it bit for bit in interpret
mode on a CPU (`tests/test_alloc_kernel.py`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANE, SUBLANE, round_up
from repro.pipeline.exact64 import (
    NEG_INF,
    ONE,
    add_halves,
    ge_halves,
    join,
    mul_halves,
    select_halves,
    split,
)

__all__ = ["alloc_scan_pallas", "member_block"]

#: Flow steps per grid step along the chunk axis: one lane tile.
CHUNK = LANE

#: Most members a block steps together: one sublane tile, so a per-core
#: value is Kp vregs and the step's live values stay in registers.
_MAX_BLOCK = SUBLANE

#: VMEM the pipelined blocks may take before the scoped limit is raised:
#: under the compiler's default scoped limit (16 MiB on a v5e).
_VMEM_BUDGET = 12 << 20

_HI_ONE = ONE >> 32
_HI_NEG_INF = (NEG_INF >> 32) & 0xFFFFFFFF


def member_block(num_members: int) -> int:
    """Members a block steps together: all of them up to one sublane
    tile, else a sublane tile (the member axis is padded to a multiple)."""
    return num_members if num_members <= _MAX_BLOCK else _MAX_BLOCK


def _vmem_bytes(bb: int, kp: int, pp: int) -> int:
    """VMEM of one grid step's blocks, each double-buffered."""
    rows = round_up(bb, SUBLANE)
    planes = (4 + 4) * kp * rows * round_up(pp, LANE)  # state in and out
    per_core = (7 + 2) * kp * rows * LANE  # core arrays, final lb
    per_step = (5 + 3) * rows * CHUNK  # flow chunk in, choices out
    return 2 * 4 * (planes + per_core + per_step)


def _kernel(steps_ref, flow_ref, core_ref, state0_ref,
            out_ref, state_ref, lb_ref):
    """One grid step: members block ``program_id(0)``, flow chunk
    ``program_id(1)``.

    flow (5, Bb, T): i, j, size hi, size lo, valid; core (7, Kp, Bb, 1):
    inv_rates hi, delta hi, inv_rates lo, delta lo, lb0 hi/lo, core_mask;
    state (4, Kp, Bb, Pp): rho hi, tau hi, rho lo, tau lo; out (3, Bb, T):
    core, lb maximum hi/lo; lb (2, Kp, Bb, 1).  Every array is int32; reals
    are the uint32 words of int64 double patterns.  Each exact operation of
    a step appears once, on operands stacked along leading axes (ports i
    and j, rho and tau): the body Mosaic compiles stays short.
    """
    kp, bb, pp = state_ref.shape[1:]
    u32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)  # noqa: E731
    i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)  # noqa: E731

    def halves(x):  # int32 planes, hi then lo along axis 0 -> uint32 pair
        n = x.shape[0] // 2
        return u32(x[:n]), u32(x[n:])

    def joined(x):  # the inverse: (hi, lo) -> int32 planes, hi then lo
        return jnp.concatenate([i32(x[0]), i32(x[1])])

    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = state0_ref[...]
        lb_ref[...] = core_ref[4:6]

    start = pl.program_id(1) * CHUNK
    steps = steps_ref[0]

    @pl.when(start < steps)
    def _run():
        core = core_ref[...]
        factor = halves(core[0:4])  # (2, Kp, Bb, 1): inv_rates, delta
        real = core[6] != 0
        one = (jnp.full((1, bb, 1), _HI_ONE, jnp.uint32),
               jnp.zeros((1, bb, 1), jnp.uint32))
        zero = jnp.zeros((1, bb, 1), jnp.uint32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (5, bb, CHUNK), 2)
        port = jax.lax.broadcasted_iota(jnp.int32, (4, kp, bb, pp), 3)
        core_id = jax.lax.broadcasted_iota(jnp.int32, (kp, bb, 1), 0)
        # Typed constants: under x64 a Python literal would trace as an
        # int64, which Mosaic cannot convert.
        nil = jnp.int32(0)

        def step(t, carry):
            # This step's flow, (5, Bb, 1): with one member, five scalars
            # from SMEM; else lane t of the chunk, a masked lane sum.
            if bb == 1:
                f = jnp.concatenate([
                    jnp.full((1, 1, 1), flow_ref[w, t], jnp.int32)
                    for w in range(5)
                ])
            else:
                f = jnp.sum(jnp.where(lane == t, flow_ref[...], nil), axis=2,
                            keepdims=True, dtype=jnp.int32)
            i, j = f[0:1], f[1:2]
            size = (u32(f[2:3]), u32(f[3:4]))
            v = f[4:5] != 0
            state = state_ref[...]
            # state[:, :, b, p[b]] at p = i and p = j: (4, 2, Kp, Bb, 1).
            ij = jnp.concatenate([i, j])[None, :, None]
            at = jnp.sum(jnp.where(port[:, None] == ij, state[:, None], nil),
                         axis=4, keepdims=True, dtype=jnp.int32)
            # The oracle's candidate lb on every core, at i and at j:
            # (rho + size) * inv_rates + (tau + 1) * delta.
            by = tuple(jnp.concatenate(w) for w in zip(size, one))  # (2,Bb,1)
            s = add_halves(halves(at),
                           tuple(w[:, None, None] for w in by))
            m = mul_halves(s, tuple(w[:, None] for w in factor))
            li_lj = add_halves((m[0][0], m[1][0]), (m[0][1], m[1][1]))
            li = (li_lj[0][0], li_lj[1][0])
            lj = (li_lj[0][1], li_lj[1][1])
            lb = (u32(lb_ref[0]), u32(lb_ref[1]))
            cand = select_halves(ge_halves(li, lj), li, lj)
            cand = select_halves(ge_halves(lb, cand), lb, cand)
            # First minimum over cores, as `jnp.argmin` takes it.
            best, k = (cand[0][0], cand[1][0]), jnp.zeros((bb, 1), jnp.int32)
            for c in range(1, kp):
                here = (cand[0][c], cand[1][c])
                less = ~ge_halves(here, best)
                best = select_halves(less, here, best)
                k = jnp.where(less, jnp.int32(c), k)
            on_k = core_id == k[None]
            # The four bumps, i before j as the oracle does them (an
            # invalid flow bumps by zero, which leaves a pattern as it is).
            by = tuple(jnp.where(v, w, zero) for w in by)
            old = functools.reduce(  # at[:, :, k]: (4, 2, Bb, 1)
                lambda acc, c: jnp.where(k == c, at[:, :, c], acc),
                range(1, kp), at[:, :, 0],
            )
            new_i = add_halves(halves(old[:, 0]), by)
            new_j = add_halves(
                select_halves(i == j, new_i, halves(old[:, 1])), by
            )
            hit_i = on_k[None] & (port == i[None])
            hit_j = on_k[None] & (port == j[None])
            state_ref[...] = jnp.where(
                hit_j, joined(new_j)[:, None],
                jnp.where(hit_i, joined(new_i)[:, None], state),
            )
            lb = select_halves(on_k & v, cand, lb)
            lb_ref[...] = jnp.stack([i32(lb[0]), i32(lb[1])])
            # Largest lb over the real cores; -inf where there is none.
            top = zero[0], zero[0]
            for c in range(kp):
                here = (lb[0][c], lb[1][c])
                take = real[c] & ge_halves(here, top)
                top = select_halves(take, here, top)
            any_real = functools.reduce(jnp.logical_or, list(real))
            top = (jnp.where(any_real, top[0], jnp.uint32(_HI_NEG_INF)),
                   jnp.where(any_real, top[1], jnp.uint32(0)))
            row = jnp.stack([k, i32(top[0]), i32(top[1])])  # (3, Bb, 1)
            now = lane[0:3] == t
            out_ref[...] = jnp.where(now, row, out_ref[...])
            return carry

        jax.lax.fori_loop(nil, jnp.minimum(steps - start, CHUNK), step, nil)


@functools.partial(jax.jit, static_argnames=("interpret",))
def alloc_scan_pallas(
    steps, pi, pj, d, valid, inv_rates, delta, lb0, core_mask, rho0, tau0,
    interpret: bool = False,
):
    """The allocation scan of `_scan_all`'s operands as one Pallas call.

    Takes `_scan_all`'s arguments (int64 patterns, under x64) and returns
    ``(ks, lbs, rho, tau, lb)``: per-step core choices (B, F) int32 and
    real-core lb maxima (B, F) int64, each valid for steps below
    ``steps`` only, and the final (B, Kp, Pp) port stats and (B, Kp) lb.
    ``interpret`` runs the Pallas interpreter (CPU tests).
    """
    B, F = valid.shape
    Kp, Pp = rho0.shape[1:]
    bb = member_block(B)
    Bp = round_up(B, bb)
    Fp = round_up(F, CHUNK)
    i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)  # noqa: E731

    def words(bits):
        hi, lo = split(bits)
        return i32(hi), i32(lo)

    def pad(x, *widths):
        return jnp.pad(x, [(0, Bp - B)] + [(0, w) for w in widths])

    # Flows, member-major: (5, Bp, Fp) (one member: (5, Fp), below).
    flow = jnp.stack([pi, pj, *words(d), valid.astype(jnp.int32)])
    flow = jnp.pad(flow, [(0, 0), (0, Bp - B), (0, Fp - F)])
    # Per-core columns: (7, Kp, Bp, 1); delta is the same on every core.
    per_core = lambda x: pad(x, 0).T[:, :, None]  # noqa: E731
    dlt = jnp.broadcast_to(delta[:, None], (B, Kp))
    (inv_hi, inv_lo), (dlt_hi, dlt_lo) = words(inv_rates), words(dlt)
    core = jnp.stack([
        *map(per_core, (inv_hi, dlt_hi, inv_lo, dlt_lo)),
        *map(per_core, words(lb0)), per_core(core_mask.astype(jnp.int32)),
    ])
    # Port stats, core-major: (4, Kp, Bp, Pp).
    planes = lambda x: pad(x, 0, 0).transpose(1, 0, 2)  # noqa: E731
    (rho_hi, rho_lo), (tau_hi, tau_lo) = words(rho0), words(tau0)
    state0 = jnp.stack(list(map(planes, (rho_hi, tau_hi, rho_lo, tau_lo))))

    def chunk(b, c, steps_ref):
        # Chunks at or past ``steps`` reuse the last useful chunk's block,
        # so the pipeline fetches nothing for them.
        n, one = jnp.int32(CHUNK), jnp.int32(1)  # typed, as in `members`
        last = jax.lax.div(steps_ref[0] + n - one, n) - one
        return jnp.int32(0), b, jnp.minimum(c, jnp.maximum(last, 0 * one))

    if bb == 1:  # one member: its flows as a 2-D SMEM block (5, T)
        flow, flow_spec = flow[:, 0], pl.BlockSpec(
            (5, CHUNK), lambda b, c, s: chunk(b, c, s)[::2],
            memory_space=pltpu.SMEM,
        )
    else:
        flow_spec = pl.BlockSpec((5, bb, CHUNK), chunk)

    def members(b, c, steps_ref):  # int32 zeros: Mosaic refuses mixed types
        z = jnp.int32(0)
        return z, z, b, z

    need = _vmem_bytes(bb, Kp, Pp)
    out, state, lb = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Bp // bb, Fp // CHUNK),
            in_specs=[
                flow_spec,
                pl.BlockSpec((7, Kp, bb, 1), members),
                pl.BlockSpec((4, Kp, bb, Pp), members),
            ],
            out_specs=[
                pl.BlockSpec((3, bb, CHUNK), chunk),
                pl.BlockSpec((4, Kp, bb, Pp), members),
                pl.BlockSpec((2, Kp, bb, 1), members),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((3, Bp, Fp), jnp.int32),
            jax.ShapeDtypeStruct((4, Kp, Bp, Pp), jnp.int32),
            jax.ShapeDtypeStruct((2, Kp, Bp, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=None if need <= _VMEM_BUDGET else need + (4 << 20),
        ),
        interpret=interpret,
        name="alloc_scan",
    )(jnp.reshape(steps, (1,)).astype(jnp.int32), flow, core, state0)

    def bits(hi, lo):
        u32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)  # noqa
        return join((u32(hi), u32(lo)))

    ks = out[0, :B, :F]
    lbs = bits(out[1, :B, :F], out[2, :B, :F])
    back = lambda x: x[:, :B].transpose(1, 0, 2)  # noqa: E731
    rho = bits(back(state[0]), back(state[2]))
    tau = bits(back(state[1]), back(state[3]))
    lb = bits(lb[0, :, :B, 0].T, lb[1, :, :B, 0].T)
    return ks, lbs, rho, tau, lb
