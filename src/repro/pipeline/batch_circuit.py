"""Ensemble-batched intra-core circuit scheduling (Alg. 1 Lines 16-30, JAX).

The NumPy reference `repro.core.circuit.schedule_core` walks one core's
event calendar in a Python loop: at each decision instant it resolves the
event with the array-form primitive `resolve_event` (idle test + first-
waiting-per-port reduction), then advances to the next release or
port-free time.  After PR 3 batched allocation, this per-(instance, core)
loop became the dominant post-LP cost of every figure sweep.

Here the identical event calendar executes for the whole flattened
(ensemble x core) axis at once.  Each member g is one (instance, core)
pair with its flows padded to a shared length Fmax and its ports to
Nmax, and every round performs one resolution of `resolve_event` — the
same first-occurrence start set for both disciplines (reserving claims =
waiting flows, greedy claims = idle flows) — fused, when no other round
at this instant is possible, with one clock advance to the next event.
Flows of one (ingress, egress) pair share both ports and execute
sequentially, so only each pair's head (first waiting flow) can ever
claim or start, and a round is a reduction over the (G, Nmax, Nmax)
pair matrix instead of the flow axis.

The platform picks one of two executors (`_engine`), and nothing
overrides that choice:

  * ``"kernel"`` — the device calendar, on TPU and GPU
    (`_run_calendar_pairs_impl`): ONE lockstep `lax.while_loop` over the
    whole (G, ...) batch whose fused round (claim -> start -> clock
    advance) is a single dispatch with donated calendar buffers.  Its
    state is a pair-major flow slab (G, Dp, P = Nmax^2), Dp the most
    flows on one pair, so a round is dense passes over G·Dp·P cells and
    never a gather or scatter by index; the per-round reduction is the
    `repro.kernels.event_resolve.pair_resolve` Pallas kernel, compiled
    natively on TPU (the jnp pair oracle on other backends, warned once).
  * ``"wide"`` — the host calendar, on a CPU (`_run_calendar_wide`): the
    same rounds lockstep in NumPy, with per-pair head pointers.

`repro.core.circuit.schedule_core` is the oracle both are fuzz-checked
against, bit for bit (`tests/test_batch_circuit.py`).  Tests that must
run the device calendar on a CPU monkeypatch `_engine` (and
`_PAIR_KERNEL_INTERPRET` for the Pallas interpreter).

The calendar is bounded: every flow contributes at most a handful of
rounds and every advance lands on a distinct release or port-free value
(at most F each), so ``3 * Fmax + 4`` iterations always suffice and the
`while_loop` is compile-time bounded.

Padding semantics mirror `batch_alloc`:

  * padded flows start with ``pending=False``, sort into a sentinel pair
    past every real pair, and can never claim, start, or contribute
    event times;
  * padded members (bucket rounding) have no pending flows and finish on
    iteration zero;
  * padded ports are never indexed by real flows.

All times are the int64 bit patterns of non-negative doubles (locally
enabled x64; `repro.pipeline.exact64`) and the per-round operations are
pure selections (compares, min/max on the patterns) plus ``t + dur``, an
exact IEEE addition in integer arithmetic (XLA:TPU only emulates f64,
not to the last bit), with ``dur`` precomputed on the host exactly as
the oracle's ``delta + size / rate``; so establishment and completion
times are **bit-identical** to `schedule_core` on both disciplines and
every backend.

Shapes are rounded up to small quanta so repeated sweeps, schemes and
disciplines over similar ensembles reuse one compiled program per padded
bucket instead of recompiling per call.
"""

from __future__ import annotations

import functools
import warnings
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.allocation import Allocation
from repro.core.circuit import NOT_SCHEDULED, CoreSchedule
from repro.core.coflow import CoflowInstance
from repro.core.validate import ccts_from_schedules
from repro.pipeline.ensemble_batch import AllocationBatch, EnsembleBatch
from repro.pipeline.exact64 import INF, NEG_INF, add, from_bits, to_bits
from repro.trace import count, span, to_host

__all__ = [
    "schedule_batch",
    "schedule_batch_arrays",
    "cct_batch_arrays",
    "member_tables",
    "event_bound",
    "lower_calendar",
]

#: Test hook: run the kernel engine's Pallas round in the Pallas
#: interpreter, so CPU tests execute the program the TPU compiles.
_PAIR_KERNEL_INTERPRET = False

#: The claim matrix carries flow ids in f32 lanes: exact below 2**24.
_MAX_KERNEL_FLOWS = 1 << 24

_KERNEL_FALLBACK_WARNED = False

#: `NOT_SCHEDULED` as the device programs carry it (an int64 pattern).
_UNSCHEDULED = int(np.float64(NOT_SCHEDULED).view(np.int64))

# Bucket quanta: flows, ports and members round up to these so that
# near-shaped ensembles (e.g. the same sweep under both disciplines, or
# schemes sharing an allocation) hit one compiled program per bucket.
_F_QUANTUM = 16
_N_QUANTUM = 4
_G_QUANTUM = 8
#: The kernel engine's flow slab is a power of two deep, at least this
#: (one sublane tile of 32-bit values on a TPU).
_D_FLOOR = 8


def event_bound(num_flows: int) -> int:
    """Compile-time iteration bound of the padded event calendar.

    At most ``num_flows`` rounds start flows (each starts >= 1), and every
    no-start round advances the clock to a new event value drawn from the
    <= F distinct releases plus <= F port-free (completion) times.  (The
    wide CPU engine may additionally stop at each of the <= F release
    instants themselves, so it budgets one more F.)
    """
    return 3 * num_flows + 4


def _round_up(n: int, q: int) -> int:
    return -(-max(n, 1) // q) * q


def member_tables(
    instance: CoflowInstance, alloc: Allocation, order: np.ndarray
) -> list[dict]:
    """Per-core flow tables of one instance, in scheduling priority order.

    Returns one dict per core with the (F_k,) arrays `schedule_core` would
    sort internally — coflow/src/dst/size plus the derived ``rel`` and
    ``dur`` vectors — so the batched calendar consumes exactly the
    oracle's inputs (and its output arrays line up position for position).
    """
    from repro.core.scheduler import _flow_priorities

    M, K = instance.num_coflows, instance.num_cores
    prio = _flow_priorities(alloc, order, M)
    out = []
    for k in range(K):
        sel = alloc.core == k
        o = np.argsort(prio[sel], kind="stable")
        coflow = alloc.coflow[sel][o]
        size = alloc.size[sel][o]
        rate = float(instance.rates[k])
        out.append(
            dict(
                coflow=coflow,
                src=alloc.src[sel][o],
                dst=alloc.dst[sel][o],
                size=size,
                rel=instance.releases[coflow],
                dur=instance.delta + size / rate,
                rate=rate,
            )
        )
    return out


def _pair_segments(keys: np.ndarray, num_pairs: int):
    """Pair-sorted layout of one bucket for the kernel engine's slab.

    ``keys`` (G, Fmax) holds each flow's pair ``src * Nmax + dst``
    (``num_pairs`` for padded flows, which sort last).  Returns ``perm``
    (G, Fmax) i32 — the stable sort by pair, so a pair's flows keep their
    priority order — and, per sorted position, ``pair`` (G, Fmax) its
    pair and ``depth`` (G, Fmax) its place among its pair's flows: the
    slab cell ``(g, depth, pair)`` of `_run_calendar_pairs_impl`
    (``depth`` -1 for a padded flow).
    """
    G, F = keys.shape
    perm = np.argsort(keys, axis=1, kind="stable").astype(np.int32)
    pair = np.take_along_axis(keys, perm, axis=1)
    pos = np.arange(F)
    new = np.ones((G, F), dtype=bool)
    new[:, 1:] = pair[:, 1:] != pair[:, :-1]
    first = np.maximum.accumulate(np.where(new, pos, 0), axis=1)
    depth = np.where(pair < num_pairs, pos - first, -1)
    return perm, pair, depth


def _slab_depth(depth: np.ndarray) -> int:
    """Slab depth Dp of a bucket: its most flows on one pair of one
    member, rounded up to a power of two, at least `_D_FLOOR`."""
    need = int(depth.max(initial=0)) + 1
    return max(_D_FLOOR, 1 << (need - 1).bit_length())


def _pair_slab(rel, dur, pending, keys, num_pairs):
    """The kernel engine's inputs from a padded bucket.

    ``rel`` / ``dur`` (G, Fmax) i64 patterns, ``pending`` and the pair
    ``keys`` of `_pair_segments`, in flow order.  Returns ``perm`` (the
    pair-sorted order of the outputs), the (G, Dp, P) slabs of rel, dur,
    pending and priority ids (flow ``perm[g, i]`` at cell ``(g,
    depth[g, i], pair[g, i])``; empty cells not pending) and ``slot``
    (G, Fmax) i32, each sorted position's flat cell ``depth * P + pair``
    (-1 for a padded flow).
    """
    perm, pair, depth = _pair_segments(keys, num_pairs)
    G, F = perm.shape
    P = num_pairs
    D = _slab_depth(depth)
    valid = depth >= 0
    slot = np.where(valid, depth * P + pair, -1).astype(np.int32)
    rows = np.broadcast_to(np.arange(G)[:, None], (G, F))[valid]
    cells = slot[valid]

    def slab(a, fill):  # ``a`` in pair-sorted order
        out = np.full((G, D * P), fill, dtype=a.dtype)
        out[rows, cells] = a[valid]
        return out.reshape(G, D, P)

    take = lambda a: np.take_along_axis(a, perm, axis=1)  # noqa: E731
    return (
        perm, slab(take(rel), 0), slab(take(dur), 0),
        slab(take(pending), False), slab(perm, F), slot,
    )


def _unsort(a: np.ndarray, perm: np.ndarray) -> np.ndarray:
    """Pair-sorted (G, Fmax) outputs back to flow order."""
    out = np.empty_like(a)
    np.put_along_axis(out, perm, a, axis=1)
    return out


def _run_calendar_pairs_impl(
    rel, dur, pending0, free0, ids, slot,
    reserving, bound, use_kernel, interpret=False,
):
    """The device calendar: one lockstep pair-space calendar.

    Only each pair's head (first waiting flow) can ever claim or start,
    so the whole batch advances through ONE `lax.while_loop` whose round
    body is a single fused dispatch (claim -> `pair_resolve` -> start
    writes -> clock advance) over (G, P = Nmax^2) pair state.

    The round state is a pair-major flow slab (G, Dp, P): flow ``d`` of
    pair ``p`` in priority order sits at cell ``(g, d, p)``, pairs on the
    lanes, and a cell without a flow is never pending.  Every pass of a
    round is dense — elementwise ops and reductions over the depth axis,
    no gather or scatter by index, which on a TPU v5e costs 4-10 ns per
    index against a fraction of a nanosecond per dense cell:

      * heads are stateless: each pair's head depth is the least waiting
        depth (no head-rewind bookkeeping at release crossings), and its
        priority id and duration are read through the one-hot of that
        depth;
      * the round reduction — idle & row-first & col-first over the
        (G, N, N) claim matrix — is the `repro.kernels.event_resolve.
        pair_resolve` Pallas kernel when ``use_kernel`` (compiled for the
        TPU, or run by the Pallas interpreter with ``interpret``), else
        its jnp oracle; both reduce exact integer ids;
      * started heads are written through the same one-hot; completions
        are ``establish + dur`` after the loop;
      * the next event time is the least of (a) the post-round free time
        of each pair that still has a waiting flow, where later than t,
        and (b) the least pending release later than t.  That is
        `schedule_core`'s next event, or a release instant before it at
        which every pending flow's earliest start is still later — a
        round that starts nothing, as the wide engine's release stops.

    Every time comparison stays in exact selections on double patterns,
    so CCTs remain bit-identical to `schedule_core`.

    Shapes: rel/dur (G, Dp, P) i64 double patterns, pending0 (G, Dp, P)
    bool and ids (G, Dp, P) i32 (each flow's priority id), the slab of
    `_pair_slab`; free0 (G, Nmax) i64 zeros; slot (G, Fmax) i32 — the
    slab cell ``d * P + p`` of each pair-sorted flow position (-1 for a
    padded flow).  Returns (establish, complete) (G, Fmax) i64 patterns
    in pair-sorted order (one gather of ``slot`` a call), per-member
    ``unfinished`` / ``stalled`` flags (bound exhausted / no event time
    could advance the clock — both impossible for well-formed inputs,
    checked on host), the loop's round count and each member's (G,) i32
    rounds: the rounds in which it still had a pending flow (its own
    calendar's length; the lockstep batch runs the largest member's).
    """
    from repro.kernels.event_resolve import pair_resolve

    G, D, P = rel.shape
    N = free0.shape[1]
    F = slot.shape[1]
    depth = jnp.arange(D, dtype=jnp.int32)[None, :, None]

    def ports(free_in, free_out):
        # Each pair's ingress and egress free times, (G, P) each: pair
        # (i, j) sits at i * N + j, so these are broadcasts, not gathers.
        return (
            jnp.broadcast_to(free_in[:, :, None], (G, N, N)).reshape(G, P),
            jnp.broadcast_to(free_out[:, None, :], (G, N, N)).reshape(G, P),
        )

    def cond(carry):
        _, _, _, pending, _, it, stalled, _ = carry
        return jnp.any(pending & ~stalled[:, None, None]) & (it < bound)

    def body(carry):
        free_in, free_out, est, pending, t, it, stalled, rounds = carry
        t_ = t[:, None]
        t3 = t[:, None, None]
        waiting = pending & (rel <= t3) & ~stalled[:, None, None]
        # Pair heads: the least waiting depth of each pair (D for none).
        dh = jnp.min(jnp.where(waiting, depth, D), axis=1)
        has = dh < D
        head = depth == dh[:, None, :]
        hid = jnp.min(jnp.where(head, ids, F), axis=1)
        dur_p = jnp.max(jnp.where(head, dur, 0), axis=1)
        fi, fo = ports(free_in, free_out)
        idle = has & (fi <= t_) & (fo <= t_)
        claim = has if reserving else idle
        claimf = jnp.where(claim, hid, F).astype(jnp.float32)
        startp = pair_resolve(
            claimf.reshape(G, N, N),
            idle.reshape(G, N, N),
            use_kernel=use_kernel,
            interpret=interpret,
        ).reshape(G, P)
        started = head & startp[:, None, :]
        est = jnp.where(started, t3, est)
        pending = pending & ~started
        # Port frees via (G, N, N) row/column max reductions — at most one
        # pair per row/column starts, so the max picks its completion.
        ev = jnp.where(startp, add(t_, dur_p), NEG_INF).reshape(G, N, N)
        sm = startp.reshape(G, N, N)
        free_in = jnp.where(sm.any(2), ev.max(2), free_in)
        free_out = jnp.where(sm.any(1), ev.max(1), free_out)
        # Advance unless another round at this t is possible: a
        # zero-duration start chains its pair's next flow, and (greedy) an
        # idle-but-blocked pair may start once its blocker started.
        chained = jnp.any(startp & (dur_p == 0), axis=1)
        if reserving:
            more = chained
        else:
            more = chained | jnp.any(idle & ~startp, axis=1)
        advance = ~more
        # A pair still waits after the round unless it started its only
        # waiting flow: a waiting flow deeper than a started head.
        after = jnp.any(waiting & (depth > dh[:, None, :]), axis=1)
        still = has & (~startp | after)
        pfree = jnp.maximum(*ports(free_in, free_out))
        t_next = jnp.minimum(
            jnp.min(jnp.where(still & (pfree > t_), pfree, INF), axis=1),
            jnp.min(jnp.where(pending & (rel > t3), rel, INF), axis=(1, 2)),
        )
        alive = jnp.any(pending, axis=(1, 2))
        stall = advance & alive & (t_next == INF) & ~stalled
        t = jnp.where(advance & (t_next < INF) & ~stalled, t_next, t)
        stalled = stalled | stall
        # A member left with a pending flow takes part in the next round.
        rounds = rounds + (alive & ~stalled).astype(jnp.int32)
        return free_in, free_out, est, pending, t, it + 1, stalled, rounds

    init = (
        free0,
        free0,
        jnp.full((G, D, P), _UNSCHEDULED, rel.dtype),
        pending0,
        jnp.min(jnp.where(pending0, rel, INF), axis=(1, 2)),
        jnp.int32(0),
        jnp.zeros((G,), bool),
        jnp.any(pending0, axis=(1, 2)).astype(jnp.int32),
    )
    out = jax.lax.while_loop(cond, body, init)
    _, _, est, pending, _, it, stalled, rounds = out
    comp = jnp.where(est != _UNSCHEDULED, add(est, dur), _UNSCHEDULED)
    # Back to pair-sorted flow order: one gather of both times per flow.
    times = jnp.stack([est, comp], axis=-1).reshape(G, D * P, 2)
    flows = jax.vmap(lambda x, i: x[i])(times, jnp.maximum(slot, 0))
    flows = jnp.where((slot >= 0)[..., None], flows, _UNSCHEDULED)
    unfinished = jnp.any(pending, axis=(1, 2))
    # A member still live when the bound cut the loop was counted for a
    # round that never ran.
    rounds = rounds - (unfinished & ~stalled).astype(jnp.int32)
    return flows[..., 0], flows[..., 1], unfinished, stalled, it, rounds


_PAIR_STATICS = ("reserving", "bound", "use_kernel", "interpret")
_run_calendar_pairs = jax.jit(
    _run_calendar_pairs_impl, static_argnames=_PAIR_STATICS
)
# Donated variant for accelerator backends: the round's big carry
# buffers alias their inputs so each fused dispatch updates in place (CPU
# ignores donation with a UserWarning, so it gets the plain jit).
_run_calendar_pairs_donated = jax.jit(
    _run_calendar_pairs_impl,
    static_argnames=_PAIR_STATICS,
    donate_argnames=("pending0", "free0"),
)


@functools.lru_cache(maxsize=None)
def _run_calendar_pairs_sharded(
    mesh, reserving, bound, use_kernel, interpret=False
):
    """The device calendar of a bucket sharded over ``data``.

    The compiler cannot partition a Mosaic kernel, and members are
    independent, so every device steps its own members' lockstep loop:
    one `jax.shard_map` over the member axis, no cross-device traffic.
    """
    spec = jax.sharding.PartitionSpec("data")

    def body(*args):
        *out, it, rounds = _run_calendar_pairs_impl(
            *args, reserving=reserving, bound=bound, use_kernel=use_kernel,
            interpret=interpret,
        )
        # Each device's loop count, one per shard (the host takes the max).
        return (*out, it[None], rounds)

    # check_vma=False: the Pallas call's output shape declares no
    # per-axis variance, and every value here is per-member anyway.
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
        )
    )


def _run_calendar_wide(
    src, dst, rel, dur, valid, num_ports, reserving, bound, labels=None
):
    """CPU execution of the same padded event calendar, lockstep in NumPy.

    XLA:CPU pays milliseconds per `while_loop` iteration at sweep sizes
    (serial gathers, carry copies), so on hosts the calendar runs here:
    the identical round/advance semantics, restructured around per-port-
    *pair* head pointers so one round costs O(N^2) instead of O(F) per
    member — flows of one (ingress, egress) pair share both ports, hence
    execute sequentially, hence only each pair's first waiting flow (its
    head) can ever claim or start.  Rounds evaluate the (G, N, N)
    candidate matrix (row/column minima reproduce `resolve_event`'s
    first-claimer-per-port pass exactly); heads advance past started and
    not-yet-released flows and rewind when a release lands before them.
    The clock may additionally stop at release instants whose flows then
    turn out blocked — no-op rounds that leave the schedule untouched —
    so ``bound`` carries one extra F of slack over `event_bound`.

    Members drop out of the lockstep batch as they finish.  Identical
    f64 selections as `schedule_core`: bit-exact.
    Returns (establish, complete) (G, F), the lockstep round count and
    each member's (G,) rounds (those it started with a pending flow).
    """
    G, F = src.shape
    N = int(num_ports)
    P = N * N
    NOT = NOT_SCHEDULED
    out_est = np.full((G, F), NOT)
    out_comp = np.full((G, F), NOT)
    member_rounds = np.zeros(G, dtype=np.int64)
    if G == 0 or F == 0:
        return out_est, out_comp, 0, member_rounds

    pairid = np.where(valid, src.astype(np.int64) * N + dst, P)
    psort = np.argsort(pairid, axis=1, kind="stable")
    keys = np.take_along_axis(pairid, psort, 1)
    pos = np.empty((G, F), dtype=np.int64)
    np.put_along_axis(
        pos, psort, np.broadcast_to(np.arange(F), (G, F)), 1
    )
    pairstart = np.empty((G, P), dtype=np.int64)
    pairend = np.empty((G, P), dtype=np.int64)
    ports = np.arange(P)
    for g in range(G):
        pairstart[g] = np.searchsorted(keys[g], ports, side="left")
        pairend[g] = np.searchsorted(keys[g], ports, side="right")
    # Release calendar per member: flows grouped by release instant; the
    # t0 group needs no rewind (heads start at the segment fronts).
    groups: list[list] = []
    t0 = np.empty(G)
    for g in range(G):
        fids = np.nonzero(valid[g])[0]
        if fids.size == 0:  # quantum-padded member: drops out at entry
            groups.append([])
            t0[g] = np.inf
            continue
        o = np.argsort(rel[g, fids], kind="stable")
        fs = fids[o]
        uniq, starts = np.unique(rel[g, fs], return_index=True)
        bounds = list(starts) + [fs.size]
        groups.append(
            [
                (uniq[i], fs[bounds[i]:bounds[i + 1]])
                for i in range(len(uniq))
            ]
        )
        t0[g] = uniq[0]
    ptr = np.ones(G, dtype=np.int64)
    next_rel = np.array(
        [g[1][0] if len(g) > 1 else np.inf for g in groups]
    )

    PI = ports // N  # static pair -> ingress port
    PJ = ports % N  # static pair -> egress port
    h = pairstart.copy()
    free_in = np.zeros((G, N))
    free_out = np.zeros((G, N))
    est = np.full((G, F), NOT)
    comp = np.full((G, F), NOT)
    pending = valid.copy()
    remaining = valid.sum(1)
    t = t0
    orig = np.arange(G)
    it = 0

    live = remaining > 0
    if not live.all():
        (orig, h, pairstart, pairend, psort, pos, pairid, rel, dur,
         pending, est, comp, free_in, free_out, remaining, t, ptr,
         next_rel) = (
            a[live] for a in (
                orig, h, pairstart, pairend, psort, pos, pairid, rel,
                dur, pending, est, comp, free_in, free_out, remaining,
                t, ptr, next_rel,
            )
        )
        groups = [grp for g, grp in enumerate(groups) if live[g]]

    while orig.size:
        it += 1
        if it > bound:  # pragma: no cover - bound is provably large
            who = ", ".join(
                labels[g] if labels and g < len(labels) else f"member {g}"
                for g in sorted(set(orig.tolist()))
            )
            raise RuntimeError(
                f"batched scheduler exceeded the event bound ({who})"
            )
        member_rounds[orig[remaining > 0]] += 1
        Ga = orig.size
        t_ = t[:, None]
        base = (np.arange(Ga) * F)[:, None]
        # Head maintenance: skip started and not-yet-released flows (a
        # release rewind restores the latter when their instant arrives).
        while True:
            hv = h < pairend
            hc = np.minimum(h, F - 1)
            c = psort.ravel()[hc + base]
            cf = c + base
            pend_c = pending.ravel()[cf]
            rel_c = rel.ravel()[cf]
            skip = hv & (~pend_c | (rel_c > t_))
            if not skip.any():
                break
            h = h + skip
        waitc = hv & (rel_c <= t_)
        FI = free_in[:, PI]
        FO = free_out[:, PJ]
        idlec = waitc & (FI <= t_) & (FO <= t_)
        claim = waitc if reserving else idlec
        # resolve_event in pair space: claimed head ids, first claimer
        # per ingress (row min) and egress (column min).
        cl = np.where(claim, c, F)
        clm = cl.reshape(Ga, N, N)
        rowfirst = clm.min(2)
        colfirst = clm.min(1)
        start = idlec & (cl == rowfirst[:, PI]) & (cl == colfirst[:, PJ])

        dur_c = dur.ravel()[cf]
        end_c = t_ + dur_c
        sm = start.reshape(Ga, N, N)
        ev = np.where(start, end_c, -np.inf).reshape(Ga, N, N)
        row_has = sm.any(2)
        col_has = sm.any(1)
        free_in = np.where(row_has, ev.max(2), free_in)
        free_out = np.where(col_has, ev.max(1), free_out)
        gs, ps = np.nonzero(start)
        if gs.size:
            fstart = c[gs, ps]
            est[gs, fstart] = t[gs]
            comp[gs, fstart] = end_c[gs, ps]
            pending[gs, fstart] = False
            h[gs, ps] += 1
            remaining -= np.bincount(gs, minlength=Ga)
        # Another round at this instant is possible only if an idle
        # candidate was left blocked (greedy backfill) or a zero-duration
        # start chained its pair's next flow at the same t.
        chained = (start & (dur_c == 0.0)).any(1)
        if reserving:
            more = chained
        else:
            more = chained | (idlec & ~start).any(1)
        # Next event per pair: its ports' post-round free times (the new
        # head's own release, if later, surfaces as a release stop).
        hv2 = h < pairend
        pt = np.where(hv2, np.maximum(free_in[:, PI], free_out[:, PJ]), np.inf)
        times = np.where(pt > t_, pt, np.inf).min(1)
        tn = np.minimum(times, np.where(next_rel > t, next_rel, np.inf))
        adv = ~more
        alive = remaining > 0
        stall = adv & alive & ~np.isfinite(tn)
        if stall.any():
            bad = int(orig[stall][0])
            who = (
                labels[bad] if labels and bad < len(labels)
                else f"member {bad}"
            )
            raise RuntimeError(f"batched scheduler stalled ({who})")
        t = np.where(adv & alive, tn, t)
        # Release crossings: rewind heads of pairs whose newly released
        # flows land before the current head.
        for gi in np.nonzero(adv & alive & (next_rel <= t))[0]:
            grp = groups[gi]
            while ptr[gi] < len(grp) and grp[ptr[gi]][0] <= t[gi]:
                _, flows = grp[ptr[gi]]
                np.minimum.at(h[gi], pairid[gi, flows], pos[gi, flows])
                ptr[gi] += 1
            next_rel[gi] = (
                grp[ptr[gi]][0] if ptr[gi] < len(grp) else np.inf
            )
        # Finished members no-op harmlessly inside the lockstep batch, so
        # compact (array copies) only once enough of them accumulate.
        ndone = Ga - int(alive.sum())
        if ndone and (4 * ndone >= Ga or ndone == Ga):
            done = ~alive
            out_est[orig[done]] = est[done]
            out_comp[orig[done]] = comp[done]
            (orig, h, pairstart, pairend, psort, pos, pairid, rel, dur,
             pending, est, comp, free_in, free_out, remaining, t, ptr,
             next_rel) = (
                a[alive] for a in (
                    orig, h, pairstart, pairend, psort, pos, pairid,
                    rel, dur, pending, est, comp, free_in, free_out,
                    remaining, t, ptr, next_rel,
                )
            )
            groups = [
                grp for g, grp in enumerate(groups) if alive[g]
            ]
    return out_est, out_comp, it, member_rounds


def _engine() -> str:
    """The calendar executor of this platform: the device calendar
    (``"kernel"``) on TPU and GPU, the host calendar (``"wide"``) on a
    CPU."""
    return "kernel" if jax.default_backend() in ("tpu", "gpu") else "wide"


def _check_discipline(discipline: str) -> None:
    if discipline not in ("reserving", "greedy"):
        raise ValueError(f"unknown discipline {discipline!r}")


def _warn_kernel_fallback() -> None:
    """Warn (once per process) that the device calendar runs its round
    through the jnp pair oracle because the Pallas kernel has no native
    backend here — silent oracle fallbacks would invalidate any perf
    claim made off this executor's timings."""
    global _KERNEL_FALLBACK_WARNED
    if _KERNEL_FALLBACK_WARNED:
        return
    _KERNEL_FALLBACK_WARNED = True
    warnings.warn(
        'circuit engine "kernel": the Pallas pair_resolve kernel is not '
        f"native on backend {jax.default_backend()!r}; the round reduction "
        "runs through the jnp pair oracle (results identical, timings are "
        "not kernel timings)",
        RuntimeWarning,
        stacklevel=4,
    )


def _pair_kernel_mode(num_flows: int) -> tuple[bool, bool]:
    """``(use_kernel, interpret)`` of the kernel engine's round reduction.

    On TPU the Pallas kernel is compiled natively, and a bucket it cannot
    take is an error, never a quiet switch to the oracle.  Other backends
    run the jnp pair oracle (warned once) unless a test asks for the
    interpreter through `_PAIR_KERNEL_INTERPRET`.
    """
    if _PAIR_KERNEL_INTERPRET:
        return True, True
    if jax.default_backend() == "tpu":
        if num_flows >= _MAX_KERNEL_FLOWS:
            raise ValueError(
                f"calendar bucket of {num_flows} flows per member exceeds "
                f"the pair kernel's exact f32 id range ({_MAX_KERNEL_FLOWS})"
            )
        return True, False
    _warn_kernel_fallback()
    return False, False


def _pad_members(
    tabs: Sequence[dict], num_ports_max: int, g_multiple: int = 1
) -> dict:
    """Pad per-member flow tables into one (G, Fmax)/(G, Nmax) bucket.

    ``tabs`` holds one dict per (instance, core) member with F_k > 0
    (keys: src/dst/rel/dur as in `member_tables`).  Padded flows carry
    ``pending=False``; padding member rows (bucket rounding, plus
    ``g_multiple`` for shard counts) have no pending flows.
    """
    # Every member row steps through every lockstep round, so small
    # buckets round up to a power of two (one or two members — the whole
    # trace at K <= 2 — stay one or two rows), larger ones to the quantum.
    n = len(tabs)
    G = 1 << (n - 1).bit_length() if n <= _G_QUANTUM else _round_up(
        n, _G_QUANTUM
    )
    G = _round_up(G, g_multiple)
    Fmax = _round_up(max(t["src"].shape[0] for t in tabs), _F_QUANTUM)
    Nmax = _round_up(num_ports_max, _N_QUANTUM)
    src = np.zeros((G, Fmax), dtype=np.int32)
    dst = np.zeros((G, Fmax), dtype=np.int32)
    rel = np.zeros((G, Fmax), dtype=np.float64)
    dur = np.zeros((G, Fmax), dtype=np.float64)
    pending = np.zeros((G, Fmax), dtype=bool)
    for g, tab in enumerate(tabs):
        F = tab["src"].shape[0]
        src[g, :F] = tab["src"]
        dst[g, :F] = tab["dst"]
        rel[g, :F] = tab["rel"]
        dur[g, :F] = tab["dur"]
        pending[g, :F] = True
    return dict(
        src=src, dst=dst, rel=rel, dur=dur, pending=pending, G=G,
        Fmax=Fmax, Nmax=Nmax,
    )


def _calendar_program(pad: dict, discipline: str, sharding=None):
    """Assemble the jitted device calendar for one padded bucket.

    Returns ``(fn, args, statics, perm)`` with ``args`` host arrays —
    callers place them (with ``sharding`` when given) and invoke
    ``fn(*args, **statics)`` under `jax.enable_x64`, or lower without
    running via ``fn.lower``.  The inputs are a (G, Dp, P = Nmax^2) slab
    of flows by pair and depth, and the outputs come back in pair-sorted
    order: output column ``i`` belongs to flow ``perm[:, i]``.
    """
    reserving = discipline == "reserving"
    src, dst = pad["src"], pad["dst"]
    Fmax, Nmax = pad["Fmax"], pad["Nmax"]
    rel, dur = to_bits(pad["rel"]), to_bits(pad["dur"])
    free0 = np.zeros((pad["G"], Nmax), dtype=np.int64)
    P = Nmax * Nmax
    pairkey = np.where(
        pad["pending"], src.astype(np.int64) * Nmax + dst, P
    )
    perm, rel, dur, pending, ids, slot = _pair_slab(
        rel, dur, pad["pending"], pairkey, P
    )
    use_kernel, interpret = _pair_kernel_mode(Fmax)
    args = (rel, dur, pending, free0, ids, slot)
    statics = dict(
        reserving=reserving, bound=event_bound(Fmax), use_kernel=use_kernel,
        interpret=interpret,
    )
    if sharding is not None:
        fn = _run_calendar_pairs_sharded(sharding.mesh, **statics)
        return fn, args, {}, perm
    fn = (
        _run_calendar_pairs_donated
        if jax.default_backend() in ("tpu", "gpu")
        else _run_calendar_pairs
    )
    return fn, args, statics, perm


def _execute_members(
    tabs: Sequence[dict],
    num_ports_max: int,
    discipline: str,
    engine: str,
    labels: Sequence[str],
    sharding=None,
) -> tuple[np.ndarray, np.ndarray]:
    """Pad per-member flow tables and run the calendar executor
    ``engine`` (``"kernel"`` or ``"wide"``; callers pass `_engine()`).

    Returns the (G, Fmax) establishment/completion arrays (G rows >=
    len(tabs), padding rows garbage).  ``sharding`` places the device
    calendar's inputs with a data-axis `NamedSharding` (member rows round
    up to the shard count); the wide engine is host-side NumPy and
    ignores it.  Counts the calendar's ``calendar.rounds`` (lockstep
    rounds; the longest device's when sharded), ``calendar.members``,
    ``calendar.member_rounds`` (summed over the real members) and
    ``calendar.member_slots`` (members times lockstep rounds), and for the
    kernel engine ``calendar.pair_cells``, ``calendar.pair_slots``,
    ``calendar.slab_cells`` and ``calendar.slab_depth`` (`_count_pairs`),
    in the current `repro.trace` tally.
    """
    with span("calendar.pack"):
        g_multiple = (
            int(sharding.mesh.shape["data"])
            if sharding is not None and engine == "kernel"
            else 1
        )
        pad = _pad_members(tabs, num_ports_max, g_multiple)
    if engine == "wide":
        with span("calendar.wide"):
            est, comp, rounds, member_rounds = _run_calendar_wide(
                pad["src"], pad["dst"], pad["rel"], pad["dur"],
                pad["pending"], pad["Nmax"],
                reserving=discipline == "reserving",
                bound=event_bound(pad["Fmax"]) + pad["Fmax"],
                labels=list(labels),
            )
            _count_rounds(rounds, member_rounds, len(tabs))
    else:
        with jax.enable_x64():
            from repro.launch.mesh import place

            with span("calendar.pack"):
                fn, args, statics, perm = _calendar_program(
                    pad, discipline, sharding
                )
                args = [place(a, sharding) for a in args]
                slab_depth = args[0].shape[1]
            with span("calendar.wait"):
                est, comp, unfinished, stalled, rounds, member_rounds = (
                    to_host(*fn(*args, **statics))
                )
                del args  # frees the inputs' device buffers in the span
        with span("calendar.unpack"):
            est, comp = (
                _unsort(from_bits(a), perm) for a in (est, comp)
            )
            for g, label in enumerate(labels):
                if stalled[g]:
                    raise RuntimeError(
                        f"batched scheduler stalled ({label})"
                    )
                if unfinished[g]:  # pragma: no cover - bound is large
                    raise RuntimeError(
                        "batched scheduler exceeded the event bound "
                        f"({label})"
                    )
            _count_rounds(rounds, member_rounds, len(tabs))
            _count_pairs(len(tabs), num_ports_max, pad, slab_depth)
    return est, comp


def _count_rounds(rounds, member_rounds: np.ndarray, members: int) -> None:
    """The calendar counters of one program (see `_execute_members`)."""
    rounds = int(np.max(rounds))
    count("calendar.rounds", rounds)
    count("calendar.members", members)
    count("calendar.member_rounds", int(member_rounds[:members].sum()))
    count("calendar.member_slots", members * rounds)


def _count_pairs(
    members: int, num_ports: int, pad: dict, slab_depth: int
) -> None:
    """The pair-space counters of one kernel-engine program:
    ``calendar.pair_cells``, the members' real (ingress, egress) pairs
    (``num_ports`` squared each); ``calendar.pair_slots``, the cells the
    round's `pair_resolve` reduces for them (the Pallas kernel's
    sublane- and lane-padded block, or the oracle's (Nmax, Nmax));
    ``calendar.slab_cells``, the flow-slab cells every round touches for
    them (``slab_depth`` x Nmax^2 each, against the bucket's real flows
    the slab's padding), and ``calendar.slab_depth``, the slab's depth
    Dp."""
    from repro.kernels.event_resolve.kernel import pair_block_cells

    Nmax = pad["Nmax"]
    use_kernel, _ = _pair_kernel_mode(pad["Fmax"])
    slots = pair_block_cells(Nmax) if use_kernel else Nmax * Nmax
    count("calendar.pair_cells", members * num_ports * num_ports)
    count("calendar.pair_slots", members * slots)
    count("calendar.slab_cells", members * slab_depth * Nmax * Nmax)
    count("calendar.slab_depth", slab_depth)


def lower_calendar(
    tabs: Sequence[dict],
    num_ports_max: int,
    discipline: str = "reserving",
):
    """Lower (don't run) the device calendar for these member tables.

    Returns the `jax.stages.Lowered` of the device calendar on the padded
    bucket, on every platform (the host calendar is NumPy, with no XLA
    program) — `benchmarks/micro.py` compiles it and feeds the optimized
    HLO text to `repro.launch.hlo_cost` for the roofline report.
    """
    _check_discipline(discipline)
    if not tabs:
        raise ValueError("lower_calendar needs at least one member table")
    pad = _pad_members(tabs, num_ports_max)
    fn, args, statics, _ = _calendar_program(pad, discipline)
    with jax.enable_x64():
        return fn.lower(*args, **statics)


def schedule_batch(
    instances: Sequence[CoflowInstance],
    allocs: Sequence[Allocation],
    orders: Sequence[np.ndarray],
    discipline: str = "reserving",
) -> list[tuple[list[CoreSchedule], np.ndarray]]:
    """Circuit-schedule a whole ensemble in one vectorized program.

    Equivalent to running `repro.core.scheduler._schedule_all_cores` (and
    `ccts_from_schedules`) per instance, with bit-identical establishment
    and completion times; returns one ``(core_schedules, ccts)`` pair per
    instance, matching `CircuitStage.schedule`.  This is the
    list-of-`Allocation` oracle API; the production batch path is
    `schedule_batch_arrays`, which consumes the unified `EnsembleBatch` /
    `AllocationBatch` pytrees instead of re-extracting member tables from
    instances.

    The platform picks the executor (`_engine`): the device calendar on
    TPU and GPU, the host calendar on a CPU.  Both are bit-identical to
    the oracle and to each other.
    """
    _check_discipline(discipline)
    instances = list(instances)
    if not (len(instances) == len(allocs) == len(orders)):
        raise ValueError("instances/allocs/orders length mismatch")
    if not instances:
        return []

    tables = [
        member_tables(inst, alloc, order)
        for inst, alloc, order in zip(instances, allocs, orders)
    ]
    # Flatten (instance, core) members; empty cores skip the calendar and
    # become empty CoreSchedules directly (matching schedule_core's F=0
    # fast path).
    members = []  # (b, k, table) with F_k > 0
    for b, (inst, cores) in enumerate(zip(instances, tables)):
        for k, tab in enumerate(cores):
            if tab["coflow"].shape[0]:
                members.append((b, k, tab))

    if members:
        est, comp = _execute_members(
            [tab for _, _, tab in members],
            max(inst.num_ports for inst in instances),
            discipline,
            _engine(),
            labels=[f"instance {b}, core {k}" for b, k, _ in members],
        )

    schedules_by_member = {
        (b, k): g for g, (b, k, _) in enumerate(members)
    }
    out = []
    for b, (inst, cores) in enumerate(zip(instances, tables)):
        schedules = []
        for k, tab in enumerate(cores):
            F = tab["coflow"].shape[0]
            if F == 0:
                z = np.zeros(0)
                zi = np.zeros(0, dtype=np.int64)
                schedules.append(
                    CoreSchedule(
                        zi, zi, zi, z, z, z, tab["rate"], inst.delta
                    )
                )
                continue
            g = schedules_by_member[b, k]
            schedules.append(
                CoreSchedule(
                    coflow=tab["coflow"],
                    src=tab["src"],
                    dst=tab["dst"],
                    size=tab["size"],
                    establish=est[g, :F].copy(),
                    complete=comp[g, :F].copy(),
                    rate=tab["rate"],
                    delta=inst.delta,
                )
            )
        out.append(
            (schedules, ccts_from_schedules(inst.num_coflows, schedules))
        )
    return out


def cct_batch_arrays(
    ensemble: EnsembleBatch,
    alloc: AllocationBatch,
    discipline: str = "reserving",
) -> np.ndarray:
    """Realized per-coflow CCTs straight off the padded pytrees — lean.

    The evaluation path of candidate-search refinement
    (`repro.pipeline.refine`): identical member tables and calendar
    execution as `schedule_batch_arrays` (``busy=None``), but only the
    (B, Mp) CCT matrix is materialized — no `CoreSchedule` objects and no
    per-flow array copies, which dominate the host-side cost when the
    batch is instances × candidates wide.  Row ``b``'s first
    ``num_coflows[b]`` entries equal `ccts_from_schedules` of the full
    stage bit for bit (the max over an identical completion multiset is
    order-independent); padded entries are 0.
    """
    _check_discipline(discipline)
    B = ensemble.num_instances
    cct = np.zeros((B, ensemble.pad_coflows))
    if B == 0:
        return cct

    members = []
    for b in range(B):
        coreb = alloc.core[b]
        validb = alloc.valid[b]
        for k in range(ensemble.num_cores[b]):
            idx = np.nonzero(validb & (coreb == k))[0]
            if idx.size:
                members.append((b, k, idx))
    if members:
        tabs = [
            _member_table(ensemble, alloc, b, k, idx, None)
            for b, k, idx in members
        ]
        _est, comp = _execute_members(
            tabs,
            max(ensemble.num_ports[b] for b in range(B)),
            discipline,
            _engine(),
            labels=[f"instance {b}, core {k}" for b, k, _ in members],
            sharding=ensemble.sharding,
        )
        for g, (b, _k, idx) in enumerate(members):
            np.maximum.at(
                cct[b], alloc.coflow[b, idx], comp[g, : idx.shape[0]]
            )
    return cct


def schedule_batch_arrays(
    ensemble: EnsembleBatch,
    alloc: AllocationBatch,
    discipline: str = "reserving",
    busy: dict[tuple[int, int], dict[str, np.ndarray]] | None = None,
) -> list[tuple[list[CoreSchedule], np.ndarray]]:
    """Circuit-schedule straight off the unified padded pytrees.

    The `AllocationBatch` flow axis is already in scheduling priority
    order (global order, largest-first within coflow), so each (instance,
    core) member table is a pure stable partition of the batch arrays —
    releases, rates and delta come from the `EnsembleBatch`, and no
    `CoflowInstance` or `Allocation` object is touched.  Member tables,
    executors and outputs are bit-identical to `schedule_batch`
    (`member_tables` sorts by flow priority with a stable sort, which on
    a priority-ordered table is exactly the per-core subsequence).

    Per-instance `CoreSchedule`s / CCT vectors are materialized here —
    the circuit is the pipeline's last array stage.  When the batch
    carries a `NamedSharding`, the device calendar's member axis is
    padded to the shard count and placed with it.

    ``busy`` (streaming re-solve support) maps ``(b, k)`` to phantom
    flow tables — ``dict(src=, dst=, rel=, dur=)`` 1-D arrays describing
    circuits already committed on core ``k`` of instance ``b`` (in-flight
    non-preemptible transfers from a previous calendar).  Phantoms are
    prepended at the HEAD of the member table, so they outrank every
    real flow and claim their port pair first; in-flight circuits on one
    core are port-exclusive, so every phantom establishes exactly at its
    ``rel`` (asserted) and blocks its ingress/egress ports for ``dur``.
    Phantom rows are sliced off before `CoreSchedule`s are built — the
    returned schedules and CCTs cover real flows only.  ``busy=None``
    (the default) leaves the stage bit-identical to its previous
    behavior; ``(b, k)`` entries whose member has no real flows are
    ignored (phantoms alone constrain nothing).
    """
    with span("calendar.pack"):
        _check_discipline(discipline)
        B = ensemble.num_instances
        if B == 0:
            return []
        # (b, k, flow-row indices into the ordered flow axis, phantoms)
        members = []
        for b in range(B):
            coreb = alloc.core[b]
            validb = alloc.valid[b]
            for k in range(ensemble.num_cores[b]):
                idx = np.nonzero(validb & (coreb == k))[0]
                if idx.size:
                    nb = 0
                    if busy is not None and (b, k) in busy:
                        nb = int(np.asarray(busy[b, k]["src"]).shape[0])
                    members.append((b, k, idx, nb))
        tabs = [
            _member_table(ensemble, alloc, b, k, idx, busy if nb else None)
            for b, k, idx, nb in members
        ]

    if members:
        est, comp = _execute_members(
            tabs,
            max(ensemble.num_ports[b] for b in range(B)),
            discipline,
            _engine(),
            labels=[f"instance {b}, core {k}" for b, k, _, _ in members],
            sharding=ensemble.sharding,
        )

    with span("calendar.readback"):
        for g, (b, k, _, nb) in enumerate(members):
            if nb and not np.array_equal(est[g, :nb], tabs[g]["rel"][:nb]):
                raise AssertionError(
                    f"instance {b}, core {k}: committed phantom circuits "
                    "did not establish at their release — busy tables must "
                    "be port-exclusive with rel at the epoch time"
                )
        schedules_by_member = {
            (b, k): g for g, (b, k, _, _) in enumerate(members)
        }
        out = []
        for b in range(B):
            schedules = []
            for k in range(ensemble.num_cores[b]):
                g = schedules_by_member.get((b, k))
                if g is None:
                    z = np.zeros(0)
                    zi = np.zeros(0, dtype=np.int64)
                    schedules.append(
                        CoreSchedule(
                            zi, zi, zi, z, z, z,
                            float(ensemble.rates[b, k]),
                            float(ensemble.delta[b]),
                        )
                    )
                    continue
                _, _, idx, nb = members[g]
                F = idx.shape[0]
                schedules.append(
                    CoreSchedule(
                        coflow=alloc.coflow[b, idx],
                        src=alloc.src[b, idx],
                        dst=alloc.dst[b, idx],
                        size=alloc.size[b, idx],
                        establish=est[g, nb:nb + F].copy(),
                        complete=comp[g, nb:nb + F].copy(),
                        rate=float(ensemble.rates[b, k]),
                        delta=float(ensemble.delta[b]),
                    )
                )
            out.append(
                (
                    schedules,
                    ccts_from_schedules(ensemble.num_coflows[b], schedules),
                )
            )
        return out


def _member_table(
    ensemble: EnsembleBatch,
    alloc: AllocationBatch,
    b: int,
    k: int,
    idx: np.ndarray,
    busy: dict | None,
) -> dict:
    """Flow table of member (instance ``b``, core ``k``): the ordered flow
    rows ``idx``, with ``busy``'s phantom circuits of that member (if
    given) prepended."""
    tab = dict(
        src=alloc.src[b, idx],
        dst=alloc.dst[b, idx],
        rel=ensemble.releases[b, alloc.coflow[b, idx]],
        dur=ensemble.delta[b] + alloc.size[b, idx] / ensemble.rates[b, k],
    )
    if busy is None:
        return tab
    bz = busy[b, k]
    dtypes = dict(
        src=tab["src"].dtype, dst=tab["dst"].dtype,
        rel=np.float64, dur=np.float64,
    )
    return {
        key: np.concatenate([np.asarray(bz[key], dt), tab[key]])
        for key, dt in dtypes.items()
    }
