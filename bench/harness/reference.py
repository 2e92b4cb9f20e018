"""The plain reference: Algorithm 1's allocation and circuit calendar, the
ordering LP, and the online stream's event loop, in straightforward NumPy.

Written from the paper (Sec. IV) and the program's documented semantics;
it imports nothing of the program.  ``dtype`` is float64, the precision
the configurations state; float32 is the control, which the comparison
has to refuse.
"""

from __future__ import annotations

import collections

import numpy as np
import scipy.sparse as sp
from scipy.optimize import linprog

from harness.gen import Instance


def port_stats(demands: np.ndarray):
    """Per coflow and port (ingress 0..N-1, egress N..2N-1): load, count."""
    nz = demands > 0
    rho = np.concatenate([demands.sum(axis=2), demands.sum(axis=1)], axis=-1)
    tau = np.concatenate([nz.sum(axis=2), nz.sum(axis=1)], axis=-1)
    return rho, tau.astype(np.float64)


def flows_of(demand: np.ndarray):
    """Nonzero flows of one coflow, largest first (ties in row-major)."""
    i, j = np.nonzero(demand)
    d = demand[i, j]
    o = np.argsort(-d, kind="stable")
    return i[o], j[o], d[o]


def allocate(inst: Instance, order, dtype=np.float64) -> dict:
    """Greedy allocation (Alg. 1 Lines 3-15): along the order, each flow
    goes whole to the core whose post-placement prefix lower bound
    max(LB_k, per-port load/r_k + count * delta) is least."""
    N, K = inst.num_ports, inst.num_cores
    one = dtype(1.0)
    inv = (one / inst.rates.astype(dtype)).astype(dtype)
    delta = dtype(inst.delta)
    rho = np.zeros((K, 2 * N), dtype)
    tau = np.zeros((K, 2 * N), dtype)
    lb = np.zeros(K, dtype)
    out = collections.defaultdict(list)
    for m in order:
        for i, j, d in zip(*flows_of(inst.demands[m])):
            d = dtype(d)
            pi, pj = i, N + j
            li = (rho[:, pi] + d) * inv + (tau[:, pi] + one) * delta
            lj = (rho[:, pj] + d) * inv + (tau[:, pj] + one) * delta
            cand = np.maximum(lb, np.maximum(li, lj))
            k = int(np.argmin(cand))
            rho[k, pi] += d
            rho[k, pj] += d
            tau[k, pi] += one
            tau[k, pj] += one
            lb[k] = cand[k]
            for key, v in (("coflow", m), ("src", i), ("dst", j),
                           ("size", d), ("core", k)):
                out[key].append(v)
    return {
        "coflow": np.asarray(out["coflow"], np.int64),
        "src": np.asarray(out["src"], np.int64),
        "dst": np.asarray(out["dst"], np.int64),
        "size": np.asarray(out["size"], dtype),
        "core": np.asarray(out["core"], np.int64),
    }


def _resolve(src, dst, free_in, free_out, waiting, t, reserving):
    """One round at instant t: a flow starts iff both its ports are idle
    and it is the first claimer on each (reserving: every waiting flow
    claims; greedy: only idle ones)."""
    idle = waiting & (free_in[src] <= t) & (free_out[dst] <= t)
    claim = waiting if reserving else idle
    F = src.shape[0]
    ar = np.arange(F)
    idx = np.where(claim, ar, F)
    first_in = np.full(free_in.shape[0], F)
    np.minimum.at(first_in, src, idx)
    first_out = np.full(free_out.shape[0], F)
    np.minimum.at(first_out, dst, idx)
    return idle & (ar == first_in[src]) & (ar == first_out[dst])


def schedule_core(coflow, src, dst, size, releases, num_ports, rate, delta,
                  discipline, dtype=np.float64) -> dict:
    """Not-all-stop list scheduling of one core's flows, given in priority
    order: at each decision instant start every waiting flow whose ports
    are free (and, reserving, unclaimed by an earlier waiting flow),
    repeating rounds until none starts, then advance to the next release
    or port-free time."""
    F = coflow.shape[0]
    rel = releases[coflow].astype(dtype)
    dur = dtype(delta) + size.astype(dtype) / dtype(rate)
    free_in = np.zeros(num_ports, dtype)
    free_out = np.zeros(num_ports, dtype)
    est = np.full(F, -1.0, dtype)
    comp = np.full(F, -1.0, dtype)
    pending = np.ones(F, bool)
    reserving = discipline == "reserving"
    t = rel.min() if F else dtype(0)
    left = F
    while left:
        waiting = pending & (rel <= t)
        while waiting.any():
            start = _resolve(src, dst, free_in, free_out, waiting, t, reserving)
            if not start.any():
                break
            end = t + dur[start]
            est[start] = t
            comp[start] = end
            free_in[src[start]] = end
            free_out[dst[start]] = end
            pending[start] = False
            left -= int(start.sum())
            waiting &= ~start
        if not left:
            break
        idx = np.nonzero(pending)[0]
        times = np.maximum.reduce([rel[idx], free_in[src[idx]],
                                   free_out[dst[idx]]])
        times = times[times > t]
        if not times.size:
            raise RuntimeError(f"reference calendar stalled at t={t}")
        t = times.min()
    return {"coflow": coflow, "src": src, "dst": dst, "size": size,
            "establish": est, "complete": comp}


def schedule(inst: Instance, order, discipline,
             dtype=np.float64) -> tuple[dict, list[dict]]:
    """Allocation and every core's calendar along ``order``; flows keep
    the allocation's sequence (coflow rank, then largest first) as their
    priority on each core."""
    alloc = allocate(inst, order, dtype)
    cores = []
    for k in range(inst.num_cores):
        sel = alloc["core"] == k
        cores.append(schedule_core(
            alloc["coflow"][sel], alloc["src"][sel], alloc["dst"][sel],
            alloc["size"][sel], inst.releases, inst.num_ports,
            inst.rates[k], inst.delta, discipline, dtype,
        ))
    return alloc, cores


def ccts(num_coflows: int, cores: list[dict]) -> np.ndarray:
    out = np.zeros(num_coflows)
    for cs in cores:
        if cs["coflow"].size:
            np.maximum.at(out, cs["coflow"], cs["complete"].astype(np.float64))
    return out


def flat(cores: list[dict]) -> dict:
    """Every core's flows in one table sorted by (coflow, src, dst)."""
    cols = {k: np.concatenate([np.asarray(cs[k]) for cs in cores])
            for k in ("coflow", "src", "dst", "size", "establish", "complete")}
    cols["core"] = np.concatenate([
        np.full(len(cs["coflow"]), k, np.int64) for k, cs in enumerate(cores)
    ])
    o = np.lexsort((cols["dst"], cols["src"], cols["coflow"]))
    return {k: v[o] for k, v in cols.items()}


def flow_mismatch(got: dict, want: dict) -> int:
    """Flows whose core, establishment or completion differ bit for bit,
    plus flows that only one side carries."""
    def table(t):
        return {
            key: (c, np.float64(e), np.float64(f))
            for key, c, e, f in zip(
                zip(t["coflow"].tolist(), t["src"].tolist(), t["dst"].tolist()),
                t["core"].tolist(), t["establish"], t["complete"])
        }

    g, w = table(got), table(want)
    return sum(1 for key in g.keys() | w.keys() if g.get(key) != w.get(key))


def violations(inst: Instance, cores: list[dict]) -> int:
    """Broken guarantees in a schedule: flows that start before their
    release, whose completion is not establishment + delta + size/rate,
    that overlap another flow on an ingress or egress port of their core,
    plus demand entries not carried exactly once, whole."""
    bad = 0
    seen = np.zeros(inst.demands.shape, np.int64)
    sizes = np.zeros(inst.demands.shape)
    for k, cs in enumerate(cores):
        m, i, j = cs["coflow"], cs["src"], cs["dst"]
        est, comp = (np.asarray(cs[c], np.float64)
                     for c in ("establish", "complete"))
        if not m.size:
            continue
        bad += int((est < inst.releases[m]).sum())
        want = est + (inst.delta + np.asarray(cs["size"], np.float64)
                      / inst.rates[k])
        bad += int((comp != want).sum())
        for ports in (i, j):
            o = np.lexsort((est, ports))
            p, s, e = ports[o], est[o], comp[o]
            bad += int(((p[1:] == p[:-1]) & (s[1:] < e[:-1])).sum())
        np.add.at(seen, (m, i, j), 1)
        sizes[m, i, j] = np.asarray(cs["size"], np.float64)
    carried = inst.demands > 0
    bad += int((seen[carried] != 1).sum()) + int((seen[~carried] != 0).sum())
    bad += int((sizes[carried] != inst.demands[carried]).sum())
    return bad


def exact_lp(inst: Instance) -> float:
    """Optimum of the ordering LP (paper Sec. IV-A2, HiGHS): min sum w T
    over completion times T and precedences x_ab + x_ba = 1 with
    T_m >= (1/R)(rho_m,p + sum_m' rho_m',p x_m'm),
    T_m >= (delta/K)(tau_m,p + sum_m' tau_m',p x_m'm) and T_m >= a_m."""
    M, N, K = inst.num_coflows, inst.num_ports, inst.num_cores
    rho, tau = port_stats(inst.demands)
    ia, ib = np.triu_indices(M, k=1)
    P = ia.shape[0]
    pair = np.full((M, M), -1, np.int64)
    pair[ia, ib] = np.arange(P)
    rows, cols, vals, rhs = [], [], [], []
    r = 0
    for stats, coef in ((rho, 1.0 / inst.rates.sum()), (tau, inst.delta / K)):
        if coef == 0.0:
            continue
        for m in range(M):
            lo, hi = np.arange(m), np.arange(m + 1, M)
            for p in range(2 * N):
                # x_{m',m} = y_(m',m) for m' < m and 1 - y_(m,m') for m' > m.
                rows.append(r)
                cols.append(m)
                vals.append(-1.0)
                rhs.append(-coef * (stats[m, p] + stats[hi, p].sum()))
                for others, sign, ids in ((lo, 1.0, pair[lo, m]),
                                          (hi, -1.0, pair[m, hi])):
                    nz = stats[others, p] != 0
                    rows.extend([r] * int(nz.sum()))
                    cols.extend((M + ids[nz]).tolist())
                    vals.extend((sign * coef * stats[others[nz], p]).tolist())
                r += 1
    A = sp.csr_matrix((vals, (rows, cols)), shape=(r, M + P))
    c = np.concatenate([inst.weights, np.zeros(P)])
    bounds = [(float(a), None) for a in inst.releases] + [(0.0, 1.0)] * P
    # The interior-point method with crossover: ~10 s at 192 coflows on
    # 48 ports, where the simplex default takes over a quarter of an hour.
    res = linprog(c, A_ub=A, b_ub=np.asarray(rhs), bounds=bounds,
                  method="highs-ipm")
    if not res.success:
        raise RuntimeError(f"reference LP failed: {res.message}")
    return float(res.fun)


# ---------------------------------------------------------------------------
# The online stream, replayed
# ---------------------------------------------------------------------------


def replay_stream(inst: Instance, orders, *, n_batches: int,
                  pool_size: int, discipline: str, dtype=np.float64):
    """Replay the stream's event loop with the program's per-epoch orders.

    Arrival batches are ``n_batches`` equal chunks of the release-sorted
    coflows, each admitted when its first coflow arrives.  At each epoch
    the incumbent calendar is settled (delivered flows leave the residual;
    in-flight flows are preempted, the bytes sent leaving the residual, the
    rest re-paying delta), drained coflows free their slots, queued
    arrivals are admitted FIFO into free slots, and the active coflows'
    residual instance (releases clamped to now) is re-scheduled along the
    epoch's order.  While arrivals wait for slots, the next epoch is the
    earliest projected completion.  Returns, per epoch, its time, active
    coflows, their projected completions and the epoch's instance, and the
    realized completion of every coflow.
    """
    M = inst.num_coflows
    rates = inst.rates
    residual = inst.demands.astype(np.float64).copy()
    finish = np.zeros(M)
    last = np.zeros(M)
    active: list[int] = []
    queue: collections.deque = collections.deque()
    cal = None  # (m, k, i, j, size, est, comp)
    epochs = []
    it = iter(orders)

    def advance(now):
        nonlocal cal
        if cal is not None:
            m, k, i, j, size, est, comp = cal
            delivered = comp <= now
            inflight = ~delivered & (est < now)
            sent = rates[k] * np.maximum(0.0, now - est - inst.delta)
            full = inflight & (sent >= size)
            deliver = delivered | full
            partial = inflight & ~full
            residual[m[deliver], i[deliver], j[deliver]] -= size[deliver]
            if partial.any():
                residual[m[partial], i[partial], j[partial]] -= sent[partial]
            np.maximum.at(finish, m[deliver], comp[deliver])
            cal = None
        np.maximum(residual, 0.0, out=residual)
        for m in [a for a in active if not residual[a].any()]:
            active.remove(m)

    def admit(now):
        while queue and len(active) < pool_size:
            active.append(queue.popleft())

    def epoch(now):
        nonlocal cal
        if not active:
            return
        act = np.asarray(sorted(active), np.int64)
        sub = Instance(residual[act].copy(), inst.weights[act],
                       np.maximum(inst.releases[act], now), rates, inst.delta)
        # Where the replay's actives part from the program's (a run that
        # is not correct), its order is kept for the coflows both hold.
        dense = {int(g): d for d, g in enumerate(act)}
        order = [dense[g] for g in map(int, next(it, [])) if g in dense]
        order += sorted(set(range(len(act))) - set(order))
        _, cores = schedule(sub, order, discipline, dtype)
        c = ccts(len(act), cores)
        last[act] = c
        parts = [(act[cs["coflow"]], np.full(len(cs["coflow"]), k),
                  cs["src"], cs["dst"], np.asarray(cs["size"], np.float64),
                  np.asarray(cs["establish"], np.float64),
                  np.asarray(cs["complete"], np.float64))
                 for k, cs in enumerate(cores)]
        cal = tuple(np.concatenate([p[f] for p in parts]) for f in range(7))
        epochs.append({"time": now, "actives": act, "ccts": c,
                       "instance": sub})

    order_r = np.argsort(inst.releases, kind="stable")
    for chunk in np.array_split(order_r, min(n_batches, M)):
        now = float(inst.releases[chunk[0]])
        advance(now)
        queue.extend(int(m) for m in chunk)
        admit(now)
        epoch(now)
    while queue:
        if len(epochs) > 10 * M:
            raise RuntimeError("the replayed stream does not drain")
        now = float(last[np.asarray(active)].min())
        advance(now)
        admit(now)
        epoch(now)
    if cal is not None:
        m, _, i, j, size, _, comp = cal
        residual[m, i, j] -= size
        np.maximum.at(finish, m, comp)
    return epochs, finish
