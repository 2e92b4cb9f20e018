"""The coflow-benchmark FB2010 trace's stand-in, cut to a configuration.

A copy of the program's Facebook-like trace generator and its sampler,
kept here so that a change to the program cannot move the benchmark's
inputs.  A configuration with ``"gen": "fb_trace"`` names the trace
(``trace``), its cut to ports and coflows (``num_ports``, ``cut``), its
release mode and the release-ordered slice it keeps (``first_coflow``,
``num_coflows``).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from harness.gen import Instance


@dataclasses.dataclass
class TraceCoflow:
    coflow_id: int
    arrival_ms: float
    mappers: np.ndarray
    reducers: np.ndarray
    reducer_mb: np.ndarray


def synthesize_facebook_like(
    num_coflows: int = 526,
    num_machines: int = 150,
    seed: int = 0,
    mean_interarrival_ms: float = 1000.0,
) -> list[TraceCoflow]:
    """Deterministic FB-like trace: Poisson arrivals, the published width
    mix (most coflows narrow, a minority very wide), Pareto sizes and
    lognormal receiver skew."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(mean_interarrival_ms, size=num_coflows))
    out: list[TraceCoflow] = []
    for c in range(num_coflows):
        narrow_hi = max(2, min(5, num_machines // 2))
        med_hi = max(narrow_hi + 1, min(30, num_machines // 3))
        wide_hi = max(med_hi + 1, num_machines // 2)
        u = rng.random()
        if u < 0.52:
            nm = rng.integers(1, narrow_hi)
            nr = rng.integers(1, narrow_hi)
        elif u < 0.85:
            nm = rng.integers(narrow_hi, med_hi)
            nr = rng.integers(narrow_hi, med_hi)
        else:
            nm = rng.integers(med_hi, wide_hi)
            nr = rng.integers(med_hi, wide_hi)
        mappers = rng.choice(num_machines, size=int(nm), replace=False)
        reducers = rng.choice(num_machines, size=int(nr), replace=False)
        total_mb = float((rng.pareto(1.2) + 1.0) * 8.0)
        split = rng.lognormal(mean=0.0, sigma=0.8, size=int(nr))
        reducer_mb = total_mb * split / split.sum()
        out.append(TraceCoflow(c, float(arrivals[c]), mappers, reducers,
                               reducer_mb))
    return out


def to_demands(coflows, port_map, num_ports, rng) -> np.ndarray:
    """(M, N, N) demand matrices: each receiver's traffic split
    pseudo-uniformly (+-20%) over the coflow's mapped senders."""
    mats = []
    for cf in coflows:
        mat = np.zeros((num_ports, num_ports))
        senders = [port_map[m] for m in cf.mappers if m in port_map]
        if not senders:
            mats.append(mat)
            continue
        for rid, mb in zip(cf.reducers, cf.reducer_mb):
            if rid not in port_map:
                continue
            j = port_map[rid]
            share = np.full(len(senders), 1.0 / len(senders))
            share *= rng.uniform(0.8, 1.2, size=len(senders))
            share /= share.sum()
            for i, s in zip(senders, share):
                mat[i, j] += mb * s
        mats.append(mat)
    return np.stack(mats) if mats else np.zeros((0, num_ports, num_ports))


def sample_instance(
    trace: list[TraceCoflow],
    num_ports: int,
    num_coflows: int,
    rates,
    delta: float,
    seed: int,
    release: str = "trace",
) -> Instance:
    """N machines as ports and the first M coflows (in a seeded
    permutation) with demand on them; trace arrivals rescaled so their
    span matches the service scale (paper Sec. V-A)."""
    rng = np.random.default_rng(seed)
    machines = set()
    for cf in trace:
        machines.update(int(x) for x in cf.mappers)
        machines.update(int(x) for x in cf.reducers)
    machines = np.asarray(sorted(machines))
    chosen = rng.choice(machines, size=num_ports, replace=False)
    port_map = {int(m): i for i, m in enumerate(chosen)}
    perm = rng.permutation(len(trace))
    demands, arrivals = [], []
    for idx in perm:
        mat = to_demands([trace[idx]], port_map, num_ports, rng)[0]
        if mat.sum() > 0:
            demands.append(mat)
            arrivals.append(trace[idx].arrival_ms)
        if len(demands) == num_coflows:
            break
    if len(demands) < num_coflows:
        raise ValueError(
            f"trace only yields {len(demands)} coflows on {num_ports} ports"
        )
    demands = np.stack(demands)
    weights = rng.uniform(1.0, 10.0, size=num_coflows)
    if release == "zero":
        releases = np.zeros(num_coflows)
    elif release == "trace":
        arr = np.asarray(arrivals)
        arr = arr - arr.min()
        span = demands.sum() / (sum(rates) * num_ports)
        releases = arr / max(arr.max(), 1e-9) * span
    else:
        raise ValueError(f"unknown release mode {release!r}")
    return Instance(demands, weights, releases,
                    np.asarray(rates, dtype=np.float64), float(delta))


def instance(config: dict) -> Instance:
    """The configuration's instance, before any seed: trace, cut, slice."""
    tr = config["trace"]
    trace = synthesize_facebook_like(
        num_coflows=tr["num_coflows"], num_machines=tr["num_machines"],
        seed=tr["trace_seed"], mean_interarrival_ms=tr["mean_interarrival_ms"],
    )
    cut = config["cut"]
    inst = sample_instance(
        trace, num_ports=config["num_ports"], num_coflows=cut["num_coflows"],
        rates=config["rates"], delta=config["delta"], seed=cut["cut_seed"],
        release=config["release"],
    )
    first = config["first_coflow"]
    order = np.argsort(inst.releases, kind="stable")
    return inst.subset(order[first:first + config["num_coflows"]])
