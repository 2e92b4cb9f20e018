"""Trace-scale scenarios: full-trace sweeps + long-horizon streaming.

The figure benches run paper-sized instances (N=10, M=100 subsampled
from the trace).  This module makes the *full* workloads first-class
sweeps over the cached experiment fabric:

  * ``fb_full``  — the complete 526-coflow / 150-port Facebook-like
    trace (no subsampling: every machine is a port) with trace-arrival
    releases, swept over K heterogeneous cores;
  * ``synth_1k`` — a synthetic scale-up past the trace (1024 coflows,
    64 ports, K up to 24 cores) drawn from the published width/size mix
    via `scaled_trace_instance`;
  * ``fb_quick`` — a CI-sized cut of the trace (48 coflows, 24 ports)
    whose exact-LP lower bounds keep every assertion strict.

Each scenario is a list of JSON-able **cell specs** plus the module
factory `make(spec)` — exactly the contract `repro.experiments.runner`
shards across hosts, so the same registry drives single-process runs
here and multi-host fleets via `run_shard`/`run_distributed`.

``--scenario NAME`` runs two benches and merges their stats into
``results/benchmarks/micro.json``:

  1. `bench_trace_sweep` — the scenario's sweep through the
     content-addressed cache, fresh then replayed: the replay must
     compute **zero** cells and export byte-identical rows
     (``trace_sweep_cached_replay_x`` is the wall-clock ratio);
  2. `bench_service_long` — the long-horizon streaming service on the
     scenario's service instance, run through both the rebuild-per-epoch
     and the device-resident epoch drivers: realized weighted CCT
     against the paper's (8K+1) x LP-lower-bound guarantee
     (``service_bound_margin_x`` >= 1 means within the bound), the
     floor-gated resident-vs-rebuild warm-epoch speedup
     (``service_epoch_warm_x``), plus warm re-solve latency percentiles
     (p50/p95/p99) as trajectory metrics.

For ``fb_quick`` the lower bound is the exact (HiGHS) LP optimum and
the bound check is a hard assertion.  At full scale the exact LP is
out of reach, so the subgradient *objective* stands in — it converges
to the LP optimum from the feasible side but is not certified below
OPT, so the margin is recorded as a documented reference, not
asserted.  ``--trajectory`` appends the stats (backend metadata
auto-stamped) to the repo-tracked ``BENCH_micro.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import time

import numpy as np

from benchmarks.common import results_dir
from benchmarks.micro import _merge_micro_json, record_trajectory
from repro.core import lp
from repro.traffic.instances import sample_instance, scaled_trace_instance


def _rates(k: int) -> tuple:
    """Heterogeneous core rates 10, 20, ..., 10K (paper Sec. V-A shape)."""
    return tuple(10.0 * (i + 1) for i in range(k))


# Scenario registry.  `cells` are JSON-able specs consumed by `make()`;
# `sweep` holds the sweep() kwargs; `service` configures the
# long-horizon streaming bench (and whether the LP lower bound is the
# certified exact optimum or the subgradient stand-in).
SCENARIOS = {
    "fb_quick": {
        "cells": [
            {
                "gen": "fb",
                "num_coflows": 48,
                "num_ports": 24,
                "rates": _rates(k),
                "release": "trace",
                "seed": 0,
            }
            for k in (1, 2, 4)
        ],
        "sweep": {
            "schemes": ("ours", "wspt_order"),
            "lp_method": "exact",
            "validate": True,
        },
        "service": {
            "cell": {
                "gen": "fb",
                "num_coflows": 48,
                "num_ports": 24,
                "rates": _rates(2),
                "release": "trace",
                "seed": 0,
            },
            "lp_iters": 600,
            "n_batches": 6,
            "pool_size": 16,
            "lb": "exact",
        },
    },
    # The full trace: every coflow, every machine a port.  The host
    # circuit calendar costs ~1.8 ms per flow at N=150 and the trace
    # holds 266k nonzero demand entries (a handful of all-to-all
    # coflows dominate), so each K roughly costs 470*K seconds per
    # scheme — the K sweep stops at 2 to keep a full run under an hour;
    # "K up to dozens" is synth_1k's job at a cheaper port count.
    "fb_full": {
        "cells": [
            {
                "gen": "fb",
                "num_coflows": 526,
                "num_ports": 150,
                "rates": _rates(k),
                "release": "trace",
                "seed": 0,
            }
            for k in (1, 2)
        ],
        "sweep": {
            "schemes": ("ours", "wspt_order"),
            "lp_method": "batch",
            "lp_iters": 1200,
            "validate": False,
        },
        # Long horizon = many re-solve epochs, not maximal port count:
        # a 192-coflow / 48-port cut of the trace with a binding pool
        # yields 100+ epochs (arrival + drain) at seconds-per-epoch, so
        # the re-solve latency percentiles measure the service, not one
        # giant calendar.
        "service": {
            "cell": {
                "gen": "fb",
                "num_coflows": 192,
                "num_ports": 48,
                "rates": _rates(4),
                "release": "trace",
                "seed": 0,
            },
            "lp_iters": 900,
            "n_batches": 24,
            "pool_size": 32,
            "lb": "subgradient",
        },
    },
    # Synthetic scale-up: thousands of coflows, K up to two dozen
    # cores.  Flow count scales as entries x K (the K=24 cell alone
    # schedules ~2M flows), so ports stay at 48 and the baseline scheme
    # column is dropped (the LP objective normalizes quality); rows
    # still carry absolute + normalized CCTs per K.
    "synth_1k": {
        "cells": [
            {
                "gen": "synth",
                "num_coflows": 1024,
                "num_ports": 48,
                "rates": _rates(k),
                "release": "trace",
                "seed": 1,
            }
            for k in (4, 12, 24)
        ],
        "sweep": {
            "schemes": ("ours",),
            "lp_method": "batch",
            "lp_iters": 600,
            "validate": False,
        },
        "service": {
            "cell": {
                "gen": "synth",
                "num_coflows": 256,
                "num_ports": 32,
                "rates": _rates(8),
                "release": "trace",
                "seed": 1,
            },
            "lp_iters": 500,
            "n_batches": 12,
            "pool_size": 24,
            "lb": "subgradient",
        },
    },
}


def make(spec):
    """Cell-spec factory: the runner contract (per-host generation).

    ``spec["gen"]`` picks the generator — ``"fb"`` subsamples (or, at
    526/150, takes whole) the Facebook-like trace; ``"synth"`` is the
    `scaled_trace_instance` scale-up with an identity port map.  Specs
    are plain JSON dicts, so a multi-host fleet ships them over the
    wire and every host regenerates its shard's instances locally.
    """
    spec = dict(spec)
    spec.pop("cell", None)  # runner bookkeeping, not a generator arg
    gen = spec.pop("gen")
    spec["rates"] = tuple(spec["rates"])
    if gen == "fb":
        return sample_instance(**spec)
    if gen == "synth":
        return scaled_trace_instance(**spec)
    raise ValueError(f"unknown generator {gen!r}")


def bench_trace_sweep(scenario="fb_quick", cache_root=None):
    """Scenario sweep through the cache: fresh, then a zero-compute replay.

    The replay goes through a **new** `SweepCache` handle on the same
    root (the restart path: manifest reloaded from disk) and must report
    zero computed cells; fresh and replayed rows must serialize
    byte-identically.  Also reports the mean ours/wspt CCT ratio per K
    so full-scale sweeps leave interpretable numbers in the trajectory.
    """
    from repro.experiments import SweepCache, sweep

    scen = SCENARIOS[scenario]
    if cache_root is None:
        cache_root = os.path.join(results_dir(), "cache_trace", scenario)
    shutil.rmtree(cache_root, ignore_errors=True)
    ens = [make(spec) for spec in scen["cells"]]
    metas = [
        {"cell": i, "K": len(spec["rates"]), **{
            k: v for k, v in spec.items() if k in ("gen", "num_coflows",
                                                   "num_ports", "seed")
        }}
        for i, spec in enumerate(scen["cells"])
    ]
    kwargs = dict(scen["sweep"], metas=metas)

    t0 = time.perf_counter()
    res_fresh = sweep(ens, cache=cache_root, **kwargs)
    t_fresh = time.perf_counter() - t0
    if res_fresh.cache_stats["computed"] != res_fresh.cache_stats["cells"]:
        raise AssertionError(
            f"fresh pass expected all-miss, got {res_fresh.cache_stats}"
        )

    t0 = time.perf_counter()
    res_replay = sweep(ens, cache=SweepCache(cache_root), **kwargs)
    t_replay = time.perf_counter() - t0
    if res_replay.cache_stats["computed"] != 0:
        raise AssertionError(
            f"replay recomputed cells: {res_replay.cache_stats}"
        )
    if json.dumps(res_fresh.rows(), default=float) != json.dumps(
        res_replay.rows(), default=float
    ):
        raise AssertionError("replayed sweep rows diverged from fresh run")

    # Bound the store before reporting: repeated bench runs with code /
    # config churn orphan whole cache generations (every fingerprint
    # change mints fresh keys), so a long-lived cache root accretes
    # without an eviction pass.  LRU-gc down to the live generation —
    # the cells the replay just touched are MRU and survive; anything
    # older goes.
    gc_stats = SweepCache(cache_root).gc(
        max_cells=res_replay.cache_stats["cells"]
    )
    stats = {
        "trace_cells": res_replay.cache_stats["cells"],
        "trace_sweep_fresh_s": t_fresh,
        "trace_sweep_replay_s": t_replay,
        "trace_sweep_cached_replay_x": t_fresh / t_replay,
        "trace_cache_gc_evicted": gc_stats["evicted"],
        "trace_cache_bytes": gc_stats["bytes"],
    }
    # Per-K quality: mean normalized CCT (scheme / LP bound proxy) ratio
    # of the paper scheme against the WSPT-order baseline.
    rows = res_fresh.rows()
    for spec in scen["cells"]:
        k = len(spec["rates"])
        ours = [r for r in rows if r["scheme"] == "ours" and r["K"] == k]
        base = [r for r in rows if r["scheme"] == "wspt_order" and r["K"] == k]
        if ours and base:
            stats[f"trace_k{k}_ours_vs_wspt"] = float(
                np.mean([o["total_weighted_cct"] for o in ours])
                / np.mean([b["total_weighted_cct"] for b in base])
            )
    return stats


def bench_service_long(scenario="fb_quick"):
    """Long-horizon streaming service at trace scale.

    Streams the scenario's service instance (trace arrivals, bounded
    slot pool, warm-started re-solves) through BOTH epoch drivers — the
    PR 7 rebuild-per-epoch path and the device-resident slot-pool path —
    and reports:

      * ``service_bound_margin_x`` — ((8K+1) x LP lower bound) /
        realized weighted CCT of the resident run.  >= 1 means the
        online run sits inside the paper's offline guarantee; asserted
        only when the bound is the certified exact LP (``lb: "exact"``,
        CI scenario);
      * ``service_epoch_warm_x`` — p50 warm-epoch wall time of the
        rebuild driver over the resident driver (epoch 0 excluded from
        both: it carries the compile).  This is the floor-gated speedup
        of keeping the `EnsembleBatch` device-resident and scatter-
        updating slots instead of re-packing instances every epoch;
      * re-solve latency percentiles (``service_resolve_p50/95/99_ms``)
        over the resident run's warm epochs — the operational metric a
        deployed scheduler cares about;
      * epoch/warm-start counters and end-to-end wall time (resident).
    """
    from repro.experiments import stream

    scen = SCENARIOS[scenario]["service"]
    inst = make(scen["cell"])
    K = inst.num_cores
    bound = 8.0 * K + 1.0

    if scen["lb"] == "exact":
        lb = lp.solve_exact(inst).objective
    else:
        # Full scale: HiGHS on M=526 x N=150 is out of reach; the
        # subgradient objective converges to the LP optimum from the
        # feasible side and stands in as the documented reference.
        lb = lp.solve_subgradient(inst, iters=scen["lp_iters"]).objective

    kwargs = dict(
        lp_method="batch",
        lp_iters=scen["lp_iters"],
        n_batches=scen["n_batches"],
        pool_size=scen["pool_size"],
        warm_start=True,
        validate=False,
    )
    res_rebuild = stream(inst, epoch_mode="rebuild", **kwargs)
    res = stream(inst, epoch_mode="resident", **kwargs)
    margin = (bound * lb) / res.realized_weighted_cct
    if scen["lb"] == "exact" and margin < 1.0 - 1e-9:
        raise AssertionError(
            f"streamed run violated the (8K+1) bound: margin {margin:.4f}"
        )
    resolves = np.asarray([e.lp_wall_s for e in res.epochs[1:]]) * 1e3
    warm_rebuild = np.asarray([e.wall_s for e in res_rebuild.epochs[1:]])
    warm_resident = np.asarray([e.wall_s for e in res.epochs[1:]])
    stats = {
        "service_M": inst.num_coflows,
        "service_K": K,
        "service_pool": scen["pool_size"],
        "service_epochs": res.num_resolves,
        "service_warm_resolves": res.warm_resolves,
        "service_bound_margin_x": float(margin),
        "service_realized_wcct": float(res.realized_weighted_cct),
        "service_lp_lb": float(lb),
        "service_wall_s": float(res.wall_time_s),
    }
    if warm_rebuild.size and warm_resident.size:
        stats["service_epoch_rebuild_p50_ms"] = float(
            np.percentile(warm_rebuild, 50) * 1e3
        )
        stats["service_epoch_resident_p50_ms"] = float(
            np.percentile(warm_resident, 50) * 1e3
        )
        stats["service_epoch_warm_x"] = float(
            np.percentile(warm_rebuild, 50) / np.percentile(warm_resident, 50)
        )
    if resolves.size:
        for p in (50, 95, 99):
            stats[f"service_resolve_p{p}_ms"] = float(
                np.percentile(resolves, p)
            )
    return stats


def main(quick=False, scenario=None, trajectory=False):
    scenario = scenario or ("fb_quick" if quick else "fb_full")
    stats = {"bench": "trace", "trace_scenario": scenario}
    stats.update(bench_trace_sweep(scenario))
    stats.update(bench_service_long(scenario))
    for name, val in stats.items():
        print(f"trace,{name},{val:.6g}" if isinstance(val, float)
              else f"trace,{name},{val}")
    _merge_micro_json(stats)
    if trajectory:
        path = record_trajectory(stats)
        print(f"trajectory appended to {path}")
    return stats


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--scenario",
        choices=sorted(SCENARIOS),
        default=None,
        help="scenario to run (default: fb_quick with --quick, else fb_full)",
    )
    ap.add_argument(
        "--trajectory",
        action="store_true",
        help="append the stats to the repo-tracked BENCH_micro.json",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main(quick=args.quick, scenario=args.scenario, trajectory=args.trajectory)
