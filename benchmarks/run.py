"""Benchmark runner: one module per paper table/figure + framework benches.

The figure reproductions (fig3/fig5/fig6) are shells over the
`repro.experiments` ensemble engine: each builds its instance ensemble,
runs one `sweep()` with a shared (batched or exact) LP phase and the
post-LP schemes executed batch-first through the `repro.pipeline` API,
and exports flat rows.  Results land as JSON + CSV under ``REPRO_RESULTS``
(default ``results/benchmarks/``).  ``--quick`` shrinks sweeps for
CI-speed runs; ``--alloc loop`` pins the figure sweeps to the
per-instance NumPy allocation reference instead of the batched path.
"""

from __future__ import annotations

import argparse
import time


def _benches():
    from benchmarks import (
        eps_variant,
        fig3_default,
        fig4_cdf,
        fig5_ports,
        fig6_ratio,
        localsearch_gain,
        micro,
        planner_gain,
        table3_delta,
        trace_scale,
    )

    return {
        "fig3": fig3_default.main,
        "fig4": fig4_cdf.main,
        "table3": table3_delta.main,
        "fig5": fig5_ports.main,
        "fig6": fig6_ratio.main,
        "eps": eps_variant.main,
        "micro": micro.main,
        "planner": planner_gain.main,
        "localsearch": localsearch_gain.main,
        "trace": trace_scale.main,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--only",
        default=None,
        help="comma-separated subset: fig3,fig4,table3,fig5,fig6,eps,micro,"
        "planner,localsearch,trace",
    )
    ap.add_argument(
        "--list", action="store_true", help="list benchmark names and exit"
    )
    ap.add_argument(
        "--alloc",
        choices=("batch", "loop"),
        default="batch",
        help="post-LP allocation path for the figure sweeps "
        "(batch = Pipeline.run_batch, loop = per-instance reference)",
    )
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    benches = _benches()
    if args.list:
        for name in benches:
            print(name)
        return
    if args.only:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        unknown = [n for n in names if n not in benches]
        if unknown:
            ap.error(
                f"unknown benchmark(s) {', '.join(unknown)}; "
                f"choose from: {', '.join(benches)}"
            )
        chosen = {n: benches[n] for n in names}
    else:
        chosen = benches
    # Figure sweeps accept the post-LP allocation path; other benches don't.
    takes_alloc = {"fig3", "fig5", "fig6"}
    t0 = time.perf_counter()
    for name, fn in chosen.items():
        print(f"### {name}", flush=True)
        t = time.perf_counter()
        kwargs = {"quick": args.quick}
        if name in takes_alloc:
            kwargs["alloc"] = args.alloc
        fn(**kwargs)
        print(f"### {name} done in {time.perf_counter()-t:.1f}s\n", flush=True)
    from repro.experiments import results

    print(
        f"all benchmarks done in {time.perf_counter()-t0:.1f}s "
        f"(results in {results.results_dir()}/)"
    )


if __name__ == "__main__":
    main()
