"""Readings of every number the benchmark compares, for the program and
for the control, on many seeds in one process (one set-up).

    python bench/tests/readings.py --workload fb150_k2.sweep --seeds 1 2 3 --control 1 2 3
    python bench/tests/readings.py --workload fb150_k2.sweep --seeds 4 5 6 --fault state_unchanged

For each seed: one call of the cell's work, checked against the float64
reference (the program's readings), and, for the seeds under
``--control``, the reference computed in float32 put in the program's
place (the control's readings).  ``--fault`` plants one of
`test_faults.FAULTS` in the program first, so the readings are the
fault's.  The limits in `PERF.md` are set from these.  Needs a TPU
unless called with ``require_tpu=False`` (tests).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def readings(cell: str, seeds, control_seeds, *, require_tpu=True,
             config=None, cache=True, log=print) -> list[dict]:
    from harness import cell as cell_mod
    from harness import gen, registry

    bench = registry.benchmark()
    wl = registry.workload(bench, cell)
    if require_tpu:
        cell_mod.check_devices(wl["chips"])
    if cache:
        cell_mod.enable_cache(cell_mod.CACHE_DIR)
    config = config or registry.config(wl["config"])
    traffic = registry.traffic(wl["traffic"])
    base = gen.config_instance(config)
    rows = []
    for seed in sorted(set(seeds) | set(control_seeds)):
        driver = registry.driver(traffic["driver"])(
            config, traffic, gen.relabel_ports(base, seed))
        t0 = time.perf_counter()
        out = driver.call()
        t1 = time.perf_counter()
        row = {"seed": seed, "call_s": t1 - t0}
        if seed in seeds:
            row["program"] = {k: v for k, (v, _) in driver.check([out]).items()}
        if seed in control_seeds:
            row["control"] = {k: v for k, (v, _) in
                              driver.check([out], control=True).items()}
        row["check_s"] = time.perf_counter() - t1
        log(json.dumps(row))
        rows.append(row)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control", type=int, nargs="*", default=[])
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    if args.fault:
        import importlib

        from test_faults import FAULTS

        module, name, plant = FAULTS[args.fault]
        mod = importlib.import_module(module)
        setattr(mod, name, plant(getattr(mod, name)))
    readings(args.workload, args.seeds, args.control,
             log=lambda s: print(s, flush=True))


if __name__ == "__main__":
    main()
