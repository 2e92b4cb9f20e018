"""Records the small device trace that `test_tracefile.py` reads, and
prints what a trace of one cell holds, for reading it by hand.

    python bench/tests/record_trace.py --workload fb150_k2.sweep --out bench/tests/data

Needs a TPU.  Traces one call of the cell after its warm-up, prints the
planes and lines, the busiest names of each and how the host spans line
up with the device events, and writes the events of the first
``--keep`` device operations (with the spans cut to them) as JSON.
"""

from __future__ import annotations

import argparse
import collections
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--keep", type=int, default=400)
    args = ap.parse_args(argv)

    import jax
    from jax.profiler import ProfileData

    from harness import cell, gen, registry, tracefile

    cell.check_devices(1)
    cell.enable_cache(cell.CACHE_DIR)
    bench = registry.benchmark()
    wl = registry.workload(bench, args.workload)
    config, traffic = registry.config(wl["config"]), registry.traffic(
        wl["traffic"])
    inst = gen.relabel_ports(gen.config_instance(config), 1)
    driver = registry.driver(traffic["driver"])(config, traffic, inst)
    driver.call()
    tmp = tempfile.mkdtemp(prefix="bench-record-")
    with jax.profiler.trace(tmp):
        with jax.profiler.TraceAnnotation(tracefile.WINDOW_SPAN), \
                jax.profiler.TraceAnnotation(f"bench.{driver.kind}"):
            t0 = time.perf_counter()
            driver.call()
            print(f"traced call {time.perf_counter() - t0:.3f} s")
    path = str(sorted(Path(tmp).rglob("*.xplane.pb"))[-1])
    data = ProfileData.from_file(path)
    ev = tracefile.load(path)
    shutil.rmtree(tmp, ignore_errors=True)
    for plane in data.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            t = [e.start_ns for e in evs]
            print(f"  LINE {line.name!r}: {len(evs)} events"
                  + (f", {min(t):.0f}..{max(t):.0f} ns" if t else ""))
            for n, c in names.most_common(8):
                print(f"      {c:7d}  {n[:120]}")
    t0 = time.perf_counter()
    red = tracefile.reduce(ev)
    print(f"reduced {len(ev['ops'])} operations in "
          f"{time.perf_counter() - t0:.3f} s")
    print(json.dumps(red, indent=1))
    print("programs", sorted(red["program_s"].items(), key=lambda kv: -kv[1]))
    ops = sorted(ev["ops"], key=lambda e: e[2])[: args.keep]
    t_end = max(e[3] for e in ops)
    t_start = min(e[2] for e in ops)
    keep = {
        "ops": ops,
        "modules": [m for m in ev["modules"] if m[2] < t_end],
        "spans": [(d, n, max(s, t_start - 1000.0), min(e, t_end + 1000.0))
                  for d, n, s, e in ev["spans"] if s < t_end],
    }
    out = Path(args.out) / f"trace_{args.workload}.json"
    out.write_text(json.dumps(keep))
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
