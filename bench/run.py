"""Run one benchmark cell once on the chip this machine holds.

    python bench/run.py --workload fb150_k2.sweep --seed 7 --seconds 51 --trace 0

Cells, metrics and their bounds are in `BENCHMARK.json`; a cell's
configuration, traffic mix and metric readers are files under `bench/`
found by name (`harness.registry`).  The run sets up (instance from the
configuration and ``--seed``, one untimed call of the window's work, so
every program is compiled or loaded from `.jax_cache/`), then measures
for ``--seconds`` (``--trace 0``: the cell's end-to-end metrics) or
traces one call (``--trace 1``: its per-layer metrics), and checks what
it produced against the plain NumPy reference.  The last line of
standard output is the result object; the numbers compared, each beside
its limit, are the last lines of standard error and the result's last
key.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import registry
    from harness.cell import NoChip, run_cell

    try:
        result = run_cell(
            registry.benchmark(), args.workload, args.seed, args.seconds,
            bool(args.trace), T_START,
            log=lambda s: print(s, flush=True),
        )
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
