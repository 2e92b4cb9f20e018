"""Device probes behind the numbers in PERF.md that `chip_smoke.py` does
not print.

    python benchmarks/chip_probe.py            # every probe
    python benchmarks/chip_probe.py --only lp_pad calendar

Probes (each prints its lines; run on a TPU for device numbers — on any
other backend they describe that backend):

  * ``f64`` — how far the backend's float64 is from IEEE doubles: 65,536
    doubles of the scheduler's magnitudes (1e-3 .. 1e6) taken to the
    device and back, and added, multiplied and divided there, each
    counted against NumPy.  This is why the scan and the calendar carry
    doubles as int64 bit patterns (`repro.pipeline.exact64`);
  * ``lp_pad`` — the batched subgradient LP at one member (as XLA would
    lower a lone solve) and at two (as `solve_subgradient_batch_arrays`
    pads it), warm, at the service epoch's shape and at the whole
    trace's: what the two-member floor costs;
  * ``calendar`` — the allocation scan's time (cold, then warm) and the
    kernel calendar's time per lockstep round on the whole trace's K=2
    bucket (WSPT order, device allocation scan): warm
    runs bounded at two round budgets, per round = difference over the
    budgets' difference.  With ``--profile DIR`` the longer run is traced
    into DIR and its device time per XLA op is printed.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import numpy as np

_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT / "src"))
sys.path.insert(0, str(_ROOT))


def _warm(fn, reps=5):
    """Median wall seconds of ``fn()`` after one untimed call."""
    fn()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def probe_f64():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    x = 10.0 ** rng.uniform(-3, 6, 65_536)
    y = 10.0 ** rng.uniform(-3, 6, 65_536)
    with jax.enable_x64():
        dx, dy = jnp.asarray(x), jnp.asarray(y)
        got = {
            "round trip": (np.asarray(dx), x),
            "add": (np.asarray(jax.jit(jnp.add)(dx, dy)), x + y),
            "mul": (np.asarray(jax.jit(jnp.multiply)(dx, dy)), x * y),
            "div": (np.asarray(jax.jit(jnp.divide)(dx, dy)), x / y),
        }
    for name, (g, w) in got.items():
        rel = np.abs(g - w) / w
        print(
            f"[f64] {name}: {int(np.count_nonzero(g != w))} of {w.size} "
            f"differ from NumPy, max relative error {float(rel.max())!r}",
            flush=True,
        )


def probe_lp_pad(iters=900):
    import jax

    from benchmarks.trace_scale import make
    from repro.core import lp

    shapes = {
        "service epoch (32 coflows, 48 ports)": (32, 48),
        "whole trace (526 coflows, 150 ports)": (526, 150),
    }
    for label, (m, n) in shapes.items():
        inst = make(dict(
            gen="fb", num_coflows=m, num_ports=n, rates=[10.0, 20.0],
            release="trace", seed=0,
        ))
        arrays = lp.pack_lp_arrays([inst])
        names = (
            "Y0", "p_rho", "p_tau", "weights", "releases", "inv_R",
            "delta_over_K", "coflow_mask", "port_mask",
        )
        for B in (1, 2):
            ins = [
                jax.device_put(lp._pad_members(arrays[k], B)) for k in names
            ]

            def run():
                out = lp._subgradient_run_batch(*ins, iters=iters)
                jax.block_until_ready(out)

            print(
                f"[lp_pad] {label}, {iters} steps, B={B}: "
                f"{_warm(run) * 1e3:.3f} ms",
                flush=True,
            )


def probe_calendar(
    num_coflows=526, num_ports=150, budgets=(50, 550), profile_dir=None
):
    import jax

    from benchmarks.trace_scale import make
    from repro.core.ordering import wspt_order
    from repro.pipeline import batch_alloc
    from repro.pipeline import batch_circuit as bc

    inst = make(dict(
        gen="fb", num_coflows=num_coflows, num_ports=num_ports,
        rates=[10.0, 20.0], release="trace", seed=0,
    ))
    order = wspt_order(inst)
    for run in ("cold", "warm"):
        t0 = time.perf_counter()
        (alloc,) = batch_alloc.allocate_batch([inst], [order])
        print(
            f"[calendar] allocation scan ({run}): "
            f"{time.perf_counter() - t0:.3f} s",
            flush=True,
        )
    tabs = [
        t for t in bc.member_tables(inst, alloc, order) if t["coflow"].shape[0]
    ]
    pad = bc._pad_members(tabs, inst.num_ports)
    fn, args, statics, _ = bc._calendar_program(pad, "greedy")
    print(
        f"[calendar] device calendar, G={pad['G']} Fmax={pad['Fmax']} "
        f"Nmax={pad['Nmax']}, flows per member "
        f"{[t['coflow'].shape[0] for t in tabs]}",
        flush=True,
    )
    secs = {}
    with jax.enable_x64():
        for bound in budgets:
            kw = dict(statics, bound=bound)

            def run():
                # Fresh inputs each call: the program donates its buffers.
                out = fn(*(jax.device_put(a) for a in args), **kw)
                jax.block_until_ready(out)

            secs[bound] = _warm(run, reps=3)
            print(
                f"[calendar] {bound} rounds: {secs[bound] * 1e3:.3f} ms",
                flush=True,
            )
        lo, hi = budgets
        per = (secs[hi] - secs[lo]) / (hi - lo)
        print(f"[calendar] per round {per * 1e3:.4f} ms", flush=True)
        if profile_dir:
            with jax.profiler.trace(profile_dir):
                run()
            _top_ops(profile_dir, hi)


def _top_ops(profile_dir, rounds, top=12):
    """Device time per XLA op of the traced run, per round, largest first."""
    import jax

    for path in sorted(Path(profile_dir).rglob("*.xplane.pb")):
        data = jax.profiler.ProfileData.from_file(str(path))
        for plane in data.planes:
            if not plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                ms = {}
                for ev in line.events:
                    ms[ev.name] = ms.get(ev.name, 0.0) + ev.duration_ns / 1e6
                for name, t in sorted(ms.items(), key=lambda kv: -kv[1])[:top]:
                    print(
                        f"[profile] {t / rounds:9.4f} ms/round  {name[:140]}",
                        flush=True,
                    )


PROBES = {"f64": probe_f64, "lp_pad": probe_lp_pad, "calendar": probe_calendar}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--only", nargs="+", choices=sorted(PROBES))
    ap.add_argument("--profile", metavar="DIR",
                    help="trace the calendar probe's longer run into DIR")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    print(f"{dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {enable_compile_cache()}", flush=True)
    for name in args.only or PROBES:
        if name == "calendar":
            probe_calendar(profile_dir=args.profile)
        else:
            PROBES[name]()


if __name__ == "__main__":
    main()
