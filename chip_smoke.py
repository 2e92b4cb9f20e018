"""Bring-up smoke of Algorithm 1 on a TPU, through the user entry points.

    python chip_smoke.py              # one chip: offline, parity, online
    python chip_smoke.py --chips 4    # four chips: sharded vs single sweep

Phases of the one-chip run, each timed with the host clock around calls
that return host arrays (so the device work has finished):

  * ``offline`` — `sweep()` over the whole Facebook-like trace (526
    coflows, 150 ports, 266,260 demand entries, trace releases, K=2; the
    ``fb_full`` cell of `benchmarks.trace_scale`): the batched
    subgradient LP, the allocation scan and the kernel calendar, whose
    compiled program must hold the native Pallas `pair_resolve` round
    (``tpu_custom_call``).  Schedules are validated; the realized
    weighted CCT over the LP objective is printed next to 8K+1;
  * ``parity`` — the 192-coflow / 48-port / K=4 service cut through the
    device path, through the device scan with the NumPy calendar oracle,
    and through the all-NumPy reference, on both disciplines: CCTs must
    be bit-identical;
  * ``online`` — `stream()` with the resident epoch driver on the same
    cut, run twice: the second run may compile nothing.  A single-batch,
    no-preemption stream must replay offline `run_batch` bit for bit.

``--chips 4`` runs only the multi-device phase: `sweep(mesh=...)` over
the local devices against the single-device sweep on an ensemble of
trace cuts, row for row bit-identical.  The outputs of the sharded
sweep's LP, allocation scan and kernel calendar must each be split over
every device, an equal block of members on each.

The script needs a TPU and fails without one; ``--rehearse`` runs the
same phases on whatever backend JAX has, at small sizes, to check the
control flow (``JAX_PLATFORMS=cpu``; add
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for ``--chips 4``).
On a CPU the calendar is the host one, so a rehearsal checks no calendar
layout over devices.
The last line of the output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

_ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(_ROOT / "src"))

#: Sizes per mode.  ``full`` takes the whole published-trace cell and
#: its service cut (`benchmarks.trace_scale.SCENARIOS["fb_full"]`);
#: ``rehearse`` keeps the shapes of the path and cuts the scale so a CPU
#: runs it in minutes.
_SIZES = {
    "full": dict(
        offline=dict(num_coflows=526, num_ports=150, K=2, lp_iters=1200),
        cut=dict(num_coflows=192, num_ports=48, K=4, lp_iters=900),
        n_batches=24,
        pool_size=32,
        sharded=[(48, 24, 2), (48, 24, 4), (40, 16, 2), (64, 24, 3)],
        sharded_lp_iters=600,
    ),
    "rehearse": dict(
        offline=dict(num_coflows=40, num_ports=16, K=2, lp_iters=200),
        cut=dict(num_coflows=32, num_ports=12, K=4, lp_iters=200),
        n_batches=6,
        pool_size=12,
        sharded=[(12, 6, 2), (12, 6, 4), (10, 4, 2), (16, 6, 3)],
        sharded_lp_iters=150,
    ),
}


class _Compiles:
    """Counts XLA backend compiles and their seconds (JAX monitoring)."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def _cell(num_coflows, num_ports, K, seed=0):
    """A Facebook-trace cut as `benchmarks.trace_scale` specifies cells:
    trace releases, heterogeneous core rates 10, 20, ..., 10K."""
    from benchmarks.trace_scale import make

    return make(dict(
        gen="fb", num_coflows=num_coflows, num_ports=num_ports,
        rates=[10.0 * (k + 1) for k in range(K)], release="trace", seed=seed,
    ))


def _memory(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"peak {peak / 2**30:.3f} GiB"


def _phase(name, device, compiles, fn):
    """Run one phase; print its wall time, compiles and device memory."""
    n0, s0 = compiles.count, compiles.seconds
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    print(
        f"[{name}] wall {wall:.3f} s, {compiles.count - n0} compiles "
        f"({compiles.seconds - s0:.3f} s), memory {_memory(device)}",
        flush=True,
    )
    return out


def _offline(size, on_tpu):
    """The offline trace cell through `sweep()` with the kernel calendar."""
    from repro.experiments import sweep
    from repro.pipeline import batch_circuit as bc

    cfg = size["offline"]
    inst = _cell(cfg["num_coflows"], cfg["num_ports"], cfg["K"])
    K = inst.num_cores
    print(
        f"[offline] {inst.num_coflows} coflows, {inst.num_ports} ports, "
        f"K={K}, {int(np.count_nonzero(inst.demands))} demand entries",
        flush=True,
    )
    discipline = "greedy"
    engine = bc._engine()
    if on_tpu and engine != "kernel":
        raise RuntimeError(f"the calendar on a TPU resolved to {engine!r}")
    res = sweep(
        [inst], schemes=("ours",), lp_method="batch",
        lp_iters=cfg["lp_iters"], discipline=discipline, validate=True,
    )
    rec = res.records[0]
    ours = rec.results["ours"]
    # `sweep(validate=True)` validated every core schedule already; the
    # realized CCTs must also be the ones those schedules imply.
    from repro.core.validate import ccts_from_schedules, validate_schedule

    validate_schedule(inst, ours.core_schedules)
    if not np.array_equal(
        ccts_from_schedules(inst.num_coflows, ours.core_schedules), ours.ccts
    ):
        raise RuntimeError("offline CCTs disagree with their schedules")
    ratio = ours.total_weighted_cct / rec.lp.objective
    flows = sum(int(cs.src.shape[0]) for cs in ours.core_schedules)
    print(
        f"[offline] engine {engine}, {flows} flows scheduled, LP "
        f"{res.lp_time_s:.3f} s of {res.wall_time_s:.3f} s; weighted CCT "
        f"{ours.total_weighted_cct!r}, LP objective {rec.lp.objective!r}, "
        f"ratio {ratio!r} (8K+1 = {8 * K + 1})",
        flush=True,
    )

    # The calendar program that ran: the same bucket, lowered through the
    # same selection, must hold the Pallas round as a native TPU kernel.
    tabs = [
        t for t in bc.member_tables(inst, ours.allocation, ours.order)
        if t["coflow"].shape[0]
    ]
    if engine == "kernel":
        text = bc.lower_calendar(
            tabs, inst.num_ports, discipline
        ).compile().as_text()
        native = "tpu_custom_call" in text and "pair_resolve" in text
        if on_tpu and not native:
            raise RuntimeError("calendar program lacks the Pallas kernel")
        print(f"[offline] native pair_resolve kernel in the calendar: {native}")
    print(
        f"[offline] calendar bucket: {len(tabs)} members, "
        f"{max(t['coflow'].shape[0] for t in tabs)} flows in the largest",
        flush=True,
    )


def _parity(size):
    """Device scan and calendar against the NumPy oracles, bit for bit."""
    from repro.experiments import sweep

    cfg = size["cut"]
    inst = _cell(cfg["num_coflows"], cfg["num_ports"], cfg["K"])
    for discipline in ("greedy", "reserving"):
        kw = dict(
            schemes=("ours",), lp_method="batch", lp_iters=cfg["lp_iters"],
            discipline=discipline, validate=True,
        )
        device = sweep([inst], **kw).records[0]
        runs = {
            "device scan + NumPy calendar": sweep(
                [inst], circuit="loop", **kw
            ).records[0],
            "NumPy scan + NumPy calendar": sweep(
                [inst], alloc="loop", **kw
            ).records[0],
        }
        got = device.results["ours"]
        for name, ref in runs.items():
            want = ref.results["ours"]
            if ref.lp.objective != device.lp.objective or not np.array_equal(
                want.order, got.order
            ):
                raise RuntimeError(f"{discipline}: LP order differs ({name})")
            same_alloc = all(
                np.array_equal(getattr(got.allocation, f),
                               getattr(want.allocation, f))
                for f in ("core", "coflow", "src", "dst", "size")
            )
            if not np.array_equal(got.ccts, want.ccts):
                raise RuntimeError(
                    f"{discipline}: device CCTs differ from {name} "
                    f"(allocations {'equal' if same_alloc else 'differ'})"
                )
        print(
            f"[parity] {discipline}: {inst.num_coflows} coflows, "
            f"{inst.num_ports} ports, K={inst.num_cores}: CCTs bit-identical "
            f"to the NumPy oracles, weighted CCT {got.total_weighted_cct!r}",
            flush=True,
        )


def _jit_entries():
    """Compiled-program counts of the epoch's device steps."""
    from repro.core import lp
    from repro.pipeline import batch_alloc
    from repro.pipeline import batch_circuit as bc

    fns = {
        "lp": lp._subgradient_run_batch,
        "scan": batch_alloc._scan_all,
        "calendar": (bc._run_calendar_pairs_donated, bc._run_calendar_pairs),
    }
    return {
        k: sum(f._cache_size() for f in (v if isinstance(v, tuple) else (v,)))
        for k, v in fns.items()
    }


def _online(size, compiles):
    """Resident `stream()` twice (nothing compiles the second time) and
    the single-batch replay of offline `run_batch`."""
    from repro.core import lp
    from repro.experiments import stream
    from repro.pipeline import get_pipeline

    cfg = size["cut"]
    inst = _cell(cfg["num_coflows"], cfg["num_ports"], cfg["K"])
    kw = dict(
        lp_method="batch", lp_iters=cfg["lp_iters"],
        n_batches=size["n_batches"], pool_size=size["pool_size"],
        warm_start=True, validate=True, epoch_mode="resident",
    )
    t0 = time.perf_counter()
    cold = stream(inst, **kw)
    t_cold = time.perf_counter() - t0
    before, n0 = _jit_entries(), compiles.count
    t0 = time.perf_counter()
    warm = stream(inst, **kw)
    t_warm = time.perf_counter() - t0
    after = _jit_entries()
    grown = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    if grown or compiles.count != n0:
        raise RuntimeError(
            f"warm stream compiled {compiles.count - n0} programs "
            f"(new epoch-step entries: {grown})"
        )
    if not (
        np.array_equal(cold.finish, warm.finish)
        and cold.realized_weighted_cct == warm.realized_weighted_cct
    ):
        raise RuntimeError("two identical streams disagree")
    epochs = np.asarray([e.wall_s for e in warm.epochs]) * 1e3
    print(
        f"[online] {warm.num_resolves} epochs ({warm.warm_resolves} warm), "
        f"cold {t_cold:.3f} s, warm {t_warm:.3f} s, epoch p50 "
        f"{np.percentile(epochs, 50):.3f} ms p99 "
        f"{np.percentile(epochs, 99):.3f} ms, 0 compiles after warm-up, "
        f"realized weighted CCT {warm.realized_weighted_cct!r}",
        flush=True,
    )

    sols = lp.solve_subgradient_batch([inst], iters=cfg["lp_iters"])
    off = get_pipeline(
        "ours", lp_method="batch", lp_iters=cfg["lp_iters"]
    ).run_batch([inst], lp_solutions=sols)[0]
    one = stream(
        inst, lp_method="batch", lp_iters=cfg["lp_iters"], n_batches=1,
        preempt=False, epoch_mode="resident",
    )
    e0 = one.epochs[0]
    if not (
        one.num_resolves == 1
        and np.array_equal(e0.order, off.order)
        and np.array_equal(e0.ccts, off.ccts)
        and one.realized_weighted_cct == float(np.dot(inst.weights, off.ccts))
    ):
        raise RuntimeError("single-batch stream does not replay run_batch")
    print("[online] single-batch stream replays run_batch bit for bit",
          flush=True)


@contextlib.contextmanager
def _placements(log):
    """Record how the batched stages' outputs are laid out over devices.

    While active, every call of the LP solve, the allocation scan and the
    sharded kernel calendar appends ``(stage, {device id: member rows})``
    for its first output to ``log``.
    """
    from repro.core import lp
    from repro.pipeline import batch_alloc
    from repro.pipeline import batch_circuit as bc

    def spy(stage, fn):
        def run(*args, **kw):
            out = fn(*args, **kw)
            first = jax.tree_util.tree_leaves(out)[0]
            log.append((stage, {
                s.device.id: s.data.shape[0] for s in first.addressable_shards
            }))
            return out
        return run

    import jax

    saved = (
        lp._subgradient_run_batch, batch_alloc._scan_all,
        bc._run_calendar_pairs_sharded,
    )
    lp._subgradient_run_batch = spy("LP", saved[0])
    batch_alloc._scan_all = spy("scan", saved[1])
    bc._run_calendar_pairs_sharded = lambda *a, **k: spy(
        "calendar", saved[2](*a, **k)
    )
    try:
        yield
    finally:
        (lp._subgradient_run_batch, batch_alloc._scan_all,
         bc._run_calendar_pairs_sharded) = saved


def _sharded(size):
    """`sweep(mesh=...)` over every local device against one device."""
    import jax

    from repro.experiments import sweep
    from repro.launch.mesh import make_local_mesh
    from repro.pipeline import batch_circuit as bc

    ens = [
        _cell(m, n, k, seed=s) for s, (m, n, k) in enumerate(size["sharded"])
    ]
    metas = [{"cell": i} for i in range(len(ens))]
    mesh = make_local_mesh()
    n_dev = len(jax.devices())
    # The platform picks the calendar: the sharded device calendar on a
    # TPU (checked by the one-chip run), the host `wide` calendar in a
    # `--rehearse` run on the CPU, which then has no calendar layout.
    stages = ["LP", "scan"]
    if bc._engine() == "kernel":
        stages.append("calendar")
    kw = dict(
        schemes=("ours", "wspt_order"), lp_iters=size["sharded_lp_iters"],
        metas=metas,
    )
    single = sweep(ens, **kw)
    log = []
    with _placements(log):
        sharded = sweep(ens, mesh=mesh, **kw)

    # Each stage's members must be split over every device, equally.
    for stage in stages:
        layouts = [rows for name, rows in log if name == stage]
        if not layouts:
            raise RuntimeError(f"the sharded sweep ran no {stage}")
        for rows in layouts:
            if len(rows) != n_dev or len(set(rows.values())) != 1:
                raise RuntimeError(
                    f"{stage} output is not split over the {n_dev} devices: "
                    f"rows per device {rows}"
                )
        print(
            f"[sharded] {stage}: {len(layouts)} calls, each split "
            f"{sorted(set(r for rows in layouts for r in rows.values()))} "
            f"members per device over {n_dev} devices",
            flush=True,
        )

    for a, b in zip(single.records, sharded.records):
        if a.lp.objective != b.lp.objective or not np.array_equal(
            a.lp.completion, b.lp.completion
        ):
            raise RuntimeError(f"cell {a.index}: sharded LP differs")
        for s in a.results:
            if not np.array_equal(a.results[s].ccts, b.results[s].ccts):
                raise RuntimeError(f"cell {a.index}, {s}: sharded CCTs differ")
    if single.rows() != sharded.rows():
        raise RuntimeError("sharded rows differ")
    print(
        f"[sharded] {len(ens)} cells x {len(single.records[0].results)} "
        f"schemes over {n_dev} devices: rows bit-identical; single "
        f"{single.wall_time_s:.3f} s, sharded {sharded.wall_time_s:.3f} s",
        flush=True,
    )


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the multi-device phase, on four devices",
    )
    ap.add_argument(
        "--rehearse", action="store_true",
        help="small sizes on any backend (control-flow check, no result "
        "about the chip)",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    print(devices, flush=True)
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not (on_tpu or args.rehearse):
        raise SystemExit(f"no TPU: JAX found {dev.platform} devices")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, "
                         f"JAX found {len(devices)}")

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    size = _SIZES["rehearse" if args.rehearse else "full"]
    compiles = _Compiles()
    t0 = time.perf_counter()
    if args.chips == 4:
        _phase("sharded", dev, compiles, lambda: _sharded(size))
    else:
        _phase("offline", dev, compiles, lambda: _offline(size, on_tpu))
        _phase("parity", dev, compiles, lambda: _parity(size))
        _phase("online", dev, compiles, lambda: _online(size, compiles))
    print(
        f"[total] {time.perf_counter() - t0:.3f} s, {compiles.count} "
        f"compiles ({compiles.seconds:.3f} s)",
        flush=True,
    )
    result = {
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result))


if __name__ == "__main__":
    main()
