"""One run of one cell: set-up, the measured (or traced) window, the
check against the reference, and the result line's contents."""

from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time
from pathlib import Path

from harness import gen, registry, tracefile
from harness.driver import run_window

#: JAX's persistent compilation cache, at a fixed path in the checkout.
CACHE_DIR = registry.ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Compiles:
    """Counts programs compiled or loaded from the persistent cache, compile
    requests and cache hits (JAX monitoring)."""

    def __init__(self):
        import jax

        self.backend = 0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.backend, self.requests


@dataclasses.dataclass
class Ctx:
    """What a metric reader may read."""

    driver: object
    setup_s: float
    outs: list
    elapsed: float
    trace: dict | None

    def rate(self) -> float | None:
        """The window's work (flows, epochs) per host-clock second."""
        if not self.outs:
            return None
        return sum(self.driver.work(o) for o in self.outs) / self.elapsed

    def device_per_unit(self, programs, scale: float = 1.0):
        """Device seconds (times ``scale``) of the named XLA programs in
        the trace over the traced calls' units (sweeps, epochs: the
        driver's ``units``); None where the trace holds none of them."""
        per = self.driver.units(self.outs)
        if self.trace is None or not per:
            return None
        found = [s for n, s in self.trace["program_s"].items()
                 if n in programs]
        return scale * sum(found) / per if found else None

    def idle_percent(self):
        """Percent of the traced window in which no operation ran on the
        device (1 - busy / window)."""
        if self.trace is None:
            return None
        return 100.0 * (1.0 - self.trace["busy_s"] / self.trace["window_s"])


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(tracefile.SPAN_PREFIX + name)


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")


def enable_cache(path: Path):
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(bench: dict, cell: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_tpu: bool = True,
             cache_dir: Path | None = CACHE_DIR, config: dict | None = None,
             log=print) -> dict:
    """Run one cell once; returns the result object (see `bench/run.py`).

    ``require_tpu``, ``cache_dir`` and ``config`` exist for the tests,
    which run tiny configurations on the CPU; the command line never
    changes them.
    """
    import jax

    wl = registry.workload(bench, cell)
    if require_tpu:
        check_devices(wl["chips"])
    if cache_dir is not None:
        enable_cache(cache_dir)
    config = config or registry.config(wl["config"])
    traffic = registry.traffic(wl["traffic"])
    compiles = Compiles()
    dev = jax.devices()[0]

    with _annotate("build"):
        inst = gen.relabel_ports(gen.config_instance(config), seed)
        driver = registry.driver(traffic["driver"])(config, traffic, inst)
    log(f"[setup] {cell}: {driver.inst.num_coflows} coflows, "
        f"{driver.inst.num_ports} ports, K={driver.inst.num_cores}, "
        f"{driver.inst.num_flows} flows, seed {seed}")
    with _annotate("warmup"):
        warm = driver.call()
    setup_s = time.perf_counter() - t_start
    n_setup = compiles.snapshot()
    log(f"[setup] {setup_s:.3f} s, {n_setup[0]} programs compiled or "
        f"loaded, {n_setup[1]} compile requests, {compiles.hits} cache hits")

    trace_red = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            with jax.profiler.trace(tmp):
                with _annotate("window"), _annotate(driver.kind):
                    t0 = time.perf_counter()
                    outs = [driver.call()]
                    elapsed = time.perf_counter() - t0
            paths = sorted(Path(tmp).rglob("*.xplane.pb"))
            trace_red = tracefile.reduce(tracefile.load(str(paths[-1])))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    else:
        outs, elapsed = run_window(driver, seconds)
    in_window = tuple(a - b for a, b in zip(compiles.snapshot(), n_setup))
    log(f"[window] {len(outs)} {driver.kind}s in {elapsed:.3f} s of "
        f"{seconds} s asked; {in_window[0]} programs compiled or loaded, "
        f"{in_window[1]} compile requests inside the window")
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    with _annotate("reference"):
        t0 = time.perf_counter()
        checks = driver.check(outs + [warm])
        log(f"[reference] {time.perf_counter() - t0:.3f} s")
    checks["window_compiles"] = (in_window[0] + in_window[1], 0)
    passed = all(v <= lim for v, lim in checks.values())
    # A window call fails when the checked output fails, or when it does
    # not repeat the checked output bit for bit.
    first_ok = all(v <= lim for k, (v, lim) in checks.items()
                   if k != "repeat_mismatch")
    attempted = len(outs)
    failed = checks["repeat_mismatch"][0] if first_ok else attempted

    ctx = Ctx(driver, setup_s, outs, elapsed, trace_red)
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in registry.cell_metrics(bench, cell, section):
        value = registry.metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": passed, "attempted": attempted,
           "failed": min(attempted, failed),
           "metrics": metrics, "device": device}
    if trace_red is not None:
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
        out["breakdown"] = {"device_ops": trace_red["device_ops"],
                            "idle_gaps": trace_red["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out
