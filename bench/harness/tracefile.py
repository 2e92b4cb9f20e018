"""Reduction of a profiler trace to the benchmark's device numbers.

`load` reads an ``.xplane.pb`` into plain event lists: device operations
and device program (XLA module) executions per TPU, and the benchmark's
own host spans (`SPAN_PREFIX`).  `reduce` works on those lists alone, so
it is tested on a small recorded trace kept beside the tests.

Busy time is the union of the intervals in which an operation ran on a
device (leaf operations: not the loops that contain them), averaged over
the devices; the window is the benchmark's
``bench.window`` span; an idle gap is named after the innermost other
benchmark span that holds it and the device program it lies in (the
device idles between the operations of a program's loop) or, between
programs, the one that ran before it (the host was busy).
"""

from __future__ import annotations

import collections

import numpy as np

#: Host spans the benchmark writes (`jax.profiler.TraceAnnotation`).
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load(path: str) -> dict:
    """Plain event lists ``(device, name, start_ns, end_ns)`` from a trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name[len("/device:"):]
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    dest.append((dev, e.name, float(e.start_ns),
                                 float(e.start_ns + e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(("host", e.name, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
    return {"ops": ops, "modules": modules, "spans": spans}


def _union(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def leaf_ops(ops):
    """Operations that contain no other: a control-flow operation (a
    while loop, a conditional) spans the operations it runs, and its own
    interval would hide the device's idle time between them."""
    out = []
    for dev in sorted({d for d, *_ in ops}):
        mine = sorted((o for o in ops if o[0] == dev),
                      key=lambda o: (o[2], -o[3]))
        for a, b in zip(mine, mine[1:] + [None]):
            if b is None or not (b[2] < a[3] and b[3] <= a[3]):
                out.append(a)
    return out


def program_name(module_event_name: str) -> str:
    """``jit__scan_all(42)`` -> ``jit__scan_all``."""
    return module_event_name.split("(")[0]


def reduce(events: dict, top: int = 10) -> dict | None:
    """Busy and window seconds, device seconds per program, the operations
    with the most device time and the idle time by host span; None
    without a window or a device operation."""
    win = [(s, e) for _, n, s, e in events["spans"] if n == WINDOW_SPAN]
    ops = leaf_ops(events["ops"])
    devices = sorted({d for d, *_ in ops})
    if not win or not devices:
        return None
    w0, w1 = win[0]
    spans = [(n, s, e) for _, n, s, e in events["spans"] if n != WINDOW_SPAN]
    busy_ns = 0.0
    gaps = collections.Counter()
    counts = collections.Counter()
    for dev in devices:
        busy = _union(
            (max(s, w0), min(e, w1)) for d, _, s, e in ops
            if d == dev and e > w0 and s < w1
        )
        busy_ns += sum(e - s for s, e in busy)
        mods = sorted((s, e, program_name(n)) for d, n, s, e in
                      events["modules"] if d == dev)
        mod_starts = np.asarray([s for s, _, _ in mods])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            inner = [(e - s, n) for n, s, e in spans if s <= mid <= e]
            span = min(inner)[1] if inner else WINDOW_SPAN
            k = int(np.searchsorted(mod_starts, mid, side="right")) - 1
            if k < 0:
                where = "before any program"
            elif mid < mods[k][1]:
                where = f"in {mods[k][2]}"
            else:
                where = f"after {mods[k][2]}"
            name = f"{span} {where}"
            gaps[name] += (g1 - g0) / len(devices)
            counts[name] += 1
    per_program = collections.Counter()
    for d, n, s, e in events["modules"]:
        per_program[program_name(n)] += (e - s) / len(devices)
    per_op = collections.Counter()
    for dev in devices:
        mods = sorted((s, e, program_name(n)) for d, n, s, e in
                      events["modules"] if d == dev)
        starts = np.asarray([s for s, _, _ in mods])
        for d, n, s, e in ops:
            if d != dev:
                continue
            k = int(np.searchsorted(starts, s, side="right")) - 1
            if k >= 0 and s < mods[k][1]:
                n = f"{mods[k][2]}/{n}"
            per_op[n] += (e - s) / len(devices)
    return {
        "devices": len(devices),
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / len(devices) / 1e9,
        "program_s": {k: v / 1e9 for k, v in per_program.items()},
        "device_ops": [[n[:200], v / 1e9] for n, v in per_op.most_common(top)],
        "idle_gaps": [[f"{n} (x{counts[n]})", v / 1e9]
                      for n, v in gaps.most_common(top)],
    }
