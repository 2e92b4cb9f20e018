"""Host spans and counters on the profiler's clock.

Every host phase of `sweep()` and `stream()` runs inside a `span`, and
every event worth counting goes through `count`:

  * ``span(name)`` opens ``jax.profiler.TraceAnnotation("repro." +
    name)``, so under a `jax.profiler.trace` session the phase sits on
    the host plane next to the device events and any caller's own
    annotations, and adds its host-clock seconds to the current tally;
  * ``count(name, n)`` adds ``n`` to the current tally;
  * ``to_host(*arrays)`` is the one device -> host read of stage and
    service code: it returns NumPy arrays and counts ``host_reads``.

The current tally is a `contextvars.ContextVar`.  ``collect()`` installs
a fresh `Tally` for a block and, when the block ends, adds its totals to
the tally that was current before (so a caller's tally sees everything
its callees collected).  `sweep()` and `stream()` collect per call, and
`stream()` also per epoch (`EpochRecord.spans` / ``counts``).  With no
tally installed, spans only open the annotation and counts do nothing.
Spans are host-side only: nothing here runs inside a jitted function.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import time
from typing import Iterator

import numpy as np

import jax

__all__ = ["PREFIX", "Tally", "collect", "count", "span", "to_host"]

#: Prefix of every span name in a profiler trace.
PREFIX = "repro."


@dataclasses.dataclass
class Tally:
    """Inclusive seconds per span name and totals per counter name."""

    spans: dict[str, float] = dataclasses.field(default_factory=dict)
    counts: dict[str, int] = dataclasses.field(default_factory=dict)

    def add(self, other: "Tally") -> None:
        for k, v in other.spans.items():
            self.spans[k] = self.spans.get(k, 0.0) + v
        for k, v in other.counts.items():
            self.counts[k] = self.counts.get(k, 0) + v


_CURRENT: contextvars.ContextVar[Tally | None] = contextvars.ContextVar(
    "repro_trace_tally", default=None
)


@contextlib.contextmanager
def collect() -> Iterator[Tally]:
    """Install a fresh `Tally` for the block; on exit add it to the one
    that was current before (if any)."""
    tally = Tally()
    token = _CURRENT.set(tally)
    try:
        yield tally
    finally:
        _CURRENT.reset(token)
        outer = _CURRENT.get()
        if outer is not None:
            outer.add(tally)


class span:
    """``with span(name) as s:`` — a named host phase.

    Opens the profiler annotation ``"repro." + name`` and, on exit, adds
    the phase's host-clock seconds to the current tally under ``name``;
    ``s.seconds`` holds them afterwards.  Nested spans each count their
    own (inclusive) time.
    """

    __slots__ = ("name", "seconds", "_annotation", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._annotation = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        tally = _CURRENT.get()
        if tally is not None:
            tally.spans[self.name] = (
                tally.spans.get(self.name, 0.0) + self.seconds
            )


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the current tally (if any)."""
    tally = _CURRENT.get()
    if tally is not None:
        tally.counts[name] = tally.counts.get(name, 0) + int(n)


def to_host(*arrays):
    """NumPy values of ``arrays``, counting each device array read as one
    ``host_reads``; NumPy inputs pass through uncounted.  Returns one
    array for one argument, else a tuple.

    The reads are allowed under ``jax.transfer_guard_device_to_host(
    "disallow")``, so a run under that guard proves every other device ->
    host read is gone."""
    reads = sum(isinstance(a, jax.Array) for a in arrays)
    if reads:
        count("host_reads", reads)
        with jax.transfer_guard_device_to_host("allow"):
            out = [np.asarray(a) for a in arrays]
    else:
        out = [np.asarray(a) for a in arrays]
    return out[0] if len(out) == 1 else tuple(out)
