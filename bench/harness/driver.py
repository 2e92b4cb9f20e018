"""What every driver shares: the program's view of an instance, the
limits that follow from the configuration, and the measured window.

A traffic file names its driver (``"driver"``), a class ``Driver`` in
``bench/drivers/<driver>.py`` built from the configuration, the traffic
mix and the instance.  Its ``call()`` is the work the window repeats,
``work(out)`` what a call did (flows, epochs), ``units(outs)`` the calls
or epochs a per-layer metric divides by, and ``check(outs)`` the numbers
compared with the plain reference (`harness.reference`), each
``(value, limit)``.
"""

from __future__ import annotations

import time

from harness.gen import Instance


def program_instance(inst: Instance):
    from repro.core.coflow import CoflowInstance

    return CoflowInstance(
        demands=inst.demands, weights=inst.weights, releases=inst.releases,
        rates=inst.rates, delta=inst.delta,
    )


def bound(inst: Instance) -> float:
    """The (8K+1) guarantee of Algorithm 1 under arbitrary releases."""
    return 8.0 * inst.num_cores + 1.0


#: The ordering LP lower-bounds the weighted CCT of every schedule, so an
#: LP objective above the schedule's weighted CCT is no LP solution.
LP_OVER_CCT_LIMIT = 1.0


def run_window(driver, seconds: float):
    """Call the driver back to back, starting one more call while it would
    end nearer to ``seconds`` than the window stands (taking it to last as
    long as the last call), so the window is as close to ``seconds`` as
    whole calls allow.  Returns the outputs and the window's elapsed
    seconds."""
    outs, last = [], None
    w0 = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        if last is not None and (t0 - w0) + last / 2 > seconds:
            break
        outs.append(driver.call())
        last = time.perf_counter() - t0
    return outs, time.perf_counter() - w0
