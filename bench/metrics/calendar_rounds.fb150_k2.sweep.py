"""Lockstep calendar rounds per sweep in fb150_k2.sweep: the program's
`calendar.rounds` counter (the loop count of each calendar program, read
back from the device; `SweepResult.counts`) over the traced sweeps.
None where the program does not count them."""


def read(ctx):
    if not ctx.outs or not hasattr(ctx.outs[0], "counts"):
        return None
    return sum(o.counts.get("calendar.rounds", 0)
               for o in ctx.outs) / len(ctx.outs)
