"""Percent of the cells the calendar's `pair_resolve` round reduces in
paper_k3.ensemble that hold a real (ingress, egress) pair: 100 x the
program's `calendar.pair_cells` over its `calendar.pair_slots` (the
kernel's padded block per member) counters (`SweepResult.counts`) over
the traced sweeps.  None where the program does not count them."""


def read(ctx):
    if not ctx.outs or not hasattr(ctx.outs[0], "counts"):
        return None
    cells = sum(o.counts.get("calendar.pair_cells", 0) for o in ctx.outs)
    slots = sum(o.counts.get("calendar.pair_slots", 0) for o in ctx.outs)
    return 100.0 * cells / slots if slots else None
