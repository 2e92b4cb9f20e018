"""Percent of calendar member-rounds spent idle in lockstep in
fb150_k2.sweep: 100 (1 - member rounds / (members x lockstep rounds)), from
the program's `calendar.member_rounds` (rounds in which each real member
still had a pending flow) and `calendar.member_slots` (members times the
lockstep rounds of their program) counters (`SweepResult.counts`) over
the traced sweeps.  None where the program does not count them."""


def read(ctx):
    if not ctx.outs or not hasattr(ctx.outs[0], "counts"):
        return None
    busy = sum(o.counts.get("calendar.member_rounds", 0) for o in ctx.outs)
    slots = sum(o.counts.get("calendar.member_slots", 0) for o in ctx.outs)
    return 100.0 * (1.0 - busy / slots) if slots else None
