"""Percent of the allocation scan's member-steps in paper_k3.ensemble that
carried no flow: 100 (1 - member steps / (members x steps)), from the
program's `alloc.member_steps` (member-steps with a real flow),
`alloc.members` (members of the scan) and `alloc.steps` (the steps the
scan ran) counters (`SweepResult.counts`) over the traced sweeps.  None
where the program does not count them."""


def read(ctx):
    if not ctx.outs or not any("alloc.member_steps" in getattr(o, "counts", {})
                               for o in ctx.outs):
        return None
    busy = sum(o.counts.get("alloc.member_steps", 0) for o in ctx.outs)
    slots = sum(o.counts.get("alloc.members", 0) * o.counts.get("alloc.steps", 0)
                for o in ctx.outs)
    return 100.0 * (1.0 - busy / slots) if slots else None
