"""Seconds per sweep in paper_k3.ensemble from dispatching the batched
ordering LP until its batch is host arrays: the program's `lp.wait` span
(`SweepResult.spans`), over the traced sweeps.  None where the program
has no such span."""


def read(ctx):
    if not ctx.outs or not any("lp.wait" in getattr(o, "spans", {})
                               for o in ctx.outs):
        return None
    return sum(o.spans.get("lp.wait", 0.0) for o in ctx.outs) / len(ctx.outs)
