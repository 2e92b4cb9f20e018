"""Host seconds per sweep in paper_k3.ensemble that the ordering LP spends
packing its padded inputs and unpacking its solutions: the program's
`lp.pack` and `lp.unpack` spans (`SweepResult.spans`), over the traced
sweeps.  None where the program has no such spans."""

SPANS = ("lp.pack", "lp.unpack")


def read(ctx):
    if not ctx.outs or not any(s in getattr(o, "spans", {})
                               for o in ctx.outs for s in SPANS):
        return None
    return sum(o.spans.get(s, 0.0)
               for o in ctx.outs for s in SPANS) / len(ctx.outs)
