"""Milliseconds of ordering per epoch: the program's `stream.order` span
(`EpochRecord.spans`, host clock: the ordering view, the stable sort of
the LP's completions and the slot-space order) over the traced stream's
epochs.  None where the program records no spans."""

SPANS = ("stream.order",)


def read(ctx):
    epochs = [e for o in ctx.outs for e in o.epochs]
    if not epochs or not hasattr(epochs[0], "spans"):
        return None
    return 1e3 * sum(e.spans.get(n, 0.0) for e in epochs
                     for n in SPANS) / len(epochs)
