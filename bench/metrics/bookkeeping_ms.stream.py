"""Milliseconds of service bookkeeping per epoch: the program's
`stream.advance`, `stream.admit`, `stream.scatter`, `stream.validate` and
`stream.record` spans (`EpochRecord.spans`, host clock) summed over the
traced stream's epochs, over its epochs.  None where the program records
no spans."""

SPANS = ("stream.advance", "stream.admit", "stream.scatter",
         "stream.validate", "stream.record")


def read(ctx):
    epochs = [e for o in ctx.outs for e in o.epochs]
    if not epochs or not hasattr(epochs[0], "spans"):
        return None
    return 1e3 * sum(e.spans.get(n, 0.0) for e in epochs
                     for n in SPANS) / len(epochs)
