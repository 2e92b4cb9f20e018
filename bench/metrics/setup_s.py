"""Set-up seconds: interpreter start to the end of the warm-up call,
host clock — imports, instance generation, compilation (or loading it
from the persistent cache) and one untimed call of the window's work."""


def read(ctx):
    return ctx.setup_s
