"""executables: compiled simulator programs after the warm-up job, batched
sweep executables plus single-run executables (``simlock``'s own counts)."""


def read(ctx):
    return ctx["executables"]
