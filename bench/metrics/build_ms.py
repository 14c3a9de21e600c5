"""build_ms: host milliseconds per job in the simulator's `simlock.build`
spans: the input build of every `sweep` / `run` call (validation, tables,
params, stacking and placement, the arrays made on the device).  Median
over the window's jobs, from the program's call log."""

from bench import program_log


def read(ctx):
    return program_log.phase_ms(ctx, "build")
