"""reduce_ms: host milliseconds per job in the simulator's
`simlock.reduce` spans: the numpy arithmetic of the summaries.  Median over
the window's jobs, from the program's call log."""

from bench import program_log


def read(ctx):
    return program_log.phase_ms(ctx, "reduce")
