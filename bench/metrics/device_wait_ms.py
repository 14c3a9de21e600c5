"""device_wait_ms: host milliseconds per job in the simulator's
`simlock.wait` spans: the summaries blocked until the device loop's result
is ready.  Median over the window's jobs, from the program's call log."""

from bench import program_log


def read(ctx):
    return program_log.phase_ms(ctx, "wait")
