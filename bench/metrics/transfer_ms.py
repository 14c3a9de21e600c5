"""transfer_ms: host milliseconds per job in the simulator's
`simlock.transfer` spans: the device-to-host copy of the state leaves the
summaries read.  Median over the window's jobs, from the program's call
log."""

from bench import program_log


def read(ctx):
    return program_log.phase_ms(ctx, "transfer")
