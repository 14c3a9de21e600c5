"""dispatch_ms: host milliseconds per job in the simulator's
`simlock.dispatch` spans: the executable's lookup and its asynchronous
call (a compile there would count in `compile_s` too).  Median over the
window's jobs, from the program's call log."""

from bench import program_log


def read(ctx):
    return program_log.phase_ms(ctx, "dispatch")
