"""input_arrays: device arrays the input build makes per job (the call
records' `arrays` counter: per-lane params and tables leaves, the stacked
leaves, the windows).  Median over the window's jobs, from the program's
call log."""

from bench import program_log


def read(ctx):
    return program_log.median_job(ctx, lambda r: r["arrays"])
