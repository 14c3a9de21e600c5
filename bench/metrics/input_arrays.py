"""input_arrays: leaves the input build puts on the device per job (the
call records' `arrays` counter: each call places its tables, params and
windows with one placement, ``n_tb + n_pm + 1`` leaves; a `run` counts the
same).  Median over the window's jobs, from the program's call log."""

from bench import program_log


def read(ctx):
    return program_log.median_job(ctx, lambda r: r["arrays"])
