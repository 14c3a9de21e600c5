"""keys_ms: host milliseconds per job in the simulator's `simlock.keys`
spans: the Zipf sampler's host constants of each keyed call's input build
(inside `simlock.build`, and not counted there).  Median over the window's
jobs, from the program's call log; nothing where no call logged the span
(a key-off cell, or a program without it)."""

from bench import program_log


def read(ctx):
    if not any("keys" in r["phases"] for r in program_log.records()):
        return None
    return program_log.phase_ms(ctx, "keys")
