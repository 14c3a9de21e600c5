"""key_draws: epochs per job whose Zipf key the device loop drew: the
`key_draws` counter of each `sweep_summaries` / `summarize` record, which
the program counts on the host from the `ep_cnt` leaf (`ep_cnt + 1` for
each active core; 0 in a key-off call).  Median over the window's jobs, from
the program's call log; nothing for a program without the counter."""

from bench import program_log


def read(ctx):
    if not any("key_draws" in r for r in program_log.records()):
        return None
    return program_log.median_job(ctx, lambda r: r.get("key_draws", 0))
