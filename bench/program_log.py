"""The simulator's own call log (``simlock.sweep_log()``), grouped into
the run's jobs, for the per-layer metrics that read its spans and counters.

Every call a job makes logs two records in order: the ``sweep`` or ``run``
record, then its ``sweep_summaries`` or ``summarize`` record.  A record's
``seq`` is its call's place in the process, so the records of job ``j``
(the warm-up is job 0) are those with ``seq // (2 * calls per job) == j``.
A program whose log holds no such records (no ``seq`` or ``phases``) gives
no jobs, and the metrics report nothing.
"""

from __future__ import annotations

import statistics


def records() -> list:
    """The log's records that carry spans, oldest first."""
    from repro.core import simlock
    return [r for r in simlock.sweep_log() if "seq" in r and "phases" in r]


def window_jobs(ctx) -> list:
    """The records of each whole job after the warm-up, job by job."""
    per_job = 2 * len(ctx["plan"].calls)
    jobs = {}
    for r in records():
        jobs.setdefault(r["seq"] // per_job, []).append(r)
    return [recs for j, recs in sorted(jobs.items())
            if j > 0 and len(recs) == per_job]


def median_job(ctx, of_record):
    """The median over window jobs of the sum of ``of_record`` over a
    job's records, or None without a window job."""
    jobs = window_jobs(ctx)
    if not jobs:
        return None
    return statistics.median(sum(of_record(r) for r in recs)
                             for recs in jobs)


def phase_ms(ctx, phase: str):
    """Median milliseconds a job spends in span ``phase``."""
    v = median_job(ctx, lambda r: r["phases"].get(phase, 0.0))
    return None if v is None else v * 1e3
