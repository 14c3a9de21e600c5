"""compile_s: seconds the process spent tracing, lowering and compiling
(or loading from the persistent cache) inside the simulator's calls, the
warm-up job included: the sum of every call record's `compile_s`."""

from bench import program_log


def read(ctx):
    recs = [r for r in program_log.records() if "compile_s" in r]
    return sum(r["compile_s"] for r in recs) if recs else None
