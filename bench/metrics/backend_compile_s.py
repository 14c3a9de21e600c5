"""backend_compile_s: seconds the process spent in XLA's backend compile
inside the simulator's calls, the warm-up job included: the sum of every
call record's `backend_s` (JAX's `backend_compile_duration` event), which
holds the persistent-cache loads (`cache_load_s`) where the cache has the
program.  Nothing for a program whose records lack the split."""

from bench import program_log


def read(ctx):
    recs = [r for r in program_log.records() if "backend_s" in r]
    return sum(r["backend_s"] for r in recs) if recs else None
