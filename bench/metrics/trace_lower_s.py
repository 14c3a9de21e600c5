"""trace_lower_s: seconds the process spent tracing to jaxprs and lowering
to MLIR inside the simulator's calls, the warm-up job included: the sum of
every call record's `trace_s` and `lower_s` (JAX's
`jaxpr_trace_duration` and `jaxpr_to_mlir_module_duration` events).  Paid
even where the persistent cache holds the program.  Nothing for a program
whose records lack the split."""

from bench import program_log


def read(ctx):
    recs = [r for r in program_log.records() if "trace_s" in r]
    return sum(r["trace_s"] + r["lower_s"] for r in recs) if recs else None
