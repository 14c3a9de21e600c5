"""inputs_ms: host milliseconds per job inside the ``inputs`` spans, the
benchmark's span around each ``simlock.sweep`` / ``simlock.run`` call until
it returns (input build, placement and the asynchronous dispatch)."""


def read(ctx):
    spans = ctx["trace"]["spans"]
    jobs = len(spans.get("job", []))
    if not jobs or not spans.get("inputs"):
        return None
    return sum(spans["inputs"]) / jobs * 1e3
