"""summaries_ms: host milliseconds per job inside the ``summaries`` spans,
the benchmark's span around each ``sweep_summaries`` / ``summarize`` call
(the device-to-host transfer, and the wait for the device, included)."""


def read(ctx):
    spans = ctx["trace"]["spans"]
    jobs = len(spans.get("job", []))
    if not jobs or not spans.get("summaries"):
        return None
    return sum(spans["summaries"]) / jobs * 1e3
