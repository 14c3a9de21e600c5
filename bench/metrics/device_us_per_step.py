"""device_us_per_step: device microseconds of the simulator's loop per
loop step.  The loop's time is that of the trace's ``while`` operations
(``trace_reduce``: the input build's small programs and the summaries'
operations are left out).  A job's loop steps are, per executable call,
``chunk`` x the chunks its longest lane needs (every lane of a vmapped loop
steps until the last one ends); with several chips, the busiest chip's
loop time."""

import math


def read(ctx):
    t, jobs = ctx["trace"], ctx["lane_events"]
    if not jobs or not t["jobs"] or not any(t["loop_per_device"]):
        return None
    steps = sum(chunk * math.ceil(max(events) / chunk)
                for calls in jobs for chunk, events in calls) / len(jobs)
    if not steps:
        return None
    return max(t["loop_per_device"]) / t["jobs"] / steps * 1e6
