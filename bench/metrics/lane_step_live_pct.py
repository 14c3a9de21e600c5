"""lane_step_live_pct: the share of lane-steps that retire an event: the
events the lanes retired (their ``events`` leaf) over lanes x loop steps,
where a call's loop steps are ``chunk`` x the chunks its longest lane
needs.  Read from the traced jobs' summaries; needs no trace."""

import math


def live_share(calls):
    """Events over lane-steps for a list of (chunk, per-lane events)."""
    done = slots = 0
    for chunk, events in calls:
        steps = chunk * math.ceil(max(events) / chunk)
        done += sum(events)
        slots += steps * len(events)
    return 100.0 * done / slots if slots else None


def read(ctx):
    return live_share([c for calls in ctx["lane_events"] for c in calls])
