"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The benchmark wraps each traced job in host spans of its own
(``jax.profiler.TraceAnnotation``): ``window`` around each traced job,
``job`` around the job, ``inputs`` around each ``sweep``/``run`` call and
``summaries`` around each summary call.  The device side is each TPU
plane's ``XLA Ops`` line, one event per operation run.

From those, for the traced window (first ``window`` start to last end):

* busy time per chip: the union of its operations' intervals;
* loop time per chip: the union of its ``while`` operations' intervals
  (the simulator's device loop; nested loops count once), which leaves
  out the input build's small programs and the summaries' operations;
* collective time per chip: the summed durations of its collective
  operations (all-reduce, all-gather, reduce-scatter, all-to-all,
  collective-permute);
* ``breakdown``: the operations that took most device time (a loop
  counts its body's time too), and the
  longest idle gaps on the first chip, each labelled by the benchmark span
  the host was in at the gap's middle (``inputs``, ``summaries``, ``job``
  for the rest of a job, ``between jobs`` outside any).
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPANS = ("window", "job", "inputs", "summaries")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
TOP = 10


def op_name(event: str) -> str:
    """An ``XLA Ops`` event is named by its HLO instruction
    (``%fusion.12 = f32[8] fusion(...)``); the op's name is its left side."""
    return event.split(" = ", 1)[0]


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def is_loop(name: str) -> bool:
    """A ``while`` operation (``%while.94``): an XLA loop, body included."""
    return name.lstrip("%").startswith("while")


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def label_at(t, spans) -> str:
    for name in ("inputs", "summaries", "job"):
        for s, e in spans.get(name, []):
            if s <= t < e:
                return name
    return "between jobs"


def reduce_planes(planes, n_chips: int) -> dict:
    """``planes``: iterable of (plane name, [(line name, [(op name, start_ns,
    duration_ns)])]).  Returns the numbers the metric readers use."""
    spans, devices = {}, {}
    for pname, lines in planes:
        for lname, events in lines:
            if pname.startswith(DEVICE_PREFIX):
                if lname == OPS_LINE:
                    devices.setdefault(pname, []).extend(events)
            else:
                for name, s, d in events:
                    if name in SPANS:
                        spans.setdefault(name, []).append((s, s + d))
    win = spans.get("window")
    if not win:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    window_s = (hi - lo) * 1e-9
    names = sorted(devices, key=lambda p: int(p[len(DEVICE_PREFIX):]))
    names = names[:n_chips]
    busy, loop, coll, by_op, first_busy = [], [], [], {}, None
    for p in names:
        evs = [(n, s, s + d) for n, s, d in devices[p] if s + d > lo and
               s < hi]
        u = union(clip([(s, e) for _, s, e in evs], lo, hi))
        if first_busy is None:
            first_busy = u
        busy.append(sum(e - s for s, e in u) * 1e-9)
        lu = union(clip([(s, e) for n, s, e in evs if is_loop(op_name(n))],
                        lo, hi))
        loop.append(sum(e - s for s, e in lu) * 1e-9)
        coll.append(sum(e - s for n, s, e in evs if is_collective(n)) * 1e-9)
        for n, s, e in evs:
            op = op_name(n)
            by_op[op] = by_op.get(op, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
    n_dev = max(len(names), 1)
    ops = sorted(([n, t / n_dev] for n, t in by_op.items()),
                 key=lambda x: -x[1])[:TOP]
    gaps, prev = [], lo
    for s, e in (first_busy or []) + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    idle = [[label_at((s + e) / 2, spans), (e - s) * 1e-9] for s, e in gaps]
    in_win = {k: [(e - s) * 1e-9 for s, e in v if s >= lo and e <= hi]
              for k, v in spans.items()}
    return {"window_s": window_s,
            "busy_s": sum(busy) / n_dev if busy else 0.0,
            "busy_per_device": busy, "loop_per_device": loop,
            "collective_per_device": coll,
            "jobs": len(in_win.get("job", [])), "spans": in_win,
            "devices": names,
            "breakdown": {"device_ops": ops, "idle_gaps": idle}}


def planes_of(path: str):
    """(plane, [(line, [(event, start_ns, duration_ns)])]) of one
    ``.xplane.pb`` file, read with JAX's own profile reader."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        yield plane.name, [
            (line.name, [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events])
            for line in plane.lines]


def reduce_file(path: str, n_chips: int) -> dict:
    return reduce_planes(planes_of(path), n_chips)


def reduce_dir(log_dir: str, n_chips: int) -> dict:
    """Reduce the one trace ``jax.profiler`` wrote under ``log_dir``."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {log_dir}, found {files}")
    return reduce_file(files[0], n_chips)
