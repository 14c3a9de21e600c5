"""The simulator's call log: spans and counters of each user-facing call.

:mod:`repro.core.simlock` opens one record per ``sweep`` (per computed
slice on the resumable path), ``run``, ``sweep_summaries`` and
``summarize`` of device state, and times its phases as spans:

* ``build``: the host input build and its one placement on the device;
* ``compile``: a batched executable's lower, compile (or persistent-cache
  load) and accounting, on a cache miss;
* ``dispatch``: the executable's lookup and asynchronous call;
* ``wait``: the host blocked until the device loop's result is ready;
* ``transfer``: the device-to-host copy of the leaves the summaries read;
* ``reduce``: the host arithmetic of the summaries.

Each span is also a ``jax.profiler.TraceAnnotation`` named
``simlock.<phase>``, so a profiler trace shows it on the device trace's
clock.  A phase's seconds are its own: a span nested in another (a
compile inside a dispatch) is not counted twice.  One ``jax.monitoring``
listener adds the seconds JAX spends tracing, lowering and compiling (a
persistent-cache load included) to the call whose span is innermost on the
compiling thread; compiles outside any span are not counted.  With no
profiler session running a span costs about a microsecond; nothing is
switched on or off.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time

import jax

MAX_RECORDS = 4096          # the log keeps the most recent records
LOCK = threading.Lock()     # guards the log (and simlock's executable table)
PHASES = {"sweep": ("build", "compile", "dispatch"),
          "run": ("build", "dispatch"),
          "sweep_summaries": ("wait", "transfer", "reduce"),
          "summarize": ("wait", "transfer", "reduce")}
COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration"))

_LOG: list = []
_SEQ = itertools.count()


class _Local(threading.local):
    def __init__(self):
        self.calls = []      # open calls of this thread, innermost last
        self.spans = []      # open spans of this thread, innermost last


_TLS = _Local()


class _Call:
    __slots__ = ("rec", "compiles")

    def __init__(self, rec: dict):
        self.rec, self.compiles = rec, []


@contextlib.contextmanager
def call(kind: str, **fields):
    """Open one record of ``kind``; it joins the log when the call returns
    (a call that raises leaves none).  Yields the record, a fresh dict:
    ``kind``, ``lanes``, ``exe`` (the executable's index in compile
    order), ``hit`` (no compile was needed), ``phases`` (seconds per
    span), ``compile_s``, ``arrays`` (leaves the input build placed),
    then ``seq`` (the call's place in the process) once logged."""
    rec = {"kind": kind, "lanes": 1, "exe": None, "hit": None,
           "phases": dict.fromkeys(PHASES[kind], 0.0), "compile_s": 0.0,
           "arrays": 0}
    rec.update(fields)
    c = _Call(rec)
    calls = _TLS.calls
    calls.append(c)
    try:
        yield rec
    finally:
        calls.pop()
    rec["compile_s"] = _covered(c.compiles)
    with LOCK:
        rec["seq"] = next(_SEQ)
        _LOG.append(rec)
        if len(_LOG) > MAX_RECORDS:
            del _LOG[:-MAX_RECORDS]


def current() -> dict | None:
    """The record of this thread's innermost open call, or None."""
    calls = _TLS.calls
    return calls[-1].rec if calls else None


class span:
    """``with span("build"):`` times one phase of the innermost open call
    and marks it as ``simlock.build`` in a profiler trace."""

    __slots__ = ("name", "call", "ann", "t0", "inner")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        calls = _TLS.calls
        self.call = calls[-1] if calls else None
        self.inner = 0.0
        self.ann = jax.profiler.TraceAnnotation("simlock." + self.name)
        self.ann.__enter__()
        _TLS.spans.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        spans = _TLS.spans
        spans.pop()
        self.ann.__exit__(*exc)
        if spans:
            spans[-1].inner += dur
        if self.call is not None:
            ph = self.call.rec["phases"]
            ph[self.name] = ph.get(self.name, 0.0) + dur - self.inner
        return False


def records() -> list:
    """The logged records, oldest first (at most ``MAX_RECORDS``)."""
    with LOCK:
        return list(_LOG)


def _covered(intervals) -> float:
    """Seconds covered by (start, end) intervals: a trace that starts an
    inner function's trace reports both, nested, and counts once."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def _on_duration(event: str, duration: float, **_) -> None:
    if event in COMPILE_EVENTS:
        spans = _TLS.spans
        if spans and spans[-1].call is not None:
            end = time.perf_counter()
            spans[-1].call.compiles.append((end - duration, end))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
