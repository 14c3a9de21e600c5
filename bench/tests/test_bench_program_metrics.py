"""The seven metrics that read the simulator's call log, on a synthetic
log: the warm-up job is dropped, the rest is grouped into jobs of two
records per call, each metric is the median job's sum, and a log without
window jobs (or a program whose log holds no spans) gives nothing."""

import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from repro.core import simlock  # noqa: E402

PHASE_METRICS = {"build_ms": "build", "dispatch_ms": "dispatch",
                 "device_wait_ms": "wait", "transfer_ms": "transfer",
                 "reduce_ms": "reduce"}
ALL = tuple(PHASE_METRICS) + ("input_arrays", "compile_s")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _record(seq, kind, scale):
    """One record whose every number is ``scale`` times a fixed base."""
    if kind == "sweep":
        phases = {"build": 0.1 * scale, "compile": 0.0,
                  "dispatch": 0.01 * scale}
        arrays = 100 * scale
    else:
        phases = {"wait": 0.2 * scale, "transfer": 0.02 * scale,
                  "reduce": 0.003 * scale}
        arrays = 0
    return {"kind": kind, "seq": seq, "lanes": 8, "exe": 0, "hit": True,
            "phases": phases, "compile_s": 0.5 * scale, "arrays": arrays}


def _log(job_scales, calls=2):
    """Jobs of ``calls`` sweep + summaries pairs, job j scaled by
    ``job_scales[j]`` (job 0 is the warm-up)."""
    out, seq = [], 0
    for scale in job_scales:
        for _ in range(calls):
            for kind in ("sweep", "sweep_summaries"):
                out.append(_record(seq, kind, scale))
                seq += 1
    return out


def _ctx(calls=2):
    return {"plan": SimpleNamespace(calls=[object()] * calls)}


@pytest.fixture
def log(monkeypatch):
    box = []
    monkeypatch.setattr(simlock, "sweep_log", lambda: list(box))
    return box


@pytest.mark.parametrize("name", list(PHASE_METRICS))
def test_phase_metrics_drop_the_warm_up_and_take_the_median_job(name, log):
    # warm-up 1000x; window jobs 1x, 3x and 2x: the median job is 2x
    log.extend(_log([1000, 1, 3, 2]))
    base = _record(0, "sweep", 1)["phases"] | \
        _record(1, "summ", 1)["phases"]
    want = 2 * 2 * base[PHASE_METRICS[name]] * 1e3      # two calls a job
    assert _reader(name)(_ctx()) == pytest.approx(want)


def test_input_arrays_is_the_median_jobs_count(log):
    log.extend(_log([1000, 1, 3, 2]))
    assert _reader("input_arrays")(_ctx()) == 2 * 2 * 100


def test_compile_s_is_the_process_total_warm_up_included(log):
    log.extend(_log([10, 1, 1]))
    # 2 calls x 2 records per job, 0.5 s per unit of scale
    assert _reader("compile_s")(_ctx()) == pytest.approx(4 * 0.5 * 12)


def test_jobs_are_grouped_by_the_plans_calls(log):
    # one call a job: jobs of 2 records; the warm-up is the first pair
    log.extend(_log([1000, 1, 5, 5], calls=1))
    assert _reader("build_ms")(_ctx(calls=1)) == pytest.approx(500.0)


def test_a_bounded_log_still_finds_its_jobs(log):
    # the log dropped its oldest records, the warm-up among them: ``seq``
    # still places every record in its job, and a partial job is left out
    full = _log([1000, 1, 2, 3])
    log.extend(full[6:])            # half of job 1 is gone
    assert _reader("build_ms")(_ctx()) == pytest.approx(2 * 100 * 2.5)


@pytest.mark.parametrize("name", ALL)
def test_an_empty_log_gives_nothing(name, log):
    assert _reader(name)(_ctx()) is None


@pytest.mark.parametrize("name", ALL)
def test_records_without_spans_give_nothing(name, log):
    """A program whose log keeps only executable records (no ``seq``, no
    ``phases``) reports none of these metrics."""
    log.extend({"n_cells": 8, "devices": 1, "collectives": {}}
               for _ in range(8))
    assert _reader(name)(_ctx()) is None


@pytest.mark.parametrize("name", ALL[:-1])
def test_a_warm_up_alone_gives_nothing(name, log):
    log.extend(_log([1000]))
    assert _reader(name)(_ctx()) is None
