"""The simulator's call log: one whole record per user-facing call, with
its spans (also on the profiler's clock), compile seconds and input-array
count; results bit-identical whether or not a profiler session runs."""

import glob
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import calllog
from repro.core import simlock as sl

ROOT = Path(__file__).resolve().parents[1]
CFG = sl.SimConfig(policy="libasl", sim_time_us=300.0)
AXES = {"n_cores": [1, 2, 4]}


def _new_records(fn):
    """The records ``fn()`` logs (by ``seq``, so the log's bound is moot)."""
    log = sl.sweep_log()
    mark = log[-1]["seq"] + 1 if log else 0
    out = fn()
    return out, [r for r in sl.sweep_log() if r["seq"] >= mark]


def _sweep_job(cfg=CFG, axes=AXES, seed=0):
    st, grid = sl.sweep(cfg, axes, slo_us=50.0, seed=seed)
    return st, sl.sweep_summaries(cfg, st, grid, slo_us=50.0)


def _run_job(cfg=CFG, seed=0):
    st = sl.run(cfg, 50.0, seed)
    return st, sl.summarize(cfg, st, slo_us=50.0)


@pytest.fixture
def fresh_programs():
    sl._BATCH_EXECS.clear()
    sl._run_single.clear_cache()
    yield
    sl._BATCH_EXECS.clear()
    sl._run_single.clear_cache()


@pytest.mark.parametrize("job,kinds,lanes", [
    (_sweep_job, ["sweep", "sweep_summaries"], 3),
    (_run_job, ["run", "summarize"], 1)])
def test_each_call_logs_one_record_with_every_phase(job, kinds, lanes):
    _, recs = _new_records(job)
    assert [r["kind"] for r in recs] == kinds
    for r in recs:
        assert set(r["phases"]) == set(calllog.PHASES[r["kind"]])
        assert r["lanes"] == lanes
        for name in calllog.PHASES[r["kind"]]:
            if name != "compile":
                assert r["phases"][name] > 0.0, (r["kind"], name)
    call, summ = recs
    assert call["arrays"] > 0 and isinstance(call["exe"], int)
    assert summ["arrays"] == 0 and summ["exe"] is None
    assert recs[0]["seq"] + 1 == recs[1]["seq"]


def test_sweep_record_counts_its_input_arrays():
    (st, _), (rec, _) = _new_records(_sweep_job)
    tb, pm = sl.build_tables(CFG), sl.build_params(CFG, 50.0)
    n_tb, n_pm = len(jax.tree.leaves(tb)), len(jax.tree.leaves(pm))
    # one placement of the stacked tables, the stacked params, the windows
    assert rec["arrays"] == n_tb + n_pm + 1
    assert rec["n_cells"] == 3 and rec["devices"] == 1
    assert rec["collectives"]["total_count"] == 0
    assert "flops" not in rec and "bytes_accessed" not in rec


@pytest.mark.parametrize("job", [_sweep_job, _run_job])
def test_second_identical_call_is_a_hit_without_compiles(job,
                                                         fresh_programs):
    _, first = _new_records(job)
    _, second = _new_records(job)
    assert first[0]["hit"] is False and first[0]["compile_s"] > 0.0
    assert second[0]["hit"] is True and second[0]["compile_s"] == 0.0
    assert first[0]["exe"] == second[0]["exe"]
    if first[0]["kind"] == "sweep":
        assert first[0]["phases"]["compile"] > 0.0
        assert second[0]["phases"]["compile"] == 0.0
    assert all(r["compile_s"] == 0.0 for r in first[1:] + second[1:])


def test_compiles_outside_a_simlock_call_are_not_counted():
    _sweep_job()                                  # warm every program

    def unrelated():
        x = jnp.arange(7.0)
        return jax.jit(lambda v: jnp.cumsum(v * 3.25) - 1.5)(x)

    def job_then_unrelated():
        out = _sweep_job()
        unrelated().block_until_ready()
        return out

    _, recs = _new_records(job_then_unrelated)
    assert len(recs) == 2
    assert all(r["compile_s"] == 0.0 for r in recs)


def test_compile_seconds_count_nested_intervals_once():
    assert calllog._covered([]) == 0.0
    assert calllog._covered([(0.0, 1.0), (0.25, 0.5), (2.0, 3.0)]) == 2.0
    assert calllog._covered([(1.0, 2.0), (0.0, 1.5)]) == 2.0


def test_summaries_of_host_state_log_nothing():
    host = jax.tree.map(np.asarray, sl.run(CFG, 50.0, 1))
    _, recs = _new_records(lambda: sl.summarize(CFG, host, slo_us=50.0))
    assert recs == []


def test_resumable_sweep_logs_one_record_per_computed_slice(tmp_path,
                                                           monkeypatch):
    axes = {"n_cores": [1, 2, 3, 4, 5]}
    want, _ = sl.sweep(CFG, axes, slo_us=50.0)
    # the grid's host build takes a known 0.1 s, so where it lands shows
    grid_build = sl._sweep_inputs

    def slow_grid_build(*args, **kw):
        time.sleep(0.1)
        return grid_build(*args, **kw)

    monkeypatch.setattr(sl, "_sweep_inputs", slow_grid_build)
    (st, _), recs = _new_records(lambda: sl.sweep(
        CFG, axes, slo_us=50.0, resume_dir=tmp_path, resume_chunk=2))
    assert [r["lanes"] for r in recs] == [2, 2, 1]
    assert all(r["kind"] == "sweep" and r["phases"]["dispatch"] > 0.0
               for r in recs)
    # the first record carries the whole grid's input build
    assert recs[0]["phases"]["build"] >= 0.1
    assert all(r["phases"]["build"] < 0.1 for r in recs[1:])
    # each computed slice places its own inputs once
    n = len(jax.tree.leaves((sl.build_tables(CFG),
                             sl.build_params(CFG, 50.0)))) + 1
    assert [r["arrays"] for r in recs] == [n, n, n]
    for x, y in zip(jax.tree.leaves(want), jax.tree.leaves(st)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # a re-run restores every slice and logs the build alone
    _, again = _new_records(lambda: sl.sweep(
        CFG, axes, slo_us=50.0, resume_dir=tmp_path, resume_chunk=2))
    assert len(again) == 1 and again[0]["phases"]["dispatch"] == 0.0
    assert again[0]["arrays"] == 0


def test_spans_nest_inside_an_enclosing_annotation_in_a_trace(tmp_path):
    sys.path.insert(0, str(ROOT))
    from bench import trace_reduce
    _sweep_job()                                  # no compile in the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("inputs"):
            st, grid = sl.sweep(CFG, AXES, slo_us=50.0)
        with jax.profiler.TraceAnnotation("summaries"):
            sl.sweep_summaries(CFG, st, grid, slo_us=50.0)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {}
    for pname, lines in trace_reduce.planes_of(path):
        for _, events in lines:
            for name, s, d in events:
                if name in ("inputs", "summaries") or \
                        name.startswith("simlock."):
                    spans.setdefault(name, []).append((s, s + d))

    def inside(name, outer):
        (s, e), = spans[name]
        (lo, hi), = spans[outer]
        return lo <= s and e <= hi

    for name in ("simlock.build", "simlock.dispatch"):
        assert inside(name, "inputs"), name
    for name in ("simlock.wait", "simlock.transfer", "simlock.reduce"):
        assert inside(name, "summaries"), name


def test_state_is_bit_identical_under_a_profiler_session(tmp_path):
    def states():
        st, _ = sl.sweep(CFG, AXES, slo_us=50.0, seed=5)
        return [np.asarray(x) for x in
                jax.tree.leaves((st, sl.run(CFG, 50.0, 5)))]

    plain = states()
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = states()
    finally:
        jax.profiler.stop_trace()
    assert len(plain) == len(traced)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a, b)


def test_threads_sweeping_at_once_each_get_whole_records():
    grids = {0: {"n_cores": [1, 2]}, 1: {"n_cores": [1, 2, 3, 4]}}
    for axes in grids.values():
        _sweep_job(axes=axes)                     # compile outside
    start = threading.Barrier(2)
    errors = []

    def worker(i):
        try:
            start.wait()
            for seed in range(3):
                _sweep_job(axes=grids[i], seed=seed)
        except Exception as e:  # pragma: no cover - reported below
            errors.append(e)

    def both():
        ts = [threading.Thread(target=worker, args=(i,)) for i in grids]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    _, recs = _new_records(both)
    assert not errors
    assert len(recs) == 2 * 3 * 2
    for lanes in (2, 4):
        mine = [r for r in recs if r["lanes"] == lanes]
        assert [r["kind"] for r in mine] == ["sweep", "sweep_summaries"] * 3
        for r in mine:
            assert set(r["phases"]) == set(calllog.PHASES[r["kind"]])
            assert r["compile_s"] == 0.0
            if r["kind"] == "sweep":
                assert r["hit"] and r["n_cells"] == lanes
                assert r["phases"]["build"] > 0.0
                assert r["phases"]["dispatch"] > 0.0
            else:
                assert r["phases"]["wait"] > 0.0


def test_many_threads_never_mix_or_lose_records():
    """More threads than cores and a short switch interval: every record
    joins the log once, with a ``seq`` of its own, holding only the spans
    its own thread opened."""
    n_threads, n_calls = 16, 50
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def worker(i):
        for _ in range(n_calls):
            with calllog.call("run", lanes=i):
                with calllog.span("build"):
                    with calllog.span(f"t{i}"):
                        pass
                with calllog.span("dispatch"):
                    pass

    def many():
        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        return [t.is_alive() for t in ts]

    try:
        alive, recs = _new_records(many)
    finally:
        sys.setswitchinterval(old)
    assert not any(alive)
    assert len(recs) == n_threads * n_calls
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    for r in recs:
        assert set(r["phases"]) == {"build", "dispatch", f"t{r['lanes']}"}
        assert r["compile_s"] == 0.0
