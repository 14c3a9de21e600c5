"""The YCSB workload A cell (``ycsb_a_m1`` under ``keyshard_ycsb_a``): it
loads through the generator as the manifest names it; at a short horizon
the program equals the plain reference bit for bit and the bfloat16
control departs; at 2**24 keys the program's key draws are the ranks the
reference draws; and the four readers the cell brings, on a fabricated
call log."""

import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from repro.launch.xla_flags import ensure_host_devices  # noqa: E402

ensure_host_devices(8)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import cells  # noqa: E402
from bench import reference as ref  # noqa: E402
from bench import run as brun  # noqa: E402
from repro.core import simlock  # noqa: E402
from repro.workloads import keys as wlk  # noqa: E402

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "keyshard_ycsb_a"
SEED = 2**31 + 1616          # larger than 32 signed bits hold
N_KEYS = 2**24


def test_the_cell_loads_as_the_manifest_names_it():
    w = cells.workload(CELL, ROOT)
    assert (w["config"], w["traffic"], w["chips"]) == ("ycsb_a_m1", CELL, 1)
    entry = next(c for c in MAN["configs"] if c["name"] == "ycsb_a_m1")
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config["reduced"] == entry["reduced"] == ["sim_time_us",
                                                     "n_keys"]
    plan = cells.plan_for(CELL, ROOT)
    assert plan.keys == {"n_keys": N_KEYS, "zipf_theta": 0.99}
    assert plan.kind == "sweep" and plan.sim_time_us == 5000.0
    assert [c.policies for c in plan.calls] == \
        [(p,) for p in cells.load_json("configs", "m1_fig1")["policies"]]
    seed = cells.derive(SEED, 0)
    for call in plan.calls:
        lanes = plan.lanes(call, seed)
        assert [ln["n_active"] for ln in lanes] == list(range(1, 9))
        assert {(ln["n_keys"], ln["n_locks"]) for ln in lanes} == \
            {(N_KEYS, 16)}
        cfg = plan.sim_config(call)
        assert (cfg.n_keys, cfg.zipf_theta, cfg.n_locks) == \
            (N_KEYS, 0.99, 16)
    crew = plan.sim_config(plan.calls[8])
    assert crew.policy == "ks_crew" and \
        dict(crew.policy_kw)["crew_wfrac"] == 0.5
    # every cell that reports events_per_s reports a per-layer metric
    # that reads the new span or counter
    assert {m["name"] for m in MAN["per_layer"]
            if CELL in m["workloads"]} >= {"keys_ms", "key_draws",
                                           "trace_lower_s",
                                           "backend_compile_s"}


def _short_plan(policy):
    traffic = dict(cells.load_json("traffic", CELL), policies=[policy],
                   axes={"n_cores": [3, 8]})
    return cells.Plan(cells.load_json("configs", "ycsb_a_m1"), traffic,
                      sim_time_us=200.0)


@pytest.mark.parametrize("policy", ["ks_crew", "libasl"])
def test_program_equals_reference_and_the_control_departs(policy):
    plan = _short_plan(policy)
    job = brun.run_job(plan, 0, cells.derive(SEED, 1))
    (call, lanes, cfg, st, sums), = job.calls
    st = jax.tree.map(np.asarray, st)
    for li, lane in enumerate(lanes):
        want = ref.simulate(lane)
        got = brun.host_leaves(st, li, False)
        assert ref.leaves_differing(got, want["leaves"]) == [], li
        assert ref.summary_gap(sums[li], want["summary"]) == 0.0
        assert len(set(got["cur_lock"][:lane["n_active"]].tolist())) > 1
    ctl = brun.check(plan, [job], cells.derive(SEED, 2), control=True)
    assert ctl["lanes_compared"] == 2
    assert ctl["leaves_differing"] > brun.LIMITS["leaves_differing"]


def test_program_key_table_at_2_24_keys_is_the_references_ranks():
    seed = cells.derive(SEED, 3)
    prog = wlk.key_table(seed, 4, 4096, N_KEYS, 0.99)
    # with one lock per key the reference's lock is the rank itself
    draws = ref.EpochDraws({"n_keys": N_KEYS, "zipf_theta": 0.99,
                            "n_locks": N_KEYS, "policy": "fifo",
                            "seed": seed}, np.float32,
                           jax.devices("cpu")[0])
    want = np.asarray([[draws(c, e)[0] for e in range(4096)]
                       for c in range(4)])
    np.testing.assert_array_equal(prog, want)
    assert ((want > 2**23) & (want % 2 == 1)).any()


# --------------------------------------------------------------------------
# The four readers, on a fabricated call log
# --------------------------------------------------------------------------

NEW = ("keys_ms", "key_draws", "trace_lower_s", "backend_compile_s")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _record(seq, kind, scale, keyed=True, split=True):
    split_s = {"trace_s": 0.3 * scale, "lower_s": 0.2 * scale,
               "backend_s": 0.1 * scale, "cache_load_s": 0.05 * scale}
    rec = {"kind": kind, "seq": seq, "lanes": 8, "exe": 0, "hit": True,
           "compile_s": 0.5 * scale, "arrays": 0}
    if split:
        rec.update(split_s)
    if kind == "sweep":
        rec["phases"] = {"build": 0.1 * scale, "compile": 0.0,
                         "dispatch": 0.01 * scale}
        if keyed:
            rec["phases"]["keys"] = 0.002 * scale
    else:
        rec["phases"] = {"wait": 0.2, "transfer": 0.02, "reduce": 0.003}
        if split:
            rec["key_draws"] = 1000 * scale if keyed else 0
    return rec


def _log(job_scales, **kw):
    """Jobs of two sweep + summaries pairs; job 0 is the warm-up."""
    out, seq = [], 0
    for scale in job_scales:
        for _ in range(2):
            for kind in ("sweep", "sweep_summaries"):
                out.append(_record(seq, kind, scale, **kw))
                seq += 1
    return out


@pytest.fixture
def log(monkeypatch):
    box = []
    monkeypatch.setattr(simlock, "sweep_log", lambda: list(box))
    return box


CTX = {"plan": SimpleNamespace(calls=[object()] * 2)}


def test_readers_on_a_keyed_log(log):
    # warm-up 10x; window jobs 1x, 3x and 2x: the median job is 2x
    log.extend(_log([10, 1, 3, 2]))
    assert _reader("keys_ms")(CTX) == pytest.approx(2 * 0.002 * 2 * 1e3)
    assert _reader("key_draws")(CTX) == 2 * 1000 * 2
    # the process's totals, warm-up included: 4 records a job, 16 units
    assert _reader("trace_lower_s")(CTX) == pytest.approx(4 * 0.5 * 16)
    assert _reader("backend_compile_s")(CTX) == pytest.approx(4 * 0.1 * 16)


def test_a_key_off_log_has_no_keys_span_and_draws_nothing(log):
    log.extend(_log([10, 1, 3, 2], keyed=False))
    assert _reader("keys_ms")(CTX) is None
    assert _reader("key_draws")(CTX) == 0
    assert _reader("trace_lower_s")(CTX) == pytest.approx(32.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_span_or_counters_gives_nothing(name, log):
    """The log of a program that has neither the ``keys`` span, the
    ``key_draws`` counter nor the compile split."""
    log.extend(_log([10, 1, 3, 2], keyed=False, split=False))
    assert _reader(name)(CTX) is None


@pytest.mark.parametrize("name", NEW)
def test_an_empty_log_gives_nothing(name, log):
    assert _reader(name)(CTX) is None
