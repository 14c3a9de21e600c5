"""A run whose timed path is broken underneath reads ``correct: false``.

Each test drives ``bench.run.measure`` (everything a run does but the look
for a chip) at a tiny horizon on the CPU, with one fault planted in the
program: a step that returns its state unchanged, half of the lanes left
out, the exchange between chips left out, an answer altered where it is
produced."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from repro.launch.xla_flags import ensure_host_devices  # noqa: E402

ensure_host_devices(8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import cells  # noqa: E402
from bench import run as brun  # noqa: E402
from repro.core import simlock as sl  # noqa: E402

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
TINY_US = 300.0
SEED = 2**31 + 4242


class Broken:
    """``simlock`` with some entry points replaced."""

    def __init__(self, **over):
        self.__dict__.update(over)

    def __getattr__(self, k):
        return getattr(sl, k)


def _run(name, program=None, calls=None, plan=None):
    plan = plan or cells.plan_for(name, ROOT, sim_time_us=TINY_US)
    if calls:
        plan.calls = [c for c in plan.calls if c.policies[0] in calls]
    return brun.measure(plan, name, SEED, 0.0, False, jax.devices(), MAN,
                        sl=program)


def _lanes(st, keep):
    """Zero every leaf of the lanes where ``keep`` is False."""
    def f(x):
        m = keep.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.where(m, x, jnp.zeros_like(x))
    return jax.tree.map(f, st)


@pytest.fixture
def fresh_programs():
    sl._BATCH_EXECS.clear()
    sl._run_single.clear_cache()
    yield
    sl._BATCH_EXECS.clear()
    sl._run_single.clear_cache()


@pytest.mark.parametrize("name,calls", [("fig1_single", None),
                                        ("fig1_grid", ("libasl",))])
def test_step_returning_its_state(name, calls, fresh_programs, monkeypatch):
    monkeypatch.setattr(sl, "_step",
                        lambda cfg, tb, pm, horizon, st, masked: st)
    out = _run(name, calls=calls)
    assert not out["correct"]
    assert out["check"]["leaves_differing"]["value"] > 0


def test_half_the_lanes_left_out():
    def sweep(*a, **k):
        st, grid = sl.sweep(*a, **k)
        n = st.events.shape[0]
        return _lanes(st, jnp.arange(n) < n // 2), grid
    out = _run("fig1_grid", Broken(sweep=sweep), calls=("fifo", "shfl"))
    assert not out["correct"]


def test_exchange_between_chips_left_out():
    """Only the first chip's shard of the sharded result reaches the host;
    the other chips' lanes come back empty."""
    def sweep(*a, mesh=None, **k):
        assert mesh is not None and mesh.size == 4
        st, grid = sl.sweep(*a, mesh=mesh, **k)
        n = st.events.shape[0]
        return _lanes(st, jnp.arange(n) < n // 4), grid
    # fig1's grid with its seed replicas sharded over four chips
    traffic = dict(cells.load_json("traffic", "fig1_grid"),
                   policies=["libasl"], seed_replicas=2)
    plan = cells.Plan(cells.load_json("configs", "m1_fig1"), traffic,
                      chips=4, sim_time_us=TINY_US)
    out = _run("fig1_grid", Broken(sweep=sweep), plan=plan)
    assert not out["correct"]


@pytest.mark.parametrize("name", ["fig1_single", "fig1_grid"])
def test_answer_altered_where_produced(name):
    def bump(st):
        return st._replace(cs_cnt=st.cs_cnt.at[..., 0].add(1))

    def sweep(*a, **k):
        st, grid = sl.sweep(*a, **k)
        return bump(st), grid

    def run(*a, **k):
        return bump(sl.run(*a, **k))
    out = _run(name, Broken(sweep=sweep, run=run),
               calls=("fifo", "ks_crew") if name == "fig1_grid" else None)
    assert not out["correct"]
    assert out["check"]["summary_gap"]["value"] > 0
