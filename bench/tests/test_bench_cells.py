"""Every cell builds from its files and runs at a tiny horizon on the CPU;
the reference agrees with the program where the CPU's arithmetic is the
chip's; the control disagrees; a cell added as files only is found."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from repro.launch.xla_flags import ensure_host_devices  # noqa: E402

ensure_host_devices(8)

import numpy as np  # noqa: E402

from bench import cells  # noqa: E402
from bench import reference as ref  # noqa: E402
from bench import run as brun  # noqa: E402
from bench.metrics import lane_step_live_pct  # noqa: E402

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
TINY_US = 300.0
SEED = 2**31 + 977          # larger than 32 signed bits hold


@pytest.mark.parametrize("name", [w["name"] for w in MAN["workloads"]])
def test_cell_builds_its_grid(name):
    plan = cells.plan_for(name, ROOT, sim_time_us=TINY_US)
    w = cells.workload(name, ROOT)
    assert plan.chips == w["chips"]
    seed = cells.derive(SEED, 0)
    assert 0 <= seed < 2**31
    for call in plan.calls:
        lanes = plan.lanes(call, seed)
        cfg = plan.sim_config(call)
        axes = plan.axes(call, lanes)
        assert lanes and all(len(v) == len(lanes) for v in axes.values())
        assert cfg.sim_time_us == TINY_US
        assert {ln["seed"] for ln in lanes} <= set(
            axes.get("seed", [seed])) | {seed}
    assert plan.lanes(plan.calls[0], seed) == plan.lanes(plan.calls[0], seed)


@pytest.mark.parametrize("name", [w["name"] for w in MAN["workloads"]])
def test_cell_runs_a_tiny_job(name):
    import jax
    plan = cells.plan_for(name, ROOT, sim_time_us=TINY_US)
    plan.calls = plan.calls[:2]
    mesh = None
    if plan.chips > 1:
        from repro.launch.mesh import make_sweep_mesh
        mesh = make_sweep_mesh(plan.chips)
    job = brun.run_job(plan, 0, cells.derive(SEED, 0), mesh)
    lanes = sum(len(c[1]) for c in job.calls)
    assert job.events > 0 and job.wall > 0
    assert sum(len(e) for _, e in job.lane_events) == lanes
    assert all(isinstance(x, jax.Array) for c in job.calls
               for x in jax.tree.leaves(c[3]))


@pytest.mark.parametrize("name,policies", [
    ("fig1_grid", ("tas", "libasl")), ("fig1_grid", ("edf", "ks_jbsq")),
    ("fig1_grid", ("prop", "dvfs_race"))])
def test_sound_sweep_is_correct(name, policies):
    """The program and the reference agree bit for bit on the CPU too, so
    a sound run reads correct."""
    import jax
    plan = cells.plan_for(name, ROOT, sim_time_us=TINY_US)
    plan.calls = [c for c in plan.calls if c.policies[0] in policies]
    out = brun.measure(plan, name, SEED, 0.0, False, jax.devices(), MAN)
    assert out["correct"], out["check"]
    assert out["check"]["leaves_differing"]["value"] == 0
    assert out["metrics"]["events_per_s"]["value"] > 0


def test_sound_single_run_is_correct():
    import jax
    plan = cells.plan_for("fig1_single", ROOT, sim_time_us=TINY_US)
    out = brun.measure(plan, "fig1_single", SEED, 0.0, False, jax.devices(),
                       MAN)
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"events_per_s", "job_p95_ms", "setup_s"}
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("name,policy", [("fig1_grid", "libasl"),
                                         ("fig1_grid", "tas"),
                                         ("fig1_single", "libasl")])
def test_control_fails(name, policy):
    """The control, the reference in bfloat16, departs from the float32
    reference in some state leaf."""
    plan = cells.plan_for(name, ROOT, sim_time_us=1500.0)
    call = next(c for c in plan.calls if policy in c.policies)
    lanes = [ln for ln in plan.lanes(call, cells.derive(SEED, 3))
             if ln["policy"] == policy][-1:]
    for lane in lanes:
        want = ref.simulate(lane)["leaves"]
        got = ref.simulate(lane, dtype=ref.CONTROL_DTYPE)["leaves"]
        assert ref.leaves_differing(got, want)


def test_lane_step_live_share_arithmetic():
    live = lane_step_live_pct.live_share
    assert live([(128, [128, 128])]) == 100.0
    assert live([(128, [256, 128])]) == pytest.approx(75.0)
    # 100 events need one chunk of 128: 100 of 128 slots
    assert live([(128, [100])]) == pytest.approx(100 * 100 / 128)
    # two calls pool their slots: (10+20) of 2x32 + (5) of 1x8
    assert live([(32, [10, 20]), (8, [5])]) == pytest.approx(
        100 * 35 / (64 + 8))
    assert live([]) is None


def test_cell_added_as_files_is_found(tmp_path):
    """A later cell needs a traffic file, maybe a config file, and a
    BENCHMARK.json entry: no code."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = json.loads((ROOT / "bench/traffic/fig1_single.json").read_text())
    traffic["policies"] = ["shfl"]
    (tmp_path / "bench/traffic/shfl_single.json").write_text(
        json.dumps(traffic))
    man["workloads"].append({"name": "shfl_single", "config": "m1_fig1",
                             "traffic": "shfl_single", "chips": 1,
                             "why": "a test cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    plan = cells.plan_for("shfl_single", tmp_path)
    assert plan.calls[0].policies == ("shfl",)
    lane = plan.lanes(plan.calls[0], 5)[0]
    assert lane["shfl_bound"] == 4 and lane["sim_time_us"] == 6000.0


def test_open_loop_config_is_refused():
    """The generator and the reference model closed-loop epochs only: a
    configuration asking for open-loop arrivals is refused, not run as
    closed-loop."""
    config = cells.load_json("configs", "m1_fig1")
    config["arrivals"] = {"open_loop": True}
    with pytest.raises(ValueError, match="closed-loop"):
        cells.Plan(config, cells.load_json("traffic", "fig1_grid"))


def test_refuses_without_a_tpu():
    """The command measures nothing on the CPU and prints no result."""
    assert brun.main(["--workload", "fig1_single", "--seed", "1",
                      "--seconds", "1", "--trace", "0"]) != 0


def test_seeds_are_fixed_by_the_run_seed():
    assert cells.derive(SEED, 4) == cells.derive(SEED, 4)
    assert cells.derive(SEED, 4) != cells.derive(SEED, 5)
    assert np.int32(cells.derive(2**40, 1)) == cells.derive(2**40, 1)
