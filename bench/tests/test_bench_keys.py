"""Key-sharded configurations: the generator passes a ``keys`` block to the
program and the reference, the reference draws each epoch's Zipf key and
read/write class as the traffic defines them, and every leaf agrees with
the program on the CPU; the control departs on keyed lanes; a block no run
could honour is refused; the key-off cells build what they built before."""

import hashlib
import json
import math
import sys
from pathlib import Path

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

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
TINY_US = 300.0
SEED = 2**31 + 1515          # larger than 32 signed bits hold
POLICIES = list(cells.load_json("configs", "m1_fig1")["policies"])


def keyed_config(theta=0.99, n_keys=64, n_locks=4):
    config = cells.load_json("configs", "m1_fig1")
    config["epoch"]["n_locks"] = n_locks
    config["keys"] = {"n_keys": n_keys, "zipf_theta": theta}
    return config


def traffic(name="fig1_grid", **over):
    return dict(cells.load_json("traffic", name), **over)


def run_and_compare(plan, seed=None) -> list:
    """One job of ``plan`` through the timed path, every lane compared with
    the reference; returns (lane, program leaves) per lane."""
    job = brun.run_job(plan, 0, cells.derive(SEED, 0) if seed is None
                       else seed)
    out = []
    for call, lanes, cfg, st, sums in job.calls:
        assert cfg.n_keys == plan.config["keys"]["n_keys"]
        st = jax.tree.map(np.asarray, st)
        for li, lane in enumerate(lanes):
            want = ref.simulate(lane)
            got = brun.host_leaves(st, li, plan.kind == "run")
            assert ref.leaves_differing(got, want["leaves"]) == [], \
                (call.policies, li)
            assert ref.summary_gap(sums[li], want["summary"]) == 0.0
            out.append((lane, got))
    return out


def locks_drawn(compared) -> set:
    return {int(x) for _, got in compared for x in got["cur_lock"]}


@pytest.mark.parametrize("policy", POLICIES)
def test_keyed_sweep_matches_reference(policy):
    plan = cells.Plan(keyed_config(), traffic(policies=[policy]),
                      sim_time_us=TINY_US)
    compared = run_and_compare(plan)
    assert len(compared) == 8
    assert locks_drawn(compared) == {0, 1, 2, 3}
    assert {ln["n_keys"] for ln, _ in compared} == {64}


def test_keyed_single_run_matches_reference():
    plan = cells.Plan(keyed_config(), traffic("fig1_single",
                                              policies=["ks_crew"]),
                      sim_time_us=TINY_US)
    (lane, got), = run_and_compare(plan)
    assert len(set(got["cur_lock"].tolist())) > 1
    assert (got["cur_rw"] < 1.0).all()
    out = brun.measure(plan, "keyed_single", SEED, 0.0, False,
                       jax.devices(), MAN)
    assert out["correct"], out["check"]


def test_keyed_merged_call_draws_rw_per_lane():
    """In one executable of fifo, ks_crew and libasl only the ks_crew lanes
    draw the read/write uniform; the others keep 1.0, a read."""
    plan = cells.Plan(keyed_config(),
                      traffic(policies=["fifo", "ks_crew", "libasl"],
                              merge_policies=True),
                      sim_time_us=TINY_US)
    assert plan.calls[0].merged
    compared = run_and_compare(plan)
    assert len(compared) == 24
    for lane, got in compared:
        if lane["policy"] == "ks_crew":
            assert (got["cur_rw"] < 1.0).all()
        else:
            assert (got["cur_rw"] == 1.0).all()
    assert locks_drawn(compared) == {0, 1, 2, 3}


@pytest.mark.parametrize("theta", [0.0, 1.0, 1.2])
def test_keyed_skew(theta):
    """Uniform keys, the pole at 1 (moved off it) and hot-key collapse."""
    plan = cells.Plan(keyed_config(theta=theta),
                      traffic(policies=["fifo", "ks_crew", "ks_jbsq"],
                              merge_policies=True, axes={"n_cores": [8]}),
                      sim_time_us=TINY_US)
    compared = run_and_compare(plan)
    assert {ln["zipf_theta"] for ln, _ in compared} == {theta}


@pytest.mark.parametrize("policy", ["fifo", "ks_crew"])
def test_control_fails_on_a_keyed_lane(policy):
    """The control draws keys and read/write classes in bfloat16 too, and
    departs from the float32 reference in them."""
    plan = cells.Plan(keyed_config(), traffic(policies=[policy]),
                      sim_time_us=1500.0)
    lane = plan.lanes(plan.calls[0], cells.derive(SEED, 3))[-1]
    want = ref.simulate(lane)["leaves"]
    got = ref.simulate(lane, dtype=ref.CONTROL_DTYPE)["leaves"]
    assert {"cur_lock", "cur_rw"} & set(ref.leaves_differing(got, want))


def test_zipf_draws_follow_the_pmf():
    """The reference's key draw has Zipf's shape: rank 0 and 1 exactly at
    their probabilities, the tail by Gray et al.'s approximation."""
    n, theta = 64, 0.99
    draws = ref.EpochDraws({"n_keys": n, "zipf_theta": theta, "n_locks": n,
                            "policy": "fifo", "seed": 7}, np.float32,
                           jax.devices("cpu")[0])
    keys = np.asarray([draws(c, e)[0] for c in range(8)
                       for e in range(ref.BLOCK)])
    assert keys.min() >= 0 and keys.max() < n
    pmf = np.arange(1, n + 1, dtype=float) ** -theta
    pmf /= pmf.sum()
    freq = np.bincount(keys, minlength=n) / keys.size
    assert abs(freq[0] - pmf[0]) < 4 * math.sqrt(pmf[0] / keys.size)
    assert abs(freq[1] - pmf[1]) < 4 * math.sqrt(pmf[1] / keys.size)
    assert freq[:8].sum() > 0.5


@pytest.mark.parametrize("keys,arrivals,match", [
    ({"n_keys": 3, "zipf_theta": 0.99}, False, "n_locks"),
    ({"n_keys": 64.0, "zipf_theta": 0.99}, False, "n_locks"),
    ({"n_keys": 64, "zipf_theta": -0.5}, False, "finite"),
    ({"n_keys": 64, "zipf_theta": float("nan")}, False, "finite"),
    ({"n_keys": 64, "zipf_theta": float("inf")}, False, "finite"),
    ({"n_keys": 64, "theta": 0.99}, False, "exactly"),
    ({"n_keys": 64, "zipf_theta": 0.99}, True, "closed-loop"),
])
def test_keys_block_refused(keys, arrivals, match):
    config = keyed_config()
    config["keys"] = keys
    config["arrivals"] = {"open_loop": arrivals}
    with pytest.raises(ValueError, match=match):
        cells.Plan(config, traffic())


# Digests of what the parent commit's generator built for the key-off
# cells: per call the reference lanes, the SimConfig's keyword arguments
# and the sweep axes, as sorted JSON, at seed derive(2**31 + 977, 0).
KEY_OFF_BUILDS = {
    "fig1_grid":
        "dce315d8e148fefcc2c82881ff9ab127a891d90546d40c5cee02a166e01a2c3b",
    "fig1_single":
        "babc482e7370f5a976ed0dd73173978dbcb32f51264f56b5bf2568fd6a5ab198",
}


@pytest.mark.parametrize("name", sorted(KEY_OFF_BUILDS))
def test_key_off_cells_build_as_before(name, monkeypatch):
    from repro.core import simlock as sl
    monkeypatch.setattr(sl, "SimConfig", lambda **kw: kw)
    plan = cells.plan_for(name, ROOT)
    seed = cells.derive(2**31 + 977, 0)
    built = []
    for call in plan.calls:
        lanes = plan.lanes(call, seed)
        built.append({"lanes": lanes, "sim_config": plan.sim_config(call),
                      "axes": plan.axes(call, lanes)})
    assert all("n_keys" not in ln for b in built for ln in b["lanes"])
    text = json.dumps(built, sort_keys=True).encode()
    assert hashlib.sha256(text).hexdigest() == KEY_OFF_BUILDS[name]
