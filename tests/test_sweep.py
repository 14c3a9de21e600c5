"""Batched sweep-engine tests: batched-vs-sequential parity, determinism,
core padding, and chunked-scan invariance.

Tolerances: parity assertions are *exact* (event counts) or rtol=1e-9
(float summaries).  The batched path runs the same per-event HLO as the
single-run path — masked handlers with the batch dimension vmapped, a
shape-independent weighted pick for every RNG draw — so on CPU the
trajectories are bit-identical, not merely statistically close.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import simlock as sl


def _cell(st, i):
    return jax.tree.map(lambda x: np.asarray(x)[i], st)


def _close(got, want):
    assert got["events"] == want["events"]
    np.testing.assert_allclose(got["throughput_cs_per_s"],
                               want["throughput_cs_per_s"], rtol=1e-9)
    np.testing.assert_allclose(got["ep_p99_all_us"], want["ep_p99_all_us"],
                               rtol=1e-9)
    assert got["cs_per_core"] == want["cs_per_core"]


def test_sweep_slo_matches_per_config_run():
    cfg = sl.SimConfig(policy="libasl", sim_time_us=10_000.0)
    st, grid = sl.sweep(cfg, {"slo_us": [30.0, 70.0]})
    for i, slo in enumerate(grid["slo_us"]):
        _close(sl.summarize(cfg, _cell(st, i)),
               sl.summarize(cfg, sl.run(cfg, float(slo))))


def test_sweep_traced_policy_params_match_run():
    """w_big / prop_n ride as traced batch axes; cells == per-config runs."""
    tas = sl.SimConfig(policy="tas", sim_time_us=10_000.0)
    st, grid = sl.sweep(tas, {"w_big": [0.15, 8.0]})
    for i, w in enumerate(grid["w_big"]):
        _close(sl.summarize(tas, _cell(st, i)),
               sl.summarize(tas, sl.run(
                   dataclasses.replace(tas, w_big=float(w)), 1e9)))

    prop = sl.SimConfig(policy="prop", sim_time_us=10_000.0)
    st, grid = sl.sweep(prop, {"prop_n": [1, 20]})
    for i, p in enumerate(grid["prop_n"]):
        _close(sl.summarize(prop, _cell(st, i)),
               sl.summarize(prop, sl.run(
                   dataclasses.replace(prop, prop_n=int(p)), 1e9)))


def test_sweep_determinism():
    cfg = sl.SimConfig(policy="libasl", sim_time_us=8_000.0)
    a, _ = sl.sweep(cfg, {"slo_us": [50.0, 90.0], "seed": [0, 1]})
    b, _ = sl.sweep(cfg, {"slo_us": [50.0, 90.0], "seed": [0, 1]})
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # distinct seeds took distinct trajectories (events differ somewhere)
    ev = np.asarray(a.events).reshape(2, 2)
    assert (ev >= 0).all()


def test_padded_n_cores_matches_unpadded():
    """A cell running n<N cores padded to N == a dedicated n-core config."""
    for policy in ("fifo", "libasl"):
        cfg8 = sl.SimConfig(policy=policy, sim_time_us=10_000.0)
        st, _ = sl.sweep(cfg8, {"n_cores": [5]}, slo_us=60.0)
        got = sl.summarize(cfg8, _cell(st, 0), n_active=5)
        cfg5 = sl.SimConfig(
            policy=policy, n_cores=5, big=(1, 1, 1, 1, 0),
            speed_cs=(1.0,) * 4 + (3.75,), speed_nc=(1.0,) * 4 + (1.8,),
            sim_time_us=10_000.0)
        _close(got, sl.summarize(cfg5, sl.run(cfg5, 60.0)))


def test_chunked_scan_invariance():
    """chunk=1 (the seed's one-event-per-iteration loop) == chunk=128."""
    base = sl.SimConfig(policy="libasl", sim_time_us=4_000.0)
    r1 = sl.run(dataclasses.replace(base, chunk=1), 50.0, seed=3)
    r128 = sl.run(dataclasses.replace(base, chunk=128), 50.0, seed=3)
    for x, y in zip(jax.tree.leaves(r1), jax.tree.leaves(r128)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sweep_zip_mode_and_window0_axis():
    cfg = sl.SimConfig(policy="libasl", sim_time_us=8_000.0)
    st, grid = sl.sweep(cfg, {"slo_us": [0.0, 1e5],
                              "window0_us": [10.0, 1e5]}, product=False)
    assert np.asarray(st.events).shape == (2,)
    # MAX-window cell must out-throughput the zero-SLO (FIFO-like) cell
    s0 = sl.summarize(cfg, _cell(st, 0))
    s1 = sl.summarize(cfg, _cell(st, 1))
    assert s1["throughput_cs_per_s"] > s0["throughput_cs_per_s"]


def test_resumed_run_regrows_collapsed_windows():
    """The AIMD unit floor is seeded from default_window_us, not from the
    carried windows — a resume after total window collapse (FIFO
    fallback) must regrow once the SLO becomes achievable again (zero
    would otherwise be absorbing: growth is +unit, unit ~ window)."""
    cfg = sl.SimConfig(policy="libasl", sim_time_us=15_000.0)
    collapsed = sl.run(cfg, 0.0)                  # SLO=0: windows -> ~0
    assert float(np.asarray(collapsed.window)[4:].max()) < 1.0 * sl.US
    resumed = sl.run(cfg, 200.0, 0, np.asarray(collapsed.window))
    assert float(np.asarray(resumed.window)[4:].mean()) > 1.0 * sl.US


def test_sweep_rejects_unknown_axis_and_oversize_n():
    cfg = sl.SimConfig(policy="fifo", sim_time_us=1_000.0)
    with pytest.raises(ValueError):
        sl.sweep(cfg, {"bogus": [1]})
    with pytest.raises(ValueError):
        sl.sweep(cfg, {"n_cores": [cfg.n_cores + 1]})


# ---------------------------------------------------------------------------
# Resumable sweeps (sweep(resume_dir=...) over repro.ckpt.checkpointer)
# ---------------------------------------------------------------------------

def test_sweep_resume_bit_parity_after_kill(tmp_path, monkeypatch):
    """Kill-and-resume: a sweep interrupted after its first chunk and
    re-launched into the same directory must (a) not re-execute the
    completed chunk and (b) return results bit-identical to an
    uninterrupted run."""
    cfg = sl.SimConfig(policy="libasl", sim_time_us=3_000.0)
    axes = {"slo_us": [30.0, 50.0, 70.0, 90.0, 110.0], "seed": [0, 1]}
    want, _ = sl.sweep(cfg, axes)

    d = tmp_path / "resume"
    calls = {"n": 0}
    real_exec = sl._batch_executable

    def counting_exec(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2 and not (d / "poisoned").exists():
            (d / "poisoned").touch()
            raise KeyboardInterrupt("simulated kill mid-sweep")
        return real_exec(*a, **kw)

    monkeypatch.setattr(sl, "_batch_executable", counting_exec)
    with pytest.raises(KeyboardInterrupt):
        sl.sweep(cfg, axes, resume_dir=d, resume_chunk=4)
    killed_at = calls["n"]
    st, grid = sl.sweep(cfg, axes, resume_dir=d, resume_chunk=4)
    # chunk 0 (4 cells) was restored from disk, not re-executed
    assert calls["n"] == killed_at + 2
    assert len(grid["slo_us"]) == 10
    for x, y in zip(jax.tree.leaves(want), jax.tree.leaves(st)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sweep_resume_rejects_mismatched_grid(tmp_path):
    cfg = sl.SimConfig(policy="libasl", sim_time_us=1_000.0)
    d = tmp_path / "resume"
    sl.sweep(cfg, {"slo_us": [30.0, 50.0]}, resume_dir=d)
    with pytest.raises(ValueError, match="different sweep"):
        sl.sweep(cfg, {"slo_us": [30.0, 50.0]}, seed=1, resume_dir=d)


def test_sweep_resume_rejects_policy_kw_drift(tmp_path):
    """policy_kw values ride traced in SimParams.pol — the resume
    fingerprint digests them, so editing a knob between runs must not
    splice old chunks into the new sweep."""
    d = tmp_path / "resume"
    axes = {"slo_us": [30.0, 50.0]}
    cfg = sl.SimConfig(policy="shfl", sim_time_us=1_000.0,
                       policy_kw=(("shfl_bound", 4),))
    sl.sweep(cfg, axes, resume_dir=d)
    drift = dataclasses.replace(cfg, policy_kw=(("shfl_bound", 16),))
    with pytest.raises(ValueError, match="different sweep"):
        sl.sweep(drift, axes, resume_dir=d)
    # unchanged knobs still resume cleanly
    sl.sweep(cfg, axes, resume_dir=d)


def test_sweep_resume_rejects_column_drift(tmp_path):
    """Registered-column tables (owned or built-in) are digested too:
    a changed per-core table invalidates the directory."""
    d = tmp_path / "resume"
    axes = {"slo_us": [30.0, 50.0]}
    cfg = sl.with_columns(
        sl.SimConfig(policy="dvfs_race", sim_time_us=1_000.0),
        race_w=(1.0,) * 4, dvfs=(1.0,) * 4)
    sl.sweep(cfg, axes, resume_dir=d)
    for drift in (sl.with_columns(cfg, race_w=(2.0,) * 4),
                  sl.with_columns(cfg, dvfs=(1.5,) * 4),
                  sl.with_columns(cfg, slo_scale=(4.0,) * 4)):
        with pytest.raises(ValueError, match="different sweep"):
            sl.sweep(drift, axes, resume_dir=d)
    sl.sweep(cfg, axes, resume_dir=d)


def test_sweep_resume_incompatible_with_mesh(tmp_path):
    from repro.launch.mesh import make_sweep_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 (virtual) device")
    cfg = sl.SimConfig(policy="fifo", sim_time_us=1_000.0)
    with pytest.raises(ValueError, match="resume"):
        sl.sweep(cfg, {"seed": [0, 1]}, resume_dir=tmp_path / "r",
                 mesh=make_sweep_mesh())


# ---------------------------------------------------------------------------
# Mesh-sharded sweeps (conftest virtualizes 8 host devices)
# ---------------------------------------------------------------------------

def _mesh():
    from repro.launch.mesh import make_sweep_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 (virtual) device")
    return make_sweep_mesh()


def test_sharded_sweep_bit_identical_to_unsharded():
    """The tentpole invariant: sharding the cell dimension over the device
    mesh changes the schedule, not the numbers — every SimState leaf is
    exactly equal, including a non-divisible cell count (6 cells over 8
    devices => pad + trim)."""
    mesh = _mesh()
    cfg = sl.SimConfig(policy="libasl", sim_time_us=6_000.0)
    axes = {"slo_us": [30.0, 50.0, 70.0], "seed": [0, 1]}
    a, ga = sl.sweep(cfg, axes)
    b, gb = sl.sweep(cfg, axes, mesh=mesh)
    for k in ga:
        np.testing.assert_array_equal(ga[k], gb[k])
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sharded_sweep_follows_row_splits():
    """Per-device work obeys the sweep sharding rules: the cell axis is
    tiled in equal contiguous row splits over the mesh's data axis."""
    from repro.dist.sharding import build_sweep_rules, row_splits
    mesh = _mesh()
    rules = build_sweep_rules(mesh)
    n_shards = rules.num_shards("cells")
    assert n_shards == len(jax.devices())
    cfg = sl.SimConfig(policy="fifo", sim_time_us=2_000.0)
    n_cells = 2 * n_shards
    st, _ = sl.sweep(cfg, {"seed": list(range(n_cells))}, mesh=mesh)
    assert st.events.sharding.spec == rules.spec(("cells",), (n_cells,))
    splits = row_splits(n_cells, n_shards)
    got = [s.data.shape[0] for s in st.events.addressable_shards]
    assert sorted(got) == sorted(splits)


def test_sharded_executable_records_collectives():
    """The batched executable's accounting record: a sharded sweep carries
    cross-device collectives (the while_loop termination reduce), an
    unsharded one carries none."""
    mesh = _mesh()
    cfg = sl.SimConfig(policy="tas", sim_time_us=2_000.0)
    axes = {"w_big": [0.5, 1.0, 2.0, 4.0] * 2}
    sl.sweep(cfg, axes)
    sl.sweep(cfg, axes, mesh=mesh)
    unsharded, sharded = sl.sweep_log()[-2:]
    assert unsharded["devices"] == 1
    assert unsharded["collectives"]["total_count"] == 0
    assert sharded["devices"] == len(jax.devices())
    assert sharded["collectives"]["total_count"] > 0


def test_sweep_rules_degrade_without_data_axis():
    """A mesh without the requested data axis replicates instead of
    failing (same degradation discipline as the model rules)."""
    from repro.dist.sharding import build_sweep_rules
    mesh = _mesh()
    rules = build_sweep_rules(mesh, data_axis="model")
    assert rules.num_shards("cells") == 1
    cfg = sl.SimConfig(policy="fifo", sim_time_us=1_000.0)
    a, _ = sl.sweep(cfg, {"seed": [0, 1]})
    b, _ = sl.sweep(cfg, {"seed": [0, 1]}, mesh=mesh, data_axis="model")
    np.testing.assert_array_equal(np.asarray(a.events),
                                  np.asarray(b.events))
