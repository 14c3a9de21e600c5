"""Key-sharded datastore axis tests (docs/workloads.md §Key-sharded
traffic, docs/simulator.md §Multi-lock axis):

* the Zipf key stream — pmf moments, prefix invariance, host/device
  agreement (the engine's ``cur_lock`` matches the host reconstruction);
* the multi-lock engine — pre-refactor bit-parity (golden digests from
  ``tests/data/keyshard_golden.json``, captured at the old commit by
  ``tests/golden_digests.py``), keyed single-lock parity, lock padding
  parity, and the one-executable sweep discipline over the three key
  axes;
* config validation + the resume-fingerprint drift rejection for the
  new traced key params.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

import golden_digests as gd
from repro.core import simlock as sl
from repro.workloads import keys as wlk

GOLDEN = json.loads(gd.GOLDEN.read_text())


def _keyed(policy="fifo", **kw):
    base = dict(policy=policy, sim_time_us=2_000.0, n_locks=4,
                n_keys=256, zipf_theta=0.99)
    base.update(kw)
    return sl.SimConfig(**base)


# ---------------------------------------------------------------------------
# Zipf key stream
# ---------------------------------------------------------------------------

def test_zipf_moments():
    """Empirical key frequencies track the exact pmf: ranks 0/1 are
    exact in the Gray/YCSB inverse-CDF (tight tolerance), the tail is
    the power-law approximation (total-variation bound)."""
    n_keys, theta = 64, 0.99
    tab = wlk.key_table(0, 64, 512, n_keys, theta)
    emp = np.bincount(tab.ravel(), minlength=n_keys) / tab.size
    pmf = wlk.zipf_pmf(n_keys, theta)
    assert abs(emp[0] - pmf[0]) < 0.10 * pmf[0]
    assert abs(emp[1] - pmf[1]) < 0.15 * pmf[1]
    assert 0.5 * np.sum(np.abs(emp - pmf)) < 0.03     # total variation
    # rank-ordering: hot keys really are hotter
    assert emp[0] > emp[4] > emp[31]


def test_zipf_uniform_at_theta_zero():
    n_keys = 32
    tab = wlk.key_table(1, 64, 256, n_keys, 0.0)
    emp = np.bincount(tab.ravel(), minlength=n_keys) / tab.size
    assert 0.5 * np.sum(np.abs(emp - 1.0 / n_keys)) < 0.03


def test_key_table_prefix_invariance():
    """Counter-based draws: growing the table in either dimension never
    perturbs existing entries."""
    small = wlk.key_table(7, 8, 64, 128, 0.9)
    big = wlk.key_table(7, 16, 256, 128, 0.9)
    np.testing.assert_array_equal(small, big[:8, :64])


def test_zipf_consts_validation():
    with pytest.raises(ValueError, match="n_keys"):
        wlk.zipf_consts(0, 0.9)
    with pytest.raises(ValueError, match="theta"):
        wlk.zipf_consts(8, float("nan"))
    with pytest.raises(ValueError, match="theta"):
        wlk.zipf_consts(8, -0.5)
    # the pole is nudged, not rejected — and the nudged theta is
    # returned so host and device agree
    th, _, _, _ = wlk.zipf_consts(8, 1.0)
    assert th != 1.0 and abs(th - 1.0) < 1e-3


@pytest.mark.parametrize("n_keys", [2**24 + 1, 2**24 + 2, 2**25])
def test_more_keys_than_float32_ranks_reach_are_refused(n_keys):
    with pytest.raises(ValueError, match="float32"):
        wlk.zipf_consts(n_keys, 0.99)
    with pytest.raises(ValueError, match="float32"):
        sl.SimConfig(n_locks=16, n_keys=n_keys)


def test_2_24_keys_are_taken_and_reach_odd_tail_ranks():
    cfg = sl.SimConfig(n_locks=16, n_keys=2**24, zipf_theta=0.99)
    assert sl.build_params(cfg, 1e9).ks_keys == 2**24
    tab = wlk.key_table(11, 4, 4096, 2**24, 0.99)
    assert tab.min() >= 0 and tab.max() < 2**24
    top = tab[tab > 2**23]
    assert (top % 2 == 1).any() and (top % 2 == 0).any()
    # every bucket of 16 is drawn, odd buckets among them
    assert set(np.unique(tab % 16).tolist()) == set(range(16))


def test_engine_lock_matches_host_reconstruction():
    """Closed loop: after a run, every core's current lock is the host
    ``lock_table`` entry at its completed-epoch index (epoch ``ep_cnt``
    is the in-progress one — drawn at the previous release)."""
    cfg = _keyed()
    st = sl.run(cfg, 80.0, seed=3)
    tab = wlk.lock_table(3, cfg.n_cores, int(np.max(st.ep_cnt)) + 1,
                         cfg.n_keys, cfg.zipf_theta, cfg.n_locks)
    cur = np.asarray(st.cur_lock)
    ep = np.asarray(st.ep_cnt)
    for c in range(cfg.n_cores):
        assert cur[c] == tab[c, ep[c]]


def test_crew_rw_stream_matches_host():
    cfg = _keyed("ks_crew")
    st = sl.run(cfg, 80.0, seed=3)
    for c in range(cfg.n_cores):
        want = float(wlk.epoch_rw_u(3, c, int(st.ep_cnt[c])))
        assert float(st.cur_rw[c]) == want


# ---------------------------------------------------------------------------
# Engine bit-parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", sorted(GOLDEN))
def test_pre_refactor_digest_parity(policy):
    """The keyshard refactor's core guarantee: with the key gate off
    (every pre-existing config), single runs, sweeps and summaries are
    sha256-identical to the pre-refactor engine — for every policy and
    every record kind the golden file names (new state fields the
    refactor added are deliberately NOT in the file)."""
    cur = gd.capture_policy(policy)
    for kind, dig in GOLDEN[policy].items():
        if isinstance(dig, dict):
            for field, h in dig.items():
                assert cur[kind].get(field) == h, (policy, kind, field)
        else:
            assert cur[kind] == dig, (policy, kind)


#: Gate-on/gate-off comparable policies: no read/write stream (ks_crew
#: draws cur_rw when keyed, so its decisions legitimately differ).
_PARITY_FIELDS = ("t", "events", "phase", "t_ready", "seg", "ep_cnt",
                  "cs_cnt", "ep_lat", "cs_lat", "holder", "window")


@pytest.mark.parametrize("policy", ["fifo", "libasl", "ks_erew"])
def test_single_lock_keyed_matches_gate_off(policy):
    """n_locks=1 with the key gate ON is bit-identical to the gate-off
    engine on every pre-existing field: all keys bucket to lock 0, so
    the Zipf draws must not perturb the trajectory."""
    off = sl.run(sl.SimConfig(policy=policy, sim_time_us=2_000.0),
                 80.0, seed=3)
    on = sl.run(sl.SimConfig(policy=policy, sim_time_us=2_000.0,
                             n_locks=1, n_keys=64, zipf_theta=1.2),
                80.0, seed=3)
    for f in _PARITY_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(off, f)), np.asarray(getattr(on, f)), f)


def test_lock_padding_parity():
    """A swept n_locks cell runs padded to the template's cfg.n_locks —
    results must be bit-identical to an unpadded run at that count (the
    lock axis is a padded, mask-active dimension like cores)."""
    cfg = _keyed(n_locks=8)
    st_sw, _ = sl.sweep(cfg, {"n_locks": [2, 8]}, slo_us=80.0, seed=3)
    for i, nl in enumerate((2, 8)):
        single = sl.run(_keyed(n_locks=nl), 80.0, seed=3)
        cell = jax.tree.map(lambda x, i=i: x[i], st_sw)
        for f in _PARITY_FIELDS:
            if f == "holder":
                continue                      # padded shape differs
            np.testing.assert_array_equal(
                np.asarray(getattr(single, f)),
                np.asarray(getattr(cell, f)), (nl, f))
        np.testing.assert_array_equal(
            np.asarray(single.holder),
            np.asarray(cell.holder)[:nl], nl)
        np.testing.assert_array_equal(
            np.asarray(single.cur_lock), np.asarray(cell.cur_lock), nl)


def test_keyed_sweep_cell_matches_single():
    """Zipped cells over (zipf_theta, n_locks) reproduce the matching
    single runs exactly — the sweep engine's per-cell Zipf constants
    agree with build_params."""
    cfg = _keyed(n_locks=4)
    st_sw, _ = sl.sweep(cfg, {"zipf_theta": [0.5, 1.2],
                              "n_locks": [4, 2]},
                        product=False, slo_us=80.0, seed=3)
    for i, (th, nl) in enumerate(((0.5, 4), (1.2, 2))):
        single = sl.run(
            dataclasses.replace(cfg, zipf_theta=th, n_locks=nl),
            80.0, seed=3)
        cell = jax.tree.map(lambda x, i=i: x[i], st_sw)
        for f in ("t", "events", "ep_cnt", "cs_cnt", "cur_lock"):
            np.testing.assert_array_equal(
                np.asarray(getattr(single, f)),
                np.asarray(getattr(cell, f)), (th, nl, f))


def test_key_axes_share_one_executable():
    """The whole keyshard figure shape: thetas and lock counts zipped in
    one sweep call -> at most one new batched executable."""
    cfg = _keyed(n_locks=8, n_keys=512)
    axes = {"zipf_theta": [0.0, 0.9, 1.2, 0.99, 0.99],
            "n_locks": [8, 8, 8, 2, 4]}
    n0 = sl.n_batch_executables()
    st, grid = sl.sweep(cfg, axes, product=False, slo_us=80.0, seed=3)
    assert sl.n_batch_executables() - n0 <= 1
    assert np.shape(st.t)[0] == 5
    # more skew or fewer locks -> no more throughput
    eps = np.asarray(st.ep_cnt).sum(axis=1)
    assert eps[2] <= eps[0]
    assert eps[3] <= eps[0]


# ---------------------------------------------------------------------------
# Validation + sweep plumbing
# ---------------------------------------------------------------------------

def test_config_validation():
    with pytest.raises(ValueError, match="n_keys"):
        sl.SimConfig(policy="fifo", n_keys=-1)
    with pytest.raises(ValueError, match="zipf_theta"):
        sl.SimConfig(policy="fifo", n_keys=8, zipf_theta=float("nan"))
    with pytest.raises(ValueError, match="zipf_theta"):
        sl.SimConfig(policy="fifo", n_keys=8, zipf_theta=-1.0)
    with pytest.raises(ValueError, match="at least one key"):
        sl.SimConfig(policy="fifo", n_locks=8, n_keys=4)
    with pytest.raises(ValueError, match="n_locks"):
        sl.SimConfig(policy="fifo", n_locks=0)


def test_sweep_rejects_key_axes_without_gate():
    cfg = sl.SimConfig(policy="fifo", sim_time_us=1_000.0)
    with pytest.raises(ValueError, match="key-shard gate"):
        sl.sweep(cfg, {"zipf_theta": [0.5, 0.9]})
    with pytest.raises(ValueError, match="key-shard gate"):
        sl.sweep(cfg, {"n_locks": [1]})


def test_sweep_n_keys_axis_flips_gate():
    cfg = sl.SimConfig(policy="fifo", sim_time_us=1_000.0, n_locks=2)
    st, grid = sl.sweep(cfg, {"n_keys": [64, 256]}, slo_us=80.0, seed=3)
    assert np.shape(st.t)[0] == 2
    assert np.any(np.asarray(st.cur_lock) > 0)   # keys actually drawn
    with pytest.raises(ValueError, match=">= 1"):
        sl.sweep(cfg, {"n_keys": [0, 64]})


def test_sweep_rejects_bad_lock_cells():
    cfg = _keyed(n_locks=4)
    with pytest.raises(ValueError, match="n_locks axis"):
        sl.sweep(cfg, {"n_locks": [2, 8]})       # exceeds padded size
    with pytest.raises(ValueError, match="n_locks axis"):
        sl.sweep(cfg, {"n_locks": [0]})
    with pytest.raises(ValueError, match="at least one key"):
        sl.sweep(cfg, {"n_keys": [2], "n_locks": [4]}, product=False)


def test_sweep_resume_rejects_key_drift(tmp_path):
    """The resume fingerprint digests the traced key params (ks_*), so
    editing the Zipf exponent or key count between runs must not splice
    old chunks into the new sweep."""
    d = tmp_path / "resume"
    axes = {"slo_us": [30.0, 50.0]}
    cfg = _keyed(sim_time_us=1_000.0)
    sl.sweep(cfg, axes, resume_dir=d)
    for drift in (dataclasses.replace(cfg, zipf_theta=1.2),
                  dataclasses.replace(cfg, n_keys=64)):
        with pytest.raises(ValueError, match="different sweep"):
            sl.sweep(drift, axes, resume_dir=d)
    # unchanged key params still resume cleanly
    sl.sweep(cfg, axes, resume_dir=d)
