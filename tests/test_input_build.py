"""The input build of ``sweep`` and ``run``: numpy on the host, then one
``jax.device_put`` of the whole input tree a call, with no eager device
operation in between.

The build span of every call here runs under a guard that refuses implicit
host-to-device transfers, which every eager ``jnp`` scalar or stack is; an
explicit ``device_put`` passes.  The placed inputs are checked against the
digests of those the earlier eager build placed (shapes, dtypes and bytes of
every leaf), the executables against one per policy, and a sweep lane
against ``run`` of the same point, leaf for leaf."""

import contextlib
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from repro.core import calllog
from repro.core import simlock as sl
from repro.launch.mesh import make_sweep_mesh

CFG = sl.SimConfig(policy="libasl", sim_time_us=200.0)
SPEEDS = ((1.0,) * 8, (1.0,) * 4 + (2.5,) * 4)
SCALES = ((1.0,) * 8, (2.0,) * 8)

# name -> (config, axes, sweep options, lane checked against run() or
# None, the config of that run, the digests of the inputs each batched
# call was given by the eager build)
CASES = {
    "policy_axis": (
        CFG, {"policy": ["fifo", "shfl", "dvfs_race", "ks_crew", "edf"],
              "slo_us": [30.0, 50.0]}, {}, None, None,
        ["5e40afe6bc339af3"]),
    "n_cores_axis": (
        CFG, {"n_cores": [1, 3, 8]}, {}, 2, CFG,
        ["22f84a748d2feadd"]),
    "table_axis": (
        CFG, {"speed_cs": list(SPEEDS), "slo_scale": list(SCALES)}, {}, 3,
        dataclasses.replace(CFG, speed_cs=SPEEDS[1], slo_scale=SCALES[1]),
        ["9bd415ccd5c5d9b6"]),
    "mesh": (
        CFG, {"n_cores": [1, 8, 5]}, {"mesh": True}, 1, CFG,
        ["6c2983b1aba633c7"]),
    "resumable": (
        CFG, {"n_cores": [5, 6, 7, 8]}, {"resume_chunk": 2}, 3, CFG,
        ["718690dc7eb6247d", "17883efb651ad8dd"]),
}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for x in jax.tree.leaves(tree):
        a = np.asarray(x)
        h.update(repr((a.shape, a.dtype.name)).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


@pytest.fixture
def guarded_build(monkeypatch):
    """Every ``build`` span refuses implicit host-to-device transfers."""
    span = calllog.span

    @contextlib.contextmanager
    def guarded(name):
        guard = jax.transfer_guard_host_to_device("disallow") \
            if name == "build" else contextlib.nullcontext()
        with span(name), guard:
            yield

    monkeypatch.setattr(calllog, "span", guarded)


@pytest.fixture
def placed(monkeypatch):
    """The inputs each batched call is given, in call order."""
    seen, call = [], sl._call_batch

    def spy(ccfg, tb, pm, w0):
        seen.append((tb, pm, w0))
        return call(ccfg, tb, pm, w0)

    monkeypatch.setattr(sl, "_call_batch", spy)
    return seen


@pytest.fixture
def fresh_programs():
    sl._BATCH_EXECS.clear()
    yield
    sl._BATCH_EXECS.clear()


def _new_records(fn):
    log = sl.sweep_log()
    mark = log[-1]["seq"] + 1 if log else 0
    out = fn()
    return out, [r for r in sl.sweep_log() if r["seq"] >= mark]


def test_the_guard_refuses_an_eager_scalar_in_a_build(guarded_build):
    with calllog.span("build"):
        jax.device_put(np.int32(5)).block_until_ready()
        with pytest.raises(jax.errors.JaxRuntimeError, match="Disallowed"):
            jnp.int32(5).block_until_ready()


@pytest.mark.parametrize("name", list(CASES))
def test_sweep_builds_on_the_host_and_places_once(
        name, guarded_build, placed, fresh_programs, tmp_path):
    cfg, axes, opts, lane, run_cfg, digests = CASES[name]
    kw = {"slo_us": 50.0, "seed": 3}
    if opts.get("mesh"):
        kw["mesh"] = make_sweep_mesh()
    if "resume_chunk" in opts:
        kw.update(resume_dir=tmp_path, resume_chunk=opts["resume_chunk"])
    (st, grid), recs = _new_records(lambda: sl.sweep(cfg, axes, **kw))

    assert [_digest(x) for x in placed] == digests
    rows = sum(r["lanes"] for r in recs)
    for (tb, pm, w0), rec in zip(placed, recs):
        leaves = jax.tree.leaves((tb, pm, w0))
        assert rec["arrays"] == len(leaves)
        n = np.shape(w0)[0]
        for x in leaves:
            assert isinstance(x, jax.Array) and x.shape[0] == n
            assert x.dtype in (jnp.int32, jnp.float32) and not x.weak_type
            if "mesh" in kw:
                assert isinstance(x.sharding, NamedSharding)
                assert x.sharding.num_devices == 8
            else:
                assert x.sharding.device_set == {jax.devices()[0]}
    assert rows == len(next(iter(grid.values())))
    assert sl.n_batch_executables() == 1      # one policy (set), one program

    if lane is not None:
        want = sl.run(run_cfg, 50.0, 3)
        for x, y in zip(jax.tree.leaves(want), jax.tree.leaves(st)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y)[lane])


def test_a_policy_per_sweep_compiles_one_executable_each(
        guarded_build, fresh_programs):
    pols = ("fifo", "libasl", "shfl", "ks_jbsq")
    for rep in range(2):
        for p in pols:
            cfg = dataclasses.replace(CFG, policy=p)
            _, (rec,) = _new_records(lambda: sl.sweep(
                cfg, {"n_cores": [1, 4, 8]}, slo_us=50.0))
            leaves = (sl.build_tables(cfg), sl.build_params(cfg, 50.0))
            assert rec["arrays"] == len(jax.tree.leaves(leaves)) + 1
            assert rec["hit"] is (rep == 1)
        assert sl.n_batch_executables() == len(pols)


@pytest.mark.parametrize("windows0", [None, "numpy", "device"])
def test_run_builds_on_the_host_and_places_once(guarded_build, windows0):
    tb, pm = sl.build_tables(CFG), sl.build_params(CFG, 50.0, 3)
    assert _digest((tb, pm)) == "f12d90c4b411d7a0"
    assert all(isinstance(x, (np.ndarray, np.generic))
               for x in jax.tree.leaves((tb, pm)))
    w0 = {"numpy": sl._default_windows(CFG),
          "device": jax.device_put(sl._default_windows(CFG)),
          None: None}[windows0]
    st, (rec,) = _new_records(lambda: sl.run(CFG, 50.0, 3, w0))
    assert rec["arrays"] == len(jax.tree.leaves((tb, pm))) + 1
    if windows0 == "device":
        assert w0.is_deleted()                    # donated, as documented
    want = sl.run(CFG, 50.0, 3)
    for x, y in zip(jax.tree.leaves(want), jax.tree.leaves(st)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_run_with_traced_scalars_keeps_them_traced():
    want = sl.run(CFG, 50.0, 3)
    got = jax.jit(lambda slo, seed: sl.run(CFG, slo, seed))(
        jnp.float32(50.0), jnp.int32(3))
    for x, y in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
