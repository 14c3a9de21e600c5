"""Compile the simulator's main path for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a v5e 2x2
host and the TPU compiler builds each program for it, so what the chip's
compiler refuses (tiling, memory, unsupported kernel ops, collectives)
fails here at no chip time.  Nothing runs, so these tests say nothing
about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from benchmarks import paper_figs
from repro.core import simlock as sl
from repro.dist.hlo_analysis import collective_stats
from repro.dist.sharding import build_sweep_rules

HBM_BYTES = 16e9   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep such entries out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def _compile_sweep(cfg, axes, sharding, slo_us=1e9):
    cfg, cells, tb, pm, w0, _ = sl._sweep_inputs(cfg, axes, slo_us=slo_us)
    args = _shapes((tb, pm, w0), sharding)
    return jax.jit(sl._batched(sl._canon(cfg))).lower(*args).compile(), \
        len(cells)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def test_fig1_masked_sweep_compiles_for_one_chip(one_chip):
    """fig1's per-policy executable: the vmapped masked step over the
    eight n_cores cells, at the paper's widths."""
    cfg, slo = paper_figs.fig1_cell("libasl")
    compiled, n = _compile_sweep(cfg, {"n_cores": list(range(1, 9))},
                                 one_chip, slo)
    assert n == 8
    _fits(compiled)
    assert collective_stats(compiled.as_text())["total_count"] == 0


def test_single_run_switch_step_compiles_for_one_chip(one_chip):
    """``sl.run``'s executable: the ``lax.switch`` step, one cell."""
    cfg, slo = paper_figs.fig1_cell("libasl")
    args = _shapes((sl.build_tables(cfg), sl.build_params(cfg, slo, 0),
                    sl._default_windows(cfg)), one_chip)
    compiled = sl._run_single.lower(sl._canon(cfg), *args).compile()
    _fits(compiled)


def test_keyed_hist_sweep_compiles_for_one_chip(one_chip):
    """The key-sharded datastore with streaming histograms on: the
    widest state the step carries (per-lock queues, u32 buckets)."""
    cfg = paper_figs._cfg("ks_jbsq", 8, n_keys=1024, n_locks=4, hist=True)
    compiled, n = _compile_sweep(
        cfg, {"n_cores": list(range(1, 9)), "zipf_theta": [0.0, 0.99]},
        one_chip)
    assert n == 16
    _fits(compiled)


def test_sharded_sweep_compiles_for_four_chips(topo):
    """A 64-cell libasl sweep sharded over the host's four chips: the
    loop-termination all-reduce is the only collective."""
    mesh = Mesh(np.asarray(topo.devices), ("data",),
                axis_types=(AxisType.Auto,))
    rules = build_sweep_rules(mesh)
    cfg, slo = paper_figs.fig1_cell("libasl")
    axes = {"n_cores": list(range(1, 9)), "seed": list(range(8))}
    sharding = NamedSharding(mesh, rules.spec(("cells",), (64,)))
    compiled, n = _compile_sweep(cfg, axes, sharding, slo)
    assert n == 64 and rules.num_shards("cells") == 4
    _fits(compiled)
    ops = collective_stats(compiled.as_text())["ops"]
    assert set(ops) == {"all-reduce"}, ops


def test_pallas_step_never_interprets_on_tpu(one_chip):
    """``use_pallas`` lowered for the chip is Mosaic or an error, never
    the interpreter.  Mosaic refuses the engine's step today (argmin
    over the i32 clock, then per-core dynamic_slice gathers)."""
    cfg, slo = paper_figs.fig1_cell("libasl")
    cfg = dataclasses.replace(cfg, use_pallas=True)
    try:
        compiled, _ = _compile_sweep(cfg, {"n_cores": [4, 8]}, one_chip,
                                     slo)
    except NotImplementedError as e:
        assert "float32" in str(e) or "dynamic_slice" in str(e), e
    else:
        assert "tpu_custom_call" in compiled.as_text()
