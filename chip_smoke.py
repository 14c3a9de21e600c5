"""Bring-up check: the lock simulator's sweep path on one TPU chip.

    python chip_smoke.py             # one chip: phases A-C (and D)
    python chip_smoke.py --chips 4   # four chips: the sharded sweep only

Runs in one process, through the entry points a user calls
(``benchmarks.paper_figs``, ``simlock.sweep`` and ``simlock.run``), and
fails unless JAX's first device is a TPU: there is no CPU fallback.

* A — ``fig1_collapse`` at full length (``SIM_SCALE = 1.0``): every
  registered policy x ``n_cores`` 1..8, one executable per policy.  Per
  policy it prints the cold and hot wall time, their difference (the
  compile estimate), the events retired and events/s.  Every cell must
  retire events and reach 0.9 of its horizon, two passes must agree
  exactly, and the figure's headline must hold (FIFO throughput falls
  from 4 to 8 cores; TAS's 8-core P99 exceeds FIFO's).
* B — one ``simlock.run`` of fig1's libasl cell (the ``lax.switch``
  step), which must equal the sweep's 8-core libasl cell exactly.
* C — fig1's libasl sweep on the TPU and on the CPU backend of this
  process.  The cells draw no transcendental function (f32 add, multiply
  and compare, u32 counter-based random bits), and on TPU v5e every
  state leaf came out bit-identical to the CPU's, so the check is exact:
  every leaf, and so throughput and P99, must agree with zero
  tolerance.
* D — ``use_pallas=True`` must compile with Mosaic or raise; it is
  never interpreted on the chip.
* ``--chips 4`` — a 64-cell libasl sweep (``n_cores`` 1..8 x 8 seeds)
  sharded over a 4-chip mesh, every state leaf bit-identical to the
  same sweep unsharded on chip 0.

Every line but the last is a report; the last is one JSON object naming
the device.  Any failure raises and exits nonzero.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import paper_figs  # noqa: E402
from benchmarks._jax_cache import enable_persistent_cache  # noqa: E402
from repro.core import simlock as sl  # noqa: E402
from repro.launch.mesh import make_sweep_mesh  # noqa: E402

PLATFORM = "tpu"
SIM_SCALE = 1.0
HORIZON_SHARE = 0.9
MOSAIC_REFUSALS = ("Only float32 is supported", "dynamic_slice")


def log(msg):
    print(msg, flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _leaves(st):
    """(name, array) for every SimState leaf, policy slots included."""
    out = []
    for name, x in st._asdict().items():
        if isinstance(x, dict):
            out += [(f"pol.{k}", np.asarray(v)) for k, v in sorted(x.items())]
        else:
            out.append((name, np.asarray(x)))
    return out


def phase_fig1():
    """A: the paper's Figure 1 at full length; returns its rows."""
    paper_figs.SIM_SCALE = SIM_SCALE
    cold_rows, total = [], {"cold": 0.0, "hot": 0.0, "events": 0}
    for pol, cfg, slo in paper_figs.fig1_policies():
        rows, cold = _timed(lambda: paper_figs.fig1_rows(pol, cfg, slo))
        again, hot = _timed(lambda: paper_figs.fig1_rows(pol, cfg, slo))
        events = sum(r["summary"]["events"] for r in rows)
        log(f"A fig1 {pol:10s} cells={len(rows)} cold_s={cold:.3f} "
            f"hot_s={hot:.3f} compile_s_est={cold - hot:.3f} "
            f"events={events} events_per_s={events / hot:.0f}")
        for r in rows:
            s = r["summary"]
            assert s["events"] > 0, (r["name"], s["events"])
            assert s["sim_time_us"] >= HORIZON_SHARE * cfg.sim_time_us, \
                (r["name"], s["sim_time_us"], cfg.sim_time_us)
        assert _same(rows, again), f"{pol}: two passes differ"
        cold_rows += rows
        total["cold"] += cold
        total["hot"] += hot
        total["events"] += events
    rows, wall = _timed(paper_figs.fig1_collapse)
    assert _same(rows, cold_rows), "fig1_collapse differs from its parts"
    h = paper_figs.fig1_headline(rows)
    log(f"A fig1 total cells={len(rows)} cold_s={total['cold']:.3f} "
        f"hot_s={total['hot']:.3f} "
        f"compile_s_est={total['cold'] - total['hot']:.3f} "
        f"events={total['events']} "
        f"events_per_s={total['events'] / total['hot']:.0f} "
        f"fig1_collapse_s={wall:.3f}")
    log(f"A fig1 headline mcs_drop={h['mcs_drop']:.4f} "
        f"tas_p99_vs_mcs={h['tas_p99_vs_mcs']:.4f}")
    assert h["mcs_drop"] > 0, h
    assert h["tas_p99_vs_mcs"] > 1, h
    return rows


def _same(a, b):
    return json.dumps(a, sort_keys=True, default=str) == \
        json.dumps(b, sort_keys=True, default=str)


def phase_single(fig1_rows):
    """B: ``simlock.run`` of fig1's libasl cell (the switch step)."""
    cfg, slo = paper_figs.fig1_cell("libasl")
    st, cold = _timed(lambda: jax.block_until_ready(sl.run(cfg, slo)))
    st, hot = _timed(lambda: jax.block_until_ready(sl.run(cfg, slo)))
    s = sl.summarize(cfg, st, slo_us=slo)
    log(f"B run libasl n8 cold_s={cold:.3f} hot_s={hot:.3f} "
        f"compile_s_est={cold - hot:.3f} events={s['events']} "
        f"events_per_s={s['events'] / hot:.0f}")
    cell = next(r for r in fig1_rows if r["name"] == "fig1/libasl/n8")
    ref = {k: v for k, v in cell["summary"].items() if k in s}
    assert _same(s, ref), "single run differs from the sweep's n8 cell"
    log("B run == sweep cell fig1/libasl/n8: identical summary")


def phase_cpu_vs_tpu():
    """C: fig1's libasl sweep on the TPU and on this host's CPU."""
    cfg, slo = paper_figs.fig1_cell("libasl")
    axes = {"n_cores": list(range(1, 9))}
    tpu, _ = sl.sweep(cfg, axes, slo_us=slo)
    cpu_dev = jax.devices("cpu")[0]
    with jax.default_device(cpu_dev):
        (cpu, grid), wall = _timed(lambda: sl.sweep(cfg, axes, slo_us=slo))
    assert cpu.events.devices() == {cpu_dev}
    assert tpu.events.devices() == {jax.devices()[0]}
    diff = [name for (name, a), (_, b) in zip(_leaves(tpu), _leaves(cpu))
            if not np.array_equal(a, b)]
    sum_t = sl.sweep_summaries(cfg, tpu, grid, slo_us=slo)
    sum_c = sl.sweep_summaries(cfg, cpu, grid, slo_us=slo)
    worst = {}
    for s_t, s_c in zip(sum_t, sum_c):
        for k in ("throughput_cs_per_s", "cs_p99_all_us", "ep_p99_all_us"):
            rel = abs(s_t[k] - s_c[k]) / max(abs(s_c[k]), 1e-30)
            worst[k] = max(worst.get(k, 0.0), rel)
    log(f"C cpu sweep libasl cells=8 wall_s={wall:.3f}")
    log("C state leaves: " + ("all bit-identical" if not diff else
                              "differ in " + ",".join(diff)))
    log("C summary max rel diff: " + " ".join(
        f"{k}={v:.3e}" for k, v in worst.items()))
    assert not diff and _same(sum_t, sum_c), (diff, worst)


def phase_pallas():
    """D: the Pallas step on the chip is Mosaic or an error, never the
    interpreter; where it compiles it must equal the jnp step exactly."""
    cfg, slo = paper_figs.fig1_cell("libasl")
    cfg = dataclasses.replace(cfg, sim_time_us=1_000.0)
    axes = {"n_cores": [4, 8]}
    try:
        got, _ = sl.sweep(dataclasses.replace(cfg, use_pallas=True), axes,
                          slo_us=slo)
    except NotImplementedError as e:
        # The refusals Mosaic gives for the engine's step (ROADMAP A2);
        # any other error fails the phase.
        if not any(m in str(e) for m in MOSAIC_REFUSALS):
            raise
        log(f"D use_pallas refused by Mosaic: {str(e).splitlines()[0]}")
        return
    ref, _ = sl.sweep(cfg, axes, slo_us=slo)
    diff = [name for (name, a), (_, b) in zip(_leaves(ref), _leaves(got))
            if not np.array_equal(a, b)]
    assert not diff, f"use_pallas differs from the jnp step in {diff}"
    log("D use_pallas compiled; bit-identical to the jnp step")


def phase_sharded():
    """--chips 4: the 64-cell sweep sharded over four chips against the
    same sweep on chip 0."""
    assert len(jax.devices()) == 4, jax.devices()
    cfg, slo = paper_figs.fig1_cell("libasl")
    axes = {"n_cores": list(range(1, 9)), "seed": list(range(8))}
    ref, cold1 = _timed(lambda: jax.block_until_ready(
        sl.sweep(cfg, axes, slo_us=slo)[0]))
    _, hot1 = _timed(lambda: jax.block_until_ready(
        sl.sweep(cfg, axes, slo_us=slo)[0]))
    mesh = make_sweep_mesh(4)
    got, cold4 = _timed(lambda: jax.block_until_ready(
        sl.sweep(cfg, axes, slo_us=slo, mesh=mesh)[0]))
    _, hot4 = _timed(lambda: jax.block_until_ready(
        sl.sweep(cfg, axes, slo_us=slo, mesh=mesh)[0]))
    rec = sl.sweep_log()[-1]
    assert rec["devices"] == 4, rec["devices"]
    events = int(np.sum(np.asarray(ref.events)))
    log(f"4 unsharded cells=64 cold_s={cold1:.3f} hot_s={hot1:.3f} "
        f"events={events} events_per_s={events / hot1:.0f}")
    log(f"4 sharded   cells=64 cold_s={cold4:.3f} hot_s={hot4:.3f} "
        f"events_per_s={events / hot4:.0f} devices={rec['devices']} "
        f"collectives={rec['collectives']['total_count']} "
        f"kinds={sorted(rec['collectives']['ops'])}")
    diff = [name for (name, a), (_, b) in zip(_leaves(ref), _leaves(got))
            if not np.array_equal(a, b)]
    assert not diff, f"sharded sweep differs in {diff}"
    log("4 sharded == unsharded: every state leaf bit-identical")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip sweep")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != PLATFORM:
        sys.exit(f"chip_smoke: JAX found no {PLATFORM} "
                 f"(first device: {dev.platform}); nothing was run")

    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__} "
        f"cache={enable_persistent_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded()
    else:
        rows = phase_fig1()
        phase_single(rows)
        phase_cpu_vs_tpu()
        phase_pallas()
    log(f"total_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
