"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall
microseconds per produced row; derived = the figure's headline metric) and
writes full JSON to artifacts/bench/results.json.

Sections:
  sim            — CI smoke gate: fig1's batched-vs-seed acceptance bench
                   (speedup floor, <= 1 executable per registered policy),
                   a policy-matrix probe (every registered lock policy
                   runs one tiny cell), energy-layer probes (zero-power
                   purity, energy == integral-of-power conservation, the
                   energy_efficiency figure's one-executable-per-policy
                   discipline), a keyshard probe (EREW beats the CRCW
                   baseline under hot-key Zipf traffic, executable
                   ceiling kept), a merged-executable probe (a
                   fig1-shaped policy x n_cores grid compiles <= 2
                   executables via cfg.policy_set), an open-loop
                   events/s floor on the recorded BENCH_simlock.json
                   + a sharded-vs-unsharded sweep parity
                   probe; nonzero exit on failure.
                   Opt-in (not part of the default all-sections run):
                   under JAX_PLATFORMS=cpu it virtualizes 8 host devices
                   and pins XLA threading, which would skew the other
                   sections' baselines
  paper figures  — discrete-event AMP simulator (benchmarks/paper_figs.py)
  serving/fleet  — engine + dispatch + straggler sims (serving_bench.py);
                   also a CI gate: ASL must hold its TTFT P99 within
                   1.5x its SLO and FIFO must not beat ASL on token
                   throughput — nonzero exit on a break
  kernels        — per-kernel check vs jnp reference (interpret mode on
                   the CPU only)
  roofline       — reads artifacts/roofline/*.json (produced by
                   ``python -m benchmarks.roofline``; compile-heavy)
  chaos          — CI gate for the fault-injection layer
                   (docs/faults.md): every registered policy stays live
                   under combined faults, zero-rate injection is
                   bit-identical to fault-free, and LibASL's goodput
                   under maximum preemption stays >= FIFO's.  Opt-in
                   (re-runs the chaos_collapse figure)

The smoke gates are ``--section sim --quick``,
``--section serving --quick`` and ``--section chaos --quick``.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from benchmarks._jax_cache import enable_persistent_cache

ART = Path(__file__).resolve().parents[1] / "artifacts" / "bench"


def _emit(name, us_per_call, derived):
    print(f"{name},{us_per_call:.3f},{derived}")


def _run_section(section: str, fns: dict, results: dict):
    for name, fn in fns.items():
        t0 = time.time()
        rows = fn()
        dt_us = (time.time() - t0) * 1e6
        results[f"{section}/{name}"] = rows
        derived = _headline(name, rows)
        _emit(f"{section}/{name}", dt_us / max(len(rows), 1), derived)


def _headline(name, rows) -> str:
    try:
        if name.startswith("fig1"):
            from benchmarks.paper_figs import fig1_headline
            h = fig1_headline(rows)
            return (f"mcs_drop={h['mcs_drop']:.0%};"
                    f"tas_p99_vs_mcs={h['tas_p99_vs_mcs']:.1f}x")
        if name.startswith("fig4"):
            f8 = next(r for r in rows if r["policy"] == "fifo"
                      and r["n_threads"] == 8)
            t8 = next(r for r in rows if r["policy"] == "tas"
                      and r["n_threads"] == 8)
            return (f"tas_tput_vs_mcs={t8['tput'] / f8['tput']:.2f}x;"
                    f"tas_p99_vs_mcs="
                    f"{t8['ep_p99_little'] / f8['ep_p99_little']:.1f}x")
        if name.startswith("fig5"):
            return ";".join(f"p{r['proportion']}:{r['tput']:.0f}/"
                            f"{r['ep_p99_little']:.0f}us" for r in rows)
        if name == "bench1_contended":
            mcs = next(r for r in rows if r["name"].endswith("mcs"))
            mx = next(r for r in rows if r["name"].endswith("MAX"))
            return f"libaslMAX_vs_mcs={mx['tput'] / mcs['tput']:.2f}x"
        if name == "bench1_slo_sweep":
            track = [abs(r["ep_p99_little"] - r["slo_us"]) / r["slo_us"]
                     for r in rows if 40 <= r["slo_us"] <= 300]
            return f"slo_tracking_err_med={np.median(track):.1%}"
        if name == "bench2_variable":
            ach = max(r["violation_excess"] for r in rows if r["achievable"])
            fell_back = rows[-1]["mean_window_us"] < 5.0
            return (f"achievable_excess={ach:.1%};"
                    f"impossible_phase_fell_back_to_fifo={fell_back}")
        if name == "bench3_mixed":
            return ";".join(f"{r['short_pct']}%:{r['tput_vs_mcs']:.2f}x"
                            for r in rows)
        if name == "bench4_scalability":
            mx = next(r for r in rows if "MAX" in r["name"]
                      and r["n_threads"] == 8)
            f4 = next(r for r in rows if r["policy"] == "fifo"
                      and r["n_threads"] == 4)
            return f"libaslMAX8_vs_mcs4={mx['tput'] / f4['tput']:.2f}x"
        if name == "bench5_contention":
            lo = rows[-1]
            hi = rows[0]
            return (f"low_contention_vs_mcs4={lo['speedup_vs_mcs4']:.2f}x;"
                    f"high_vs_mcs8={hi['speedup_vs_mcs8']:.2f}x")
        if name == "bench6_blocking":
            by = {(r["name"].split("/")[1], r["wakeup_us"]): r
                  for r in rows}
            mcs_deg = by[("mcs-park", 0.0)]["tput"] / \
                by[("mcs-park", 20.0)]["tput"]
            asl_deg = by[("libasl-block", 0.0)]["tput"] / \
                by[("libasl-block", 20.0)]["tput"]
            rel = by[("libasl-block", 20.0)]["tput"] / \
                by[("mcs-park", 20.0)]["tput"]
            return (f"wakeup20us:mcs_degrades={mcs_deg:.2f}x,"
                    f"libasl_degrades={asl_deg:.2f}x,"
                    f"libasl_vs_mcs={rel:.2f}x")
        if name == "db_serving":
            by = {r["name"].split("/")[-1]: r for r in rows}
            return (f"asl_ttft_p99={by['asl']['ttft_p99'] * 1e3:.0f}ms(viol"
                    f"={by['asl']['slo_violation_rate']:.0%});"
                    f"fifo_itl_p99={by['fifo']['itl_p99'] * 1e3:.0f}ms;"
                    f"asl_itl_p99={by['asl']['itl_p99'] * 1e3:.0f}ms")
        if name == "dispatch_fleet":
            fr = sorted({r["load_frac"] for r in rows})
            g = {r["name"].split("/")[1]: r for r in rows
                 if r["load_frac"] == fr[0]}
            h = {r["name"].split("/")[1]: r for r in rows
                 if r["load_frac"] == fr[-1]}
            return (f"low:asl_p99={g['asl']['p99'] * 1e3:.0f}ms_vs_fair="
                    f"{g['fair']['p99'] * 1e3:.0f}ms;"
                    f"high:asl_rps={h['asl']['throughput_rps']:.0f}_vs_"
                    f"fastonly={h['fast-only']['throughput_rps']:.0f}")
        if name == "db_multiclass":
            asl = next(r for r in rows if r["name"].endswith("asl"))
            return (f"asl:lc_p99={asl['latency-critical/ttft_p99']:.2f}s,"
                    f"be_p99={asl['best-effort/ttft_p99']:.2f}s")
        if name == "loadlat_sweep":
            hi = max(r["load_frac"] for r in rows)
            h = {r["policy"]: r for r in rows if r["load_frac"] == hi}
            return (f"load{hi:.0%}:libasl_tput_vs_mcs="
                    f"{h['libasl']['tput'] / h['fifo']['tput']:.2f}x;"
                    f"libasl_p99={h['libasl']['ep_p99_little']:.0f}us"
                    f"_vs_mcs={h['fifo']['ep_p99_little']:.0f}us")
        if name == "openloop_loadlat":
            hi = max(r["load_frac"] for r in rows)
            lo = min(r["load_frac"] for r in rows)
            g = {r["policy"]: r for r in rows if r["load_frac"] == lo}
            h = {r["policy"]: r for r in rows if r["load_frac"] == hi}
            knee = h["fifo"]["ep_p99_all"] / max(g["fifo"]["ep_p99_all"],
                                                 1e-9)
            return (f"openloop_knee_fifo={knee:.0f}x_p99;"
                    f"sat:shfl_tput_vs_fifo="
                    f"{h['shfl']['tput'] / h['fifo']['tput']:.2f}x;"
                    f"libasl_little_p99={h['libasl']['ep_p99_little']:.0f}us")
        if name == "chaos_collapse":
            mx = max(r["preempt_rate"] for r in rows)
            h = {r["policy"]: r for r in rows if r["preempt_rate"] == mx}
            z = {r["policy"]: r for r in rows if r["preempt_rate"] == 0.0}
            return (f"pr{mx:g}:fifo_drop="
                    f"{1 - h['fifo']['tput'] / z['fifo']['tput']:.0%};"
                    f"libasl_goodput_vs_fifo="
                    f"{h['libasl']['goodput_eps'] / h['fifo']['goodput_eps']:.2f}x")
        if name == "energy_efficiency":
            full = {r["policy"]: r for r in rows if r["n_big"] == 8}
            lit = {r["policy"]: r for r in rows if r["n_big"] == 0}
            best = max(rows, key=lambda r: r["tput_per_watt"])
            return (f"little_power_vs_big="
                    f"{lit['fifo']['power_w'] / full['fifo']['power_w']:.2f}x;"
                    f"little_tput_vs_big="
                    f"{lit['fifo']['tput'] / full['fifo']['tput']:.2f}x;"
                    f"best_tputW={best['name']}"
                    f"@{best['tput_per_watt']:.0f}")
        if name == "keyshard":
            hot = {r["label"]: r for r in rows
                   if r["n_locks"] == 1 and r["zipf_theta"] == 0.99}
            th = {r["zipf_theta"]: r for r in rows
                  if r["label"] == "crcw" and r["n_locks"] == 16}
            return (f"hot1lock:erew_vs_crcw="
                    f"{hot['erew']['tput'] / hot['crcw']['tput']:.2f}x,"
                    f"jbsq_vs_crcw="
                    f"{hot['jbsq']['tput'] / hot['crcw']['tput']:.2f}x;"
                    f"crcw_th1.2_vs_uniform="
                    f"{th[1.2]['tput'] / th[0.0]['tput']:.2f}x")
        if name == "excess_tail":
            hi = max(r["load_frac"] for r in rows)
            h = {r["policy"]: r for r in rows if r["load_frac"] == hi}
            return (f"sat:fifo_excess_p999={h['fifo']['excess_p999']:.1f}x"
                    f"_vs_libasl={h['libasl']['excess_p999']:.1f}x;"
                    f"bound={h['fifo']['hist_rel_err_bound']:.1%}")
        if name == "straggler_training":
            by = {r["name"].split("/")[-1]: r for r in rows}
            return (f"asl_vs_sync={by['asl-staleness']['steps_per_s'] / by['sync']['steps_per_s']:.2f}x;"
                    f"p99_staleness={by['asl-staleness']['p99_staleness']:.0f}")
    except Exception as e:  # headline must never kill the run
        return f"(headline error: {e})"
    return ""


def _kernel_bench(results):
    """Kernel check + timing vs jnp reference: interpreted on the CPU,
    compiled by Mosaic anywhere else."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, h, kh, s, dh = 1, 4, 2, 512, 64
    q = jax.random.normal(ks[0], (b, h, s, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, kh, s, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, kh, s, dh), jnp.float32)
    interpret = jax.default_backend() == "cpu"
    t0 = time.time()
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=interpret)
    jax.block_until_ready(out)
    dt = (time.time() - t0) * 1e6
    err = float(jnp.max(jnp.abs(
        out - ref.flash_attention_ref(q, k, v, causal=True))))
    results["kernels/flash_attention"] = {"err": err, "us": dt}
    _emit(f"kernels/flash_attention_{'interp' if interpret else 'mosaic'}",
          dt, f"max_err={err:.1e}")


def _policy_matrix_probe(results) -> bool:
    """Every registered lock policy runs one tiny sweep cell — a cheap
    canary that a policy (or the registry wiring) broke, and that the
    one-executable-per-policy discipline holds: the probe may compile at
    most one new batched executable per registered policy."""
    import numpy as np

    from repro.core import simlock as sl
    from repro.core.policies import REGISTRY

    n0 = sl.n_batch_executables()
    probe, ok = {}, True
    for name in REGISTRY:
        try:
            cfg = sl.SimConfig(policy=name, sim_time_us=1_000.0)
            st, _ = sl.sweep(cfg, {"seed": [0, 1]}, slo_us=60.0)
            events = int(np.sum(np.asarray(st.events)))
            alive = events > 0
            probe[name] = {"events": events, "ok": bool(alive)}
            ok = ok and alive
        except Exception as e:                      # noqa: BLE001
            probe[name] = {"error": repr(e), "ok": False}
            ok = False
    new_execs = sl.n_batch_executables() - n0
    if new_execs > len(REGISTRY):
        ok = False
    results["sim/policy_matrix"] = {
        "policies": sorted(REGISTRY), "probe": probe,
        "new_executables": new_execs, "registry_size": len(REGISTRY),
        "pass": bool(ok)}
    bad = [n for n, p in probe.items() if not p["ok"]]
    _emit("sim/policy_matrix", 0.0,
          f"policies={len(REGISTRY)};execs={new_execs}"
          f"(<= {len(REGISTRY)});"
          + (f"broken={','.join(bad)};" if bad else "")
          + ("PASS" if ok else "FAIL"))
    return ok


def _energy_probe(results) -> bool:
    """CI probes for the energy/DVFS layer (docs/energy.md):

    1. purity — for every registered policy, a zero-power default-DVFS
       run is bit-identical to a gate-off run on every SimState leaf
       (the layer off is provably a no-op);
    2. conservation — uniform 1 W in every phase integrates to
       n_cores x sim-seconds (energy == integral of power dt, the
       telescoping event-step sum);
    3. batching + asymmetry — the energy_efficiency figure compiles at
       most one executable per registered policy, and the all-little
       mix draws less power AND less throughput than the all-big mix.
    """
    import jax
    import numpy as np

    from benchmarks import paper_figs
    from repro.core import simlock as sl
    from repro.core.policies import REGISTRY

    horizon = 4_000.0
    pure_ok = True
    for name in sorted(REGISTRY):
        base = sl.SimConfig(policy=name, sim_time_us=horizon)
        zero = sl.with_columns(base, dvfs=(1.0,) * 8,
                               p_cs=(0.0,) * 8, p_spin=(0.0,) * 8,
                               p_park=(0.0,) * 8, p_idle=(0.0,) * 8)
        a, b = sl.run(base, 60.0), sl.run(zero, 60.0)
        pure_ok = pure_ok and all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    _emit("sim/energy_purity", 0.0,
          f"policies={len(REGISTRY)};zero_power_bit_identical={pure_ok};"
          + ("PASS" if pure_ok else "FAIL"))

    cfg = sl.with_columns(
        sl.SimConfig(policy="fifo", sim_time_us=horizon),
        p_cs=(1.0,) * 8, p_spin=(1.0,) * 8, p_park=(1.0,) * 8,
        p_idle=(1.0,) * 8)
    s = sl.summarize(cfg, jax.tree.map(np.asarray, sl.run(cfg, 1e9)))
    want = cfg.n_cores * cfg.sim_time_us * 1e-6
    cons_ok = abs(s["energy_j"] - want) <= 0.02 * want
    _emit("sim/energy_conservation", 0.0,
          f"energy_j={s['energy_j']:.4f}_vs_integral={want:.4f};"
          + ("PASS" if cons_ok else "FAIL"))

    n0 = sl.n_batch_executables()
    rows = paper_figs.energy_efficiency()
    execs = sl.n_batch_executables() - n0
    results["sim/energy_efficiency"] = rows
    batch_ok = execs <= len(REGISTRY)
    lit = {r["policy"]: r for r in rows if r["n_big"] == 0}
    full = {r["policy"]: r for r in rows if r["n_big"] == 8}
    amp_ok = all(lit[p]["power_w"] < full[p]["power_w"]
                 and lit[p]["tput"] < full[p]["tput"] for p in lit)
    _emit("sim/energy_efficiency", 0.0,
          f"execs={execs}(<= {len(REGISTRY)});"
          f"littles_less_power_and_tput={amp_ok};"
          + ("PASS" if batch_ok and amp_ok else "FAIL"))

    ok = bool(pure_ok and cons_ok and batch_ok and amp_ok)
    results["sim/energy_gate"] = {
        "zero_power_bit_identical": bool(pure_ok),
        "conservation_energy_j": float(s["energy_j"]),
        "conservation_want_j": float(want),
        "figure_executables": int(execs),
        "registry_size": len(REGISTRY),
        "littles_less_power_and_tput": bool(amp_ok),
        "pass": ok}
    return ok


def _keyshard_probe(results) -> bool:
    """CI probe for the key-sharded datastore axis (docs/workloads.md
    §Key-sharded traffic): under hot-key traffic (Zipf theta 1.2 over 4
    bucket locks) the EREW owner-affinity policy must out-throughput the
    CRCW baseline (plain fifo under the keyed config) — big cores retire
    critical sections 3.75x faster, so pinning hot buckets to big-core
    owners wins robustly (the comparison is bit-deterministic at a fixed
    seed).  The probe may compile at most one new batched executable per
    probed policy (the keyshard figure's own discipline)."""
    from repro.core import simlock as sl

    kw = dict(sim_time_us=4_000.0, n_locks=4, n_keys=1024,
              zipf_theta=1.2)
    n0 = sl.n_batch_executables()
    tput = {}
    for name in ("fifo", "ks_erew"):
        cfg = sl.SimConfig(policy=name, **kw)
        st, grid = sl.sweep(cfg, {"seed": [3]}, slo_us=60.0)
        s = sl.sweep_summaries(cfg, st, grid)[0]
        tput[name] = float(s["throughput_epochs_per_s"])
    execs = sl.n_batch_executables() - n0
    order_ok = tput["ks_erew"] > tput["fifo"]
    exec_ok = execs <= 2
    ok = bool(order_ok and exec_ok)
    results["sim/keyshard"] = {
        "tput_eps": tput, "new_executables": int(execs),
        "hot_key_order_ok": bool(order_ok), "pass": ok}
    _emit("sim/keyshard", 0.0,
          f"hotkey:erew={tput['ks_erew']:.0f}_vs_crcw={tput['fifo']:.0f};"
          f"execs={execs}(<=2);" + ("PASS" if ok else "FAIL"))
    return ok


def _hist_tail_probe(results) -> bool:
    """CI probe for the constant-memory streaming-histogram tail
    metrics (docs/simulator.md §Streaming metrics).  On a tiny
    un-wrapped grid:

    * the histogram P99 must land within the documented one-bucket
      relative-error bound of the exact ring-buffer percentile;
    * the hist-on sweep may compile at most ONE new executable;
    * gate-off purity — every state leaf the two runs share must be
      bitwise identical (the static gate adds the histogram leaves, it
      never perturbs the event trajectory)."""
    import dataclasses

    import numpy as np

    from repro.core import simlock as sl

    cfg_off = sl.SimConfig(policy="libasl", sim_time_us=3_000.0)
    cfg_on = dataclasses.replace(cfg_off, hist=True)
    st_off, _ = sl.sweep(cfg_off, {"seed": [3]}, slo_us=60.0)
    n0 = sl.n_batch_executables()
    st_on, grid = sl.sweep(cfg_on, {"seed": [3]}, slo_us=60.0)
    execs = sl.n_batch_executables() - n0

    import jax

    def _eq(a, b):
        xs, ys = jax.tree.leaves(a), jax.tree.leaves(b)
        return len(xs) == len(ys) and all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(xs, ys))

    pure = all(
        _eq(getattr(st_on, f), getattr(st_off, f))
        for f in st_on._fields if f not in ("ep_hist", "cs_hist"))
    s = sl.sweep_summaries(cfg_on, st_on, grid, slo_us=60.0)[0]
    exact = s["ep_p99_all_us"]          # un-wrapped: the ring is exact
    est = s["ep_p99_hist_all_us"]
    bound = s["hist_rel_err_bound"]
    err = abs(est - exact) / max(exact, 1e-9)
    wrapped = bool(s.get("tail_truncated", False))
    ok = bool(err <= bound and pure and execs <= 1 and not wrapped)
    results["sim/hist_tail"] = {
        "p99_exact_us": exact, "p99_hist_us": est,
        "rel_err": err, "bound": bound, "gate_off_pure": bool(pure),
        "new_executables": int(execs), "wrapped": wrapped, "pass": ok}
    _emit("sim/hist_tail", 0.0,
          f"p99:hist={est:.1f}us_vs_exact={exact:.1f}us"
          f"(err={err:.2%}<={bound:.2%});pure={pure};"
          f"execs={execs}(<=1);" + ("PASS" if ok else "FAIL"))
    return ok


# Device events/s floors for the two open-loop figures: >= ~5x the
# pre-merge BENCH_simlock.json entries (openloop_loadlat 17609 ev/s,
# loadlat_sweep 19057 ev/s — the per-policy executables before the
# fused multi-policy sweep).  The gate reads the checked-in protocol
# file, so the speedup cannot regress silently between recordings.
OPENLOOP_EVS_FLOOR = 88_000
LOADLAT_EVS_FLOOR = 95_000


def _merged_exec_probe(results) -> bool:
    """The merged multi-policy executable discipline: a fig1-shaped grid
    (every registered policy x n_cores 1..8) swept with the full
    registry as ``policy_set`` must compile at most 2 executables —
    down from the per-policy path's <= n_policies — and every cell must
    retire events (a policy whose handlers are not switch-merge-safe
    would go silent or corrupt its neighbours)."""
    import numpy as np

    from repro.core import simlock as sl
    from repro.core.policies import REGISTRY

    names = tuple(REGISTRY)
    cfg = sl.SimConfig(policy=names[0], policy_set=names,
                       sim_time_us=1_500.0)
    axes = {"policy": [], "n_cores": []}
    for name in names:
        for n in range(1, 9):
            axes["policy"].append(name)
            axes["n_cores"].append(n)
    n0 = sl.n_batch_executables()
    t0 = time.time()
    st, _ = sl.sweep(cfg, axes, slo_us=60.0, product=False)
    ev = np.asarray(st.events)
    wall = time.time() - t0
    execs = sl.n_batch_executables() - n0
    alive = bool((ev > 0).all())
    ok = bool(execs <= 2 and alive)
    results["sim/merged_executable"] = {
        "cells": int(ev.size), "policies": len(names),
        "new_executables": int(execs), "all_cells_alive": alive,
        "wall_s": round(wall, 2), "pass": ok}
    _emit("sim/merged_executable", wall * 1e6 / ev.size,
          f"cells={ev.size};policies={len(names)};execs={execs}(<=2);"
          f"all_alive={alive};" + ("PASS" if ok else "FAIL"))
    return ok


def _openloop_floor_gate(results) -> bool:
    """The recorded open-loop device throughput cannot silently regress:
    BENCH_simlock.json (the checked-in simperf protocol) must show the
    merged open-loop figures at/above the floors derived from the
    pre-merge before/after, with fewer compilations than policies."""
    bench = ART.parents[1] / "BENCH_simlock.json"
    if not bench.exists():
        results["sim/openloop_floor"] = {"pass": False,
                                         "error": "no BENCH_simlock.json"}
        _emit("sim/openloop_floor", 0.0, "no BENCH_simlock.json;FAIL")
        return False
    figs = json.loads(bench.read_text()).get("figures", {})
    checks = {}
    ok = True
    for name, floor, n_pol in (("openloop_loadlat", OPENLOOP_EVS_FLOOR, 3),
                               ("loadlat_sweep", LOADLAT_EVS_FLOOR, 4)):
        d = figs.get(name, {})
        evs = d.get("events_per_s") or 0
        merged = d.get("compilations", n_pol) < n_pol
        checks[name] = {"events_per_s": evs, "floor": floor,
                        "compilations": d.get("compilations"),
                        "policies": n_pol, "merged": merged}
        ok = ok and evs >= floor and merged
    results["sim/openloop_floor"] = {"checks": checks, "pass": bool(ok)}
    _emit("sim/openloop_floor", 0.0,
          ";".join(f"{n}={c['events_per_s']}ev/s(>={c['floor']}),"
                   f"compiles={c['compilations']}(<{c['policies']})"
                   for n, c in checks.items())
          + (";PASS" if ok else ";FAIL"))
    return bool(ok)


def _sim_section(results, quick: bool) -> bool:
    """CI smoke gate for the simulator engine.  Runs the fig1 batched-vs-
    seed acceptance bench (the BENCH_simlock.json protocol, abridged) and
    a sharded-vs-unsharded parity probe; returns False on a gate break."""
    import jax
    import numpy as np

    from benchmarks import simperf
    from repro.core import simlock as sl

    rec = simperf.bench_fig1_batched_vs_seed(quick)
    results["sim/fig1_sweep"] = rec
    # --quick horizons are compile-dominated, so the wall ratio reads low
    # on a cold compile cache; the full >= 3 acceptance number is owned by
    # simperf's full-length run (BENCH_simlock.json).  The smoke
    # floor still catches a de-batched engine (48 compiles ~ speedup < 1).
    floor = 1.5 if quick else 3.0
    gate = (rec["speedup_vs_seed_path"] >= floor
            and rec["batched_compilations"] <= rec["policies"])
    _emit("sim/fig1_sweep", rec["batched_wall_s"] * 1e6 / rec["cells"],
          f"speedup_vs_seed={rec['speedup_vs_seed_path']}x;"
          f"compiles={rec['batched_compilations']}"
          f"(<= {rec['policies']} policies);"
          f"coll={rec['hlo']['collective_count']};"
          f"{'PASS' if gate else 'FAIL'}")

    gate = _policy_matrix_probe(results) and gate
    gate = _energy_probe(results) and gate
    gate = _keyshard_probe(results) and gate
    gate = _merged_exec_probe(results) and gate
    gate = _hist_tail_probe(results) and gate
    gate = _openloop_floor_gate(results) and gate

    if len(jax.devices()) < 2:
        # The sharded half of the gate cannot run — that is itself a gate
        # break (a one-chip host, jax imported before the 8-device CPU
        # virtualization, or a caller-pinned single device): report it,
        # don't skip it.
        results["sim/sharded_parity"] = {"devices": 1,
                                         "bit_identical": None}
        _emit("sim/sharded_parity", 0.0,
              "single device: sharded probe could not run;FAIL")
        return False
    from repro.launch.mesh import make_sweep_mesh
    cfg = sl.SimConfig(policy="libasl", sim_time_us=4_000.0)
    axes = {"slo_us": [30.0, 70.0], "seed": [0, 1, 2]}
    a, _ = sl.sweep(cfg, axes)
    b, _ = sl.sweep(cfg, axes, mesh=make_sweep_mesh())
    parity = all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    shard_rec = sl.sweep_log()[-1]
    results["sim/sharded_parity"] = {
        "devices": shard_rec["devices"], "bit_identical": parity,
        "collective_count": shard_rec["collectives"]["total_count"]}
    _emit("sim/sharded_parity", 0.0,
          f"devices={shard_rec['devices']};"
          f"bit_identical={parity};"
          f"coll={shard_rec['collectives']['total_count']}")
    return gate and parity


def _serving_section(results, quick: bool) -> bool:
    """CI gate for the serving stack (mirrors ``--section sim``): runs
    every serving bench, then gates on the db_serving rows — ASL must
    keep its TTFT P99 within ``SERVING_P99_FLOOR`` x its SLO, and FIFO
    must not beat ASL on token throughput.  Returns False on a break."""
    from benchmarks import serving_bench
    if quick:
        serving_bench.SCALE = 0.25
    _run_section("serving", serving_bench.ALL, results)
    by = {r["name"].split("/")[-1]: r
          for r in results["serving/db_serving"]}
    asl, fifo = by["asl"], by["fifo"]
    slo = asl["slo_ttft"]
    p99_ok = asl["ttft_p99"] <= SERVING_P99_FLOOR * slo
    tput_ok = asl["throughput_tok_s"] >= 0.95 * fifo["throughput_tok_s"]
    gate = bool(p99_ok and tput_ok)
    results["serving/gate"] = {
        "asl_ttft_p99": asl["ttft_p99"], "slo_ttft": slo,
        "p99_floor": SERVING_P99_FLOOR,
        "asl_tok_s": asl["throughput_tok_s"],
        "fifo_tok_s": fifo["throughput_tok_s"],
        "p99_ok": bool(p99_ok), "tput_ok": bool(tput_ok), "pass": gate}
    _emit("serving/gate", 0.0,
          f"asl_p99={asl['ttft_p99']:.2f}s(slo={slo:g}s,"
          f"floor={SERVING_P99_FLOOR:g}x);"
          f"asl_tok_s={asl['throughput_tok_s']:.0f}_vs_"
          f"fifo={fifo['throughput_tok_s']:.0f};"
          f"{'PASS' if gate else 'FAIL'}")
    return gate


SERVING_P99_FLOOR = 1.5


# Combined-fault probe load for --section chaos (docs/faults.md): lock-
# holder preemption + core churn + straggler spikes, all at once.
CHAOS_PROBE_KW = dict(preempt_rate=0.1, preempt_scale_us=30.0,
                      churn_rate=0.2, churn_period_us=200.0,
                      straggle_rate=0.05, straggle_scale=10.0)


def _chaos_section(results, quick: bool) -> bool:
    """CI gate for the fault-injection layer (docs/faults.md):

    1. liveness — every registered policy survives combined faults
       (preemption + churn + stragglers): every core keeps completing
       epochs, the sim reaches its horizon, the event budget holds;
    2. purity — a zero-rate cell of a gate-on faulted sweep is
       bit-identical to a plain fault-free run (fault injection off is
       provably a no-op);
    3. grace — the chaos_collapse figure's headline claim: LibASL's
       goodput under maximum preemption stays >= FIFO's.
    """
    import jax
    import numpy as np

    from benchmarks import paper_figs
    from repro.core import simlock as sl
    from repro.core.policies import REGISTRY

    horizon = 2_000.0 if quick else 10_000.0
    probe, live_ok = {}, True
    for name in sorted(REGISTRY):
        cfg = sl.SimConfig(policy=name, sim_time_us=horizon,
                           **CHAOS_PROBE_KW)
        st, grid = sl.sweep(cfg, {"seed": [0, 1]}, slo_us=60.0)
        cell_ok = True
        for s in sl.sweep_summaries(cfg, st, grid):
            cell_ok = (cell_ok
                       and min(s["epochs_per_core"]) > 0
                       and s["sim_time_us"] >= 0.9 * horizon
                       and s["events"] < cfg.max_events)
        probe[name] = {"ok": bool(cell_ok),
                       "summary": s}          # last cell, for the record
        live_ok = live_ok and cell_ok
    bad = [n for n, p in probe.items() if not p["ok"]]
    _emit("chaos/liveness", 0.0,
          f"policies={len(REGISTRY)};faults=preempt+churn+straggle;"
          + (f"stuck={','.join(bad)};" if bad else "")
          + ("PASS" if live_ok else "FAIL"))

    cfg = sl.SimConfig(policy="libasl", sim_time_us=horizon)
    st_sw, _ = sl.sweep(cfg, {"preempt_rate": [0.0, 0.1],
                              "churn_rate": [0.0, 0.2],
                              "straggle_rate": [0.0, 0.05]},
                        product=False, slo_us=60.0)
    st_plain = sl.run(cfg, 60.0, 0)
    zero_cell = jax.tree.map(lambda x: np.asarray(x[0]), st_sw)
    pure_ok = all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(zero_cell),
                        jax.tree.leaves(st_plain)))
    _emit("chaos/zero_rate_purity", 0.0,
          f"bit_identical={pure_ok};{'PASS' if pure_ok else 'FAIL'}")

    rows = paper_figs.chaos_collapse()
    results["chaos/chaos_collapse"] = rows
    mx = max(r["preempt_rate"] for r in rows)
    h = {r["policy"]: r for r in rows if r["preempt_rate"] == mx}
    grace_ok = h["libasl"]["goodput_eps"] >= h["fifo"]["goodput_eps"]
    _emit("chaos/goodput_gate", 0.0,
          f"pr{mx:g}:libasl_goodput={h['libasl']['goodput_eps']:.0f}"
          f"_vs_fifo={h['fifo']['goodput_eps']:.0f};"
          f"{'PASS' if grace_ok else 'FAIL'}")

    gate = bool(live_ok and pure_ok and grace_ok)
    results["chaos/gate"] = {
        "liveness": probe, "zero_rate_bit_identical": bool(pure_ok),
        "max_preempt_rate": float(mx),
        "libasl_goodput_eps": float(h["libasl"]["goodput_eps"]),
        "fifo_goodput_eps": float(h["fifo"]["goodput_eps"]),
        "pass": gate}
    return gate


def _roofline_section(results):
    art = Path(__file__).resolve().parents[1] / "artifacts" / "roofline"
    cells = []
    if art.exists():
        for f in sorted(art.glob("*.json")):
            d = json.loads(f.read_text())
            if d.get("ok") and not d.get("skipped"):
                cells.append(d)
                _emit(f"roofline/{d['cell']}",
                      max(d["t_compute_s"], d["t_memory_s"],
                          d["t_collective_s"]) * 1e6,
                      f"dom={d['dominant']};"
                      f"frac={d['roofline_fraction']:.2f};"
                      f"useful={d['useful_ratio']:.2f}")
    if not cells:
        _emit("roofline/missing", 0.0,
              "run: PYTHONPATH=src python -m benchmarks.roofline")
    results["roofline/cells"] = cells


SECTIONS = ("sim", "paper", "serving", "kernels", "roofline", "chaos")
# "sim" and "chaos" are opt-in (--section ...): "sim" mutates the XLA
# environment (8 virtual devices, pinned intra-op threading), which
# would silently change the kernel/serving baselines of a default
# all-sections run; "chaos" re-runs the chaos_collapse figure the paper
# section already produces.
DEFAULT_SECTIONS = ("paper", "serving", "kernels", "roofline")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--section", action="append", choices=SECTIONS,
                    default=None,
                    help="run only the given section(s); repeatable")
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: 0.1x simulator horizons so the "
                         "paper section fits in CI time")
    args = ap.parse_args(argv)
    sections = set(args.section or DEFAULT_SECTIONS)

    # The sim smoke gate probes the mesh-sharded sweep path.  Under
    # JAX_PLATFORMS=cpu it runs on 8 virtual host devices, with XLA's CPU
    # intra-op threading pinned exactly as benchmarks/simperf.py does (the
    # policy sweeps compile concurrently; unpinned they thrash the host's
    # cores and the speedup gate reads low).  On a TPU host the mesh is
    # the chips.  Only effective before the first jax import, so a
    # caller-provided XLA_FLAGS wins.
    if "sim" in sections:
        from repro.launch.xla_flags import (cpu_platform,
                                            ensure_host_devices, prepend)
        if cpu_platform():
            prepend("--xla_cpu_multi_thread_eigen=false",
                    "intra_op_parallelism_threads=1")
            ensure_host_devices(8)

    enable_persistent_cache()
    ART.mkdir(parents=True, exist_ok=True)
    results = {}
    from benchmarks import paper_figs
    if args.quick:
        paper_figs.SIM_SCALE = 0.1
    sim_ok = serving_ok = chaos_ok = True
    if "sim" in sections:
        sim_ok = _sim_section(results, args.quick)
    if "paper" in sections:
        _run_section("paper", paper_figs.ALL, results)
    if "serving" in sections:
        serving_ok = _serving_section(results, args.quick)
    if "kernels" in sections:
        _kernel_bench(results)
    if "roofline" in sections:
        _roofline_section(results)
    if "chaos" in sections:
        chaos_ok = _chaos_section(results, args.quick)
    # Merge into the existing file: a partial --section run must not
    # wipe the other sections' committed rows.
    out = ART / "results.json"
    if out.exists():
        try:
            prev = json.loads(out.read_text())
        except ValueError:
            prev = {}
        prev.update(results)
        results = prev
    out.write_text(json.dumps(results, indent=1, default=str))
    print(f"# wrote {out} ({len(results)} entries)")
    if not (sim_ok and serving_ok and chaos_ok):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
