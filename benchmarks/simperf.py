"""Simulator performance benchmark — tracks the batched sweep engine.

Measures, per paper figure: total wall-clock, the compile/run split (cold
call vs. hot repeat), simulated events/second, and how many XLA
executables the figure compiled.  For fig1 it additionally times the
*per-cell seed path* — one jit per (policy, n_cores) cell with the seed's
one-event-per-iteration loop (``chunk=1``) — against the batched sweep
(one executable per policy, all thread counts as an active-core mask).

Compiles go through the persistent compilation cache
(``benchmarks/_jax_cache.py``), so the cold-minus-hot compile estimate is
the set-up a user with that cache pays.  Every record names the device
it ran on.

Writes ``BENCH_simlock.json`` at the repo root so the perf trajectory is
tracked from PR to PR (protocol in docs/simulator.md).

    PYTHONPATH=src python -m benchmarks.simperf [--quick] [--figs fig1,...]
"""

from __future__ import annotations

import sys

# Both must precede the first jax import (hence PYTHONPATH=src in every
# invocation), and both apply only where JAX is pinned to the CPU: per-op
# shapes in the simulator are tiny (N<=8 cores), so XLA's CPU intra-op
# threading buys nothing and only thrashes — pinning it lets the
# concurrently-dispatched policy sweeps (and their compiles) overlap
# cleanly on the host's cores.  --devices N virtualizes N host-platform
# devices there so the sweeps can shard their cell dimension over a data
# mesh; on a TPU host the mesh is made of the chips.
from repro.launch.xla_flags import (argv_device_count, cpu_platform,
                                    ensure_host_devices, prepend)

if cpu_platform():
    prepend("--xla_cpu_multi_thread_eigen=false",
            "intra_op_parallelism_threads=1")
    _n = int(argv_device_count(sys.argv, 1))
    if _n > 1:
        ensure_host_devices(_n)

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import numpy as np

from benchmarks._jax_cache import enable_persistent_cache
from repro.core import simlock as sl

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "BENCH_simlock.json"


def _compiles() -> int:
    return sl.n_batch_executables() + sl._run_single._cache_size()


def _events(st) -> int:
    return int(np.sum(np.asarray(st.events)))


def _log_mark() -> int:
    """The ``seq`` the simulator's next logged call will carry."""
    log = sl.sweep_log()
    return log[-1]["seq"] + 1 if log else 0


def _hlo_accounting(mark: int) -> dict:
    """Aggregate the collective schedule of every sweep executable run
    since ``mark`` (a :func:`_log_mark`; simlock's call log, cache hits
    included, single-run ``sl.run`` cells excluded)."""
    recs = [r for r in sl.sweep_log()
            if r["seq"] >= mark and r["kind"] == "sweep"]
    return {
        "sweep_calls": len(recs),
        "collective_count": sum(r["collectives"]["total_count"]
                                for r in recs),
        "collective_bytes": sum(r["collectives"]["total_bytes"]
                                for r in recs),
        "devices": max((r["devices"] for r in recs), default=1),
    }


def _fig1_policies(quick: bool):
    """Exactly fig1's workload — reuse paper_figs' calibration (every
    registered policy, one executable each) so this benchmark can never
    drift from the figure it claims to track."""
    from benchmarks import paper_figs
    paper_figs.SIM_SCALE = 0.1 if quick else 1.0
    return paper_figs.fig1_policies()


def bench_fig1_batched_vs_seed(quick: bool) -> dict:
    """The acceptance benchmark: fig1's cells (8 thread counts x every
    registered policy), batched vs. per-cell."""
    from concurrent.futures import ThreadPoolExecutor
    from benchmarks import paper_figs
    cfgs = _fig1_policies(quick)
    ns = list(range(1, 9))

    def one_policy(arg):
        _, cfg, slo = arg
        st, _ = sl.sweep(cfg, {"n_cores": ns}, slo_us=slo,
                         mesh=paper_figs.MESH)
        jax.block_until_ready(st.events)
        return _events(st)

    # --- batched sweep engine: one executable per policy, the policies
    # dispatched concurrently (independent executables; XLA releases the
    # GIL, so they overlap on the container's cores).  Concurrency is
    # capped at cores+1: with the registry at 7 policies, 7 concurrent
    # XLA compiles on 2 cores thrash (measured 59s cold vs 43s at 3
    # workers).  The seed path below stays sequential — exactly how the
    # seed ran it.  Mesh-sharded sweeps must NOT overlap in one process:
    # XLA CPU's collective rendezvous interleaves participants from
    # concurrent executables sharing a device set and deadlocks.
    import os
    n_workers = 1 if paper_figs.MESH is not None else \
        min(len(cfgs), (os.cpu_count() or 2) + 1)
    with ThreadPoolExecutor(n_workers) as pool:
        c0 = _compiles()
        h0 = _log_mark()
        t0 = time.time()
        events = sum(pool.map(one_policy, cfgs))
        batched_cold = time.time() - t0
        batched_compiles = _compiles() - c0
        hlo = _hlo_accounting(h0)
        t0 = time.time()
        sum(pool.map(one_policy, cfgs))
        batched_hot = time.time() - t0

    # --- per-cell seed path: the pre-batching shape of this benchmark:
    # one executable per (policy, n) cell and one event per loop
    # iteration (chunk=1), exactly as the seed simulator ran it.
    c0 = _compiles()
    t0 = time.time()
    for pol, _, slo in cfgs:
        for n in ns:
            cell = dataclasses.replace(
                paper_figs._cfg(pol, n, **paper_figs.FIG1_KW.get(pol, {})),
                chunk=1)
            jax.block_until_ready(sl.run(cell, slo).events)
    seed_wall = time.time() - t0
    seed_compiles = _compiles() - c0

    return {
        "cells": len(cfgs) * len(ns),
        "policies": len(cfgs),
        "events": events,
        "batched_wall_s": round(batched_cold, 2),
        "batched_hot_s": round(batched_hot, 2),
        "batched_compile_s_est": round(batched_cold - batched_hot, 2),
        "batched_compilations": batched_compiles,
        "batched_events_per_s": round(events / batched_hot),
        "seed_path_wall_s": round(seed_wall, 2),
        "seed_path_compilations": seed_compiles,
        "speedup_vs_seed_path": round(seed_wall / batched_cold, 2),
        "hlo": hlo,
    }


def bench_figures(quick: bool, figs=None) -> dict:
    """Wall-clock + events/s for every paper figure on the new API."""
    from benchmarks import paper_figs
    paper_figs.SIM_SCALE = 0.1 if quick else 1.0
    out = {}
    for name, fn in paper_figs.ALL.items():
        if figs and name not in figs:
            continue
        c0 = _compiles()
        h0 = _log_mark()
        t0 = time.time()
        rows = fn()
        wall = time.time() - t0
        events = sum(r["summary"]["events"] for r in rows if "summary" in r)
        out[name] = {
            "rows": len(rows),
            "wall_s": round(wall, 2),
            "compilations": _compiles() - c0,
            "events": events,
            "hlo": _hlo_accounting(h0),
        }
        if events:
            out[name]["events_per_s"] = round(events / max(wall, 1e-9))
        else:
            # Host-bound figures (bench2/3/5) emit derived aggregate rows
            # with no raw per-cell summaries: a device events/s would be
            # meaningless, so record host row throughput instead.
            # benchmarks/report.py renders either shape.
            out[name]["rows_per_s"] = round(len(rows) / max(wall, 1e-9), 2)
        rate = (f"ev/s={out[name]['events_per_s']}" if events else
                f"rows/s={out[name]['rows_per_s']}")
        print(f"{name:22s} rows={len(rows):3d} wall={wall:7.2f}s "
              f"compiles={out[name]['compilations']} {rate} "
              f"coll={out[name]['hlo']['collective_count']}", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="0.1x sim horizons (CI smoke)")
    ap.add_argument("--figs", type=str, default=None,
                    help="comma-separated figure subset")
    ap.add_argument("--skip-figures", action="store_true",
                    help="only the fig1 batched-vs-seed acceptance bench")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard every sweep's cell dimension over a 1-D "
                         "data mesh of N devices: the chips of a TPU host, "
                         "or N virtual host devices under JAX_PLATFORMS=cpu "
                         "(collective accounting goes nonzero)")
    args = ap.parse_args()
    enable_persistent_cache()
    if args.devices > 1:
        from benchmarks import paper_figs
        from repro.launch.mesh import make_sweep_mesh
        paper_figs.MESH = make_sweep_mesh(args.devices)

    figs = set(args.figs.split(",")) if args.figs else None
    dev = jax.devices()[0]
    rec = {
        "bench": "simlock",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "jax": jax.__version__,
        "quick": bool(args.quick),
        "chunk": sl.SimConfig().chunk,
        "devices": args.devices,
    }
    print("== fig1: batched sweep vs per-cell seed path ==", flush=True)
    rec["fig1_sweep"] = bench_fig1_batched_vs_seed(args.quick)
    for k, v in rec["fig1_sweep"].items():
        print(f"  {k}: {v}")
    if not args.skip_figures:
        print("== per-figure wall clock ==", flush=True)
        rec["figures"] = bench_figures(args.quick, figs)
        if figs and OUT.exists():
            # A subset recording must not drop the other figures'
            # committed entries: merge into the existing protocol file.
            try:
                prev = json.loads(OUT.read_text()).get("figures", {})
            except ValueError:
                prev = {}
            prev.update(rec["figures"])
            rec["figures"] = prev

    OUT.write_text(json.dumps(rec, indent=1))
    print(f"# wrote {OUT}")


if __name__ == "__main__":
    main()
