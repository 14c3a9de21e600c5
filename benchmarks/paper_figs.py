"""Paper figure/table reproductions on the JAX discrete-event AMP simulator.

Calibration (documented in EXPERIMENTS.md §Paper-validation): 4 big + 4
little cores (Apple M1 topology); critical sections 3.75x slower on little
cores (the Sysbench gap), non-critical NOP work 1.8x slower (the NOP gap);
CS = 3us on a big core (contended 4-cache-line RMW), intra-epoch noncrit
1us, inter-epoch 5us — chosen so 4 big cores already saturate the lock,
the regime of paper Figures 1/4.  All numbers are simulated microseconds.

Every figure is expressed on the batched sweep engine
(``simlock.sweep``): one vmapped+jitted call per (policy, program), with
thread counts, SLOs, policy weights, mix ratios and wakeup costs riding as
traced batch axes — fig1's 24 cells compile exactly 3 executables (one per
policy).  ``SIM_SCALE`` shortens every simulation for CI smoke runs
(``benchmarks/run.py --quick``).
"""

from __future__ import annotations

import numpy as np

from repro.core import energy
from repro.core import simlock as sl

BIG_SPEED = 1.0
CS_RATIO = 3.75
NC_RATIO = 1.8

# Global sim-length scale: benchmarks/run.py --quick sets this < 1 so a
# smoke run of every figure fits in CI time.
SIM_SCALE = 1.0

# Optional jax.sharding.Mesh: when set (benchmarks/simperf.py --devices N),
# every figure's sweep shards its cell dimension over MESH's DATA_AXIS —
# results are bit-identical to the unsharded run (tests/test_sweep.py).
MESH = None
DATA_AXIS = "data"


def _cfg(policy, n_cores=8, sim_time_us=60_000.0, **kw):
    n_big = min(n_cores, 4)
    big = tuple([1] * n_big + [0] * (n_cores - n_big))
    base = dict(
        policy=policy, n_cores=n_cores, big=big,
        speed_cs=tuple(1.0 if b else CS_RATIO for b in big),
        speed_nc=tuple(1.0 if b else NC_RATIO for b in big),
        seg_noncrit_us=(1.0,), seg_cs_us=(3.0,), seg_lock=(0,),
        inter_epoch_us=5.0, sim_time_us=sim_time_us * SIM_SCALE)
    base.update(kw)
    return sl.SimConfig(**base)


def _rowdict(name, cfg, s):
    return dict(name=name, policy=cfg.policy,
                tput=s["throughput_cs_per_s"],
                p99_all=s["cs_p99_all_us"], ep_p99_all=s["ep_p99_all_us"],
                ep_p99_big=s["ep_p99_big_us"],
                ep_p99_little=s["ep_p99_little_us"], summary=s)


def _row(name, cfg, slo=1e9, seed=0, windows0=None):
    """Single-cell fallback (bench2's sequential window-carry phases)."""
    st = sl.run(cfg, slo, seed, windows0)
    return _rowdict(name, cfg, sl.summarize(cfg, st))


def _sweep_rows(cfg, axes, namer, *, slo_us=1e9, product=True, extra=None):
    """One batched call -> one row per cell (name via ``namer(cell)``)."""
    st, grid = sl.sweep(cfg, axes, slo_us=slo_us, product=product,
                        mesh=MESH, data_axis=DATA_AXIS)
    rows = []
    for s in sl.sweep_summaries(cfg, st, grid, slo_us=slo_us):
        cell = {k: s[k] for k in grid}
        r = _rowdict(namer(cell), cfg, s)
        r.update({k: v for k, v in cell.items()
                  if not isinstance(v, tuple)})
        if extra:
            r.update(extra(cell, s))
        rows.append(r)
    return rows


# ---------------------------------------------------------------------------
# Figure 1: throughput/latency collapse scaling 1..8 threads
# (TAS shows little-core-affinity in this regime)
# Registry-driven: every policy in repro.core.policies gets a curve — 48
# cells, one compilation per policy (the n axis is an active-core mask;
# w_big and the per-policy knobs ride traced).
# ---------------------------------------------------------------------------

# Per-policy fig1 calibration: non-default knobs + the SLO the policy
# tracks (1e9 = pure-throughput mode).  Policies absent here run with
# defaults, so a newly registered policy appears in fig1 automatically.
FIG1_KW = {"tas": dict(w_big=0.15)}
FIG1_SLO = {"libasl": 1e9, "edf": 100.0}


def fig1_cell(name):
    """fig1's 8-core config for policy ``name`` and the SLO it tracks."""
    return _cfg(name, 8, **FIG1_KW.get(name, {})), FIG1_SLO.get(name, 1e9)


def fig1_policies():
    """The fig1 workload per registered policy — also the acceptance
    benchmark's grid (benchmarks/simperf reuses this, so the perf
    protocol can never drift from the figure it tracks)."""
    from repro.core.policies import REGISTRY
    return [(name, *fig1_cell(name)) for name in REGISTRY]


def fig1_rows(pol, cfg, slo):
    """One policy's fig1 curve: its eight thread counts in one sweep."""
    return _sweep_rows(
        cfg, {"n_cores": list(range(1, 9))},
        lambda c: f"fig1/{pol}/n{c['n_cores']}",
        slo_us=slo,
        extra=lambda c, s: dict(n_threads=int(c["n_cores"])))


def fig1_collapse():
    rows = []
    for pol, cfg, slo in fig1_policies():
        rows += fig1_rows(pol, cfg, slo)
    return rows


def fig1_headline(rows):
    """The figure's claim: FIFO (MCS) throughput falls from 4 to 8 cores
    (``mcs_drop`` > 0) and TAS's 8-core CS P99 exceeds FIFO's
    (``tas_p99_vs_mcs`` > 1)."""
    def at(policy, n):
        return next(r for r in rows
                    if r["policy"] == policy and r["n_threads"] == n)
    f4, f8, t8 = at("fifo", 4), at("fifo", 8), at("tas", 8)
    return {"mcs_drop": 1 - f8["tput"] / f4["tput"],
            "tas_p99_vs_mcs": t8["p99_all"] / f8["p99_all"]}


# ---------------------------------------------------------------------------
# Figure 4: the big-core-affinity TAS scenario (64-line CS analogue)
# ---------------------------------------------------------------------------

def fig4_big_affinity():
    rows = []
    for pol, kw in (("fifo", {}), ("tas", dict(w_big=8.0))):
        rows += _sweep_rows(
            _cfg(pol, 8, seg_cs_us=(6.0,), **kw),
            {"n_cores": list(range(1, 9))},
            lambda c, p=pol: f"fig4/{p}/n{c['n_cores']}",
            extra=lambda c, s: dict(n_threads=int(c["n_cores"])))
    return rows


# ---------------------------------------------------------------------------
# Figure 5: static proportional trade-off (prop_n is a traced batch axis)
# ---------------------------------------------------------------------------

def fig5_proportional():
    return _sweep_rows(
        _cfg("prop"), {"prop_n": [1, 2, 5, 10, 20, 50]},
        lambda c: f"fig5/prop{c['prop_n']}",
        extra=lambda c, s: dict(proportion=int(c["prop_n"])))


# ---------------------------------------------------------------------------
# Bench-1 (Fig 8a/8b): contended epochs, 4 CS over 2 locks; SLO sweep
# ---------------------------------------------------------------------------

def _bench1_cfg(policy, **kw):
    base = dict(seg_noncrit_us=(1.0, 0.5, 0.5, 0.5),
                seg_cs_us=(2.0, 1.0, 3.0, 0.5),
                seg_lock=(0, 1, 0, 1), n_locks=2,
                inter_epoch_us=7.5)
    base.update(kw)
    return _cfg(policy, **base)


def bench1_contended():
    # Both phases run the SAME merged 4-policy executable (identical
    # axis names/order and cell count -> one AOT cache entry): phase 1
    # covers the three baseline singles (plus three pad lanes, dropped),
    # phase 2 the libasl SLO column whose values need phase 1's fifo
    # p99.  One compilation for the whole figure, down from 4.
    cfg = _bench1_cfg("fifo", policy_set=("fifo", "tas", "prop",
                                          "libasl"))
    w0 = cfg.default_window_us
    label = {"fifo": "bench1/mcs", "tas": "bench1/tas-big",
             "prop": "bench1/shfl-pb10"}

    def phase(policy, w_big, slos, win0, namer):
        axes = {"policy": list(policy), "w_big": list(w_big),
                "slo_us": list(slos), "window0_us": list(win0)}
        return _sweep_rows(cfg, axes, namer, product=False)

    # Cells 3..5 are pad lanes (fifo duplicates) sliced off below.
    rows = phase(["fifo", "tas", "prop", "fifo", "fifo", "fifo"],
                 [1.0, 8.0, 1.0, 1.0, 1.0, 1.0],
                 [1e9] * 6, [w0] * 6,
                 lambda c: label[c["policy"]])[:3]
    fifo_p99 = rows[0]["ep_p99_all"]
    slos = [0.0, fifo_p99, 1.5 * fifo_p99, 2.5 * fifo_p99, 5 * fifo_p99,
            1e5]
    # LibASL-MAX = the maximum reorder window directly (paper §4), not
    # AIMD-grown from the default: the window0 axis is zipped with the SLO.
    win0 = [w0] * 5 + [1e5]

    def tag(c):
        t = "MAX" if c["slo_us"] >= 1e5 else f"{c['slo_us']:.0f}"
        return f"bench1/libasl-{t}"

    rows += phase(["libasl"] * 6, [1.0] * 6, slos, win0, tag)
    return rows


def bench1_slo_sweep():
    """Figure 8b: the whole SLO axis is one batched call."""
    cfg = _bench1_cfg("libasl")
    slos = list(np.linspace(20.0, 400.0, 14))
    return _sweep_rows(
        cfg, {"slo_us": slos},
        lambda c: f"bench1_sweep/slo{c['slo_us']:.0f}",
        extra=lambda c, s: dict(
            tput=s["throughput_cs_per_s"],
            ep_p99_little=s["ep_p99_little_us"],
            ep_p99_big=s["ep_p99_big_us"]))


# ---------------------------------------------------------------------------
# Bench-2 (Fig 8d): workload shifts; window adapts across phases
# ---------------------------------------------------------------------------

def bench2_variable(slo=150.0):
    """Paper Fig 8d: the AIMD window re-converges across load shifts; the
    final phase is deliberately impossible (epoch >> SLO) — LibASL must
    fall back to FIFO there (windows collapse), exactly as in the paper.
    Sequential by nature (the window state carries across phases; the
    donated ``windows0`` buffer makes each resume copy-free)."""
    phases = [
        ("base", dict(), True),
        ("x8", dict(seg_noncrit_us=(8.0, 4.0, 4.0, 4.0)), True),
        ("back", dict(), True),
        ("x256", dict(seg_noncrit_us=(256.0, 128.0, 128.0, 128.0)), False),
    ]
    rows = []
    windows = None
    for tag, kw, achievable in phases:
        cfg = _bench1_cfg("libasl", sim_time_us=40_000.0, **kw)
        st = sl.run(cfg, slo, 0, windows)
        windows = st.window
        s = sl.summarize(cfg, st)
        rows.append(dict(
            name=f"bench2/{tag}", slo_us=slo, achievable=achievable,
            tput=s["throughput_cs_per_s"],
            ep_p99_little=s["ep_p99_little_us"],
            mean_window_us=float(np.mean(np.asarray(windows)[4:]) / sl.US),
            violation_excess=max(
                0.0, (s["ep_p99_little_us"] - slo) / max(slo, 1e-9))))
    return rows


# ---------------------------------------------------------------------------
# Bench-3 (Fig 8c): mixed short/long epochs at different ratios
# (the mix probability is a traced batch axis: one call per policy)
# ---------------------------------------------------------------------------

def bench3_mixed(slo=400.0):
    short_pcts = (0, 20, 40, 60, 80, 100)
    probs = [1.0 - p / 100.0 for p in short_pcts]
    kw = dict(long_epoch_prob=1.0, long_epoch_scale=100.0,
              sim_time_us=120_000.0)
    asl = _sweep_rows(_bench1_cfg("libasl", **kw),
                      {"long_epoch_prob": probs},
                      lambda c: f"bench3/p{c['long_epoch_prob']:.1f}",
                      slo_us=slo)
    mcs = _sweep_rows(_bench1_cfg("fifo", **kw),
                      {"long_epoch_prob": probs},
                      lambda c: f"bench3/mcs{c['long_epoch_prob']:.1f}")
    rows = []
    for pct, r, m in zip(short_pcts, asl, mcs):
        rows.append(dict(name=f"bench3/short{pct}", slo_us=slo,
                         short_pct=pct, tput=r["tput"],
                         tput_vs_mcs=r["tput"] / m["tput"],
                         ep_p99_little=r["ep_p99_little"]))
    return rows


# ---------------------------------------------------------------------------
# Bench-4 (Fig 8e/8f): scalability at fixed SLOs
# ---------------------------------------------------------------------------

def bench4_scalability():
    # High contention (queue never drains), the paper's Fig 8e regime:
    # LibASL-MAX keeps the lock on big cores and its throughput curve
    # stays flat as little threads join.
    kw = dict(seg_cs_us=(6.0,), seg_noncrit_us=(0.5,), inter_epoch_us=2.0)
    ns = list(range(1, 9))
    fifo = _sweep_rows(_cfg("fifo", **kw), {"n_cores": ns},
                       lambda c: f"bench4/mcs/n{c['n_cores']}",
                       extra=lambda c, s: dict(n_threads=int(c["n_cores"])))
    tas = _sweep_rows(_cfg("tas", w_big=8.0, **kw), {"n_cores": ns},
                      lambda c: f"bench4/tas/n{c['n_cores']}",
                      extra=lambda c, s: dict(n_threads=int(c["n_cores"])))
    rows = []
    for f, t in zip(fifo, tas):
        rows += [f, t]

    # LibASL at 3 SLO points per n — one zipped 24-cell call (slo and
    # window0 pair with each n; "tas-lat" tracks the measured TAS P99).
    asl_cfg = _cfg("libasl", **kw)
    w_dflt = asl_cfg.default_window_us
    n_ax, slo_ax, win_ax, tags = [], [], [], []
    for t in tas:
        n = t["n_threads"]
        for slo, tag, w0 in ((0.0, "0", w_dflt),
                             (t["ep_p99_all"], "tas-lat", w_dflt),
                             (1e5, "MAX", 1e5)):
            n_ax.append(n)
            slo_ax.append(slo)
            win_ax.append(w0)
            tags.append(f"bench4/libasl-{tag}/n{n}")
    tag_of = {(n, s): tg for n, s, tg in zip(n_ax, slo_ax, tags)}
    rows += _sweep_rows(
        asl_cfg,
        {"n_cores": n_ax, "slo_us": slo_ax, "window0_us": win_ax},
        lambda c: tag_of[(int(c["n_cores"]), float(c["slo_us"]))],
        product=False,
        extra=lambda c, s: dict(n_threads=int(c["n_cores"])))
    return rows


# ---------------------------------------------------------------------------
# Bench-5 (Fig 8g): contention sweep — little cores help at low contention
# (the noncrit duration is a table batch axis: 3 calls for 27 cells)
# ---------------------------------------------------------------------------

def bench5_contention():
    ncs = (0.5, 1, 2, 4, 8, 16, 32, 64, 128)
    nc_ax = [(float(nc),) for nc in ncs]
    kw = dict(seg_cs_us=(2.0,), inter_epoch_us=0.5)
    # fifo at 8 and 4 active cores x every contention level: one call.
    fifo = _sweep_rows(
        _cfg("fifo", **kw), {"seg_noncrit_us": nc_ax, "n_cores": [8, 4]},
        lambda c: f"bench5/mcs{c['n_cores']}/nc{c['seg_noncrit_us'][0]:g}")
    tas = _sweep_rows(
        _cfg("tas", w_big=8.0, **kw), {"seg_noncrit_us": nc_ax},
        lambda c: f"bench5/tas/nc{c['seg_noncrit_us'][0]:g}")
    asl = _sweep_rows(
        _cfg("libasl", default_window_us=1e5, **kw),
        {"seg_noncrit_us": nc_ax},
        lambda c: f"bench5/libasl/nc{c['seg_noncrit_us'][0]:g}")
    mcs8 = {r["name"].rsplit("nc", 1)[1]: r for r in fifo
            if "/mcs8/" in r["name"]}
    mcs4 = {r["name"].rsplit("nc", 1)[1]: r for r in fifo
            if "/mcs4/" in r["name"]}
    rows = []
    for nc, t, a in zip(ncs, tas, asl):
        key = f"{float(nc):g}"
        m8, m4 = mcs8[key], mcs4[key]
        rows.append(dict(name=f"bench5/nc{nc}", noncrit_us=nc,
                         tput_libasl=a["tput"], tput_mcs8=m8["tput"],
                         tput_mcs4=m4["tput"], tput_tas=t["tput"],
                         speedup_vs_mcs8=a["tput"] / m8["tput"],
                         speedup_vs_mcs4=a["tput"] / m4["tput"]))
    return rows


# ---------------------------------------------------------------------------
# Load-latency sweep (queue_flex-style): offered-load sweep -> throughput
# + P99 per policy on the stochastic workload model (repro.workloads):
# open-loop Poisson think times, lognormal services.  The load axis rides
# as the traced ``arrival_rate`` sweep dimension — one executable per
# policy for the whole curve.
# ---------------------------------------------------------------------------

# Step-utilization calibration for the merged load figures: events the
# simulator retires per 8 ms of sim, measured per (policy, load frac) on
# the M1 calibration (probe: run the figure grid at sim_time_us=8e3 and
# read st.events per lane).  Each cell's horizon is stretched by
# max(table)/table[cell], so every lane of the ONE merged executable
# retires ~the same event count — a vmapped while_loop steps ALL lanes
# until the last finishes, so equalizing per-lane event counts turns
# live-guard no-op steps into retired events (~3x device events/s; see
# docs/simulator.md §Fused step kernel & multi-policy executables).
# Low-load cells simply simulate longer (their tails get MORE samples);
# stale values only cost utilization, never correctness.
_LOADLAT_EV8MS = {
    ("fifo", 0.2): 606, ("fifo", 0.4): 1134, ("fifo", 0.6): 1612,
    ("fifo", 0.8): 1958, ("fifo", 0.9): 2094, ("fifo", 1.5): 2514,
    ("fifo", 3.0): 2427,
    ("tas", 0.2): 606, ("tas", 0.4): 1139, ("tas", 0.6): 1620,
    ("tas", 0.8): 1988, ("tas", 0.9): 2158, ("tas", 1.5): 2786,
    ("tas", 3.0): 3400,
    ("prop", 0.2): 606, ("prop", 0.4): 1150, ("prop", 0.6): 1620,
    ("prop", 0.8): 2013, ("prop", 0.9): 2200, ("prop", 1.5): 2938,
    ("prop", 3.0): 3822,
    ("libasl", 0.2): 615, ("libasl", 0.4): 1164, ("libasl", 0.6): 1677,
    ("libasl", 0.8): 2047, ("libasl", 0.9): 2254, ("libasl", 1.5): 2956,
    ("libasl", 3.0): 3257,
}
_OPENLOOP_EV8MS = {
    ("fifo", 0.2): 906, ("fifo", 0.4): 1734, ("fifo", 0.6): 2562,
    ("fifo", 0.8): 3300, ("fifo", 0.9): 3690, ("fifo", 1.1): 3934,
    ("shfl", 0.2): 906, ("shfl", 0.4): 1734, ("shfl", 0.6): 2562,
    ("shfl", 0.8): 3300, ("shfl", 0.9): 3691, ("shfl", 1.1): 4288,
    ("libasl", 0.2): 910, ("libasl", 0.4): 1761, ("libasl", 0.6): 2644,
    ("libasl", 0.8): 3479, ("libasl", 0.9): 3991, ("libasl", 1.1): 4443,
}

# Seed replicas per (policy, load) cell of the merged load figures: extra
# lanes in the same executable (near-free on the batched step), averaged
# back to one row per cell by _seed_mean.
LOADLAT_SEEDS = 6
OPENLOOP_SEEDS = 6


def _seed_mean(rows):
    """Collapse per-seed replica rows (rows sharing a name) to their mean.

    Numeric row keys average over finite replicas; string/dict keys keep
    the first replica's value.  The representative ``summary`` keeps the
    first replica's detail with ``events`` summed over ALL replicas, so
    the bench harness (benchmarks/simperf) counts every simulated event
    behind the row."""
    groups: dict = {}
    for r in rows:
        groups.setdefault(r["name"], []).append(r)
    out = []
    for grp in groups.values():
        r = dict(grp[0])
        for k, v in grp[0].items():
            if isinstance(v, bool) or not isinstance(
                    v, (int, float, np.integer, np.floating)):
                continue
            vals = np.asarray([g[k] for g in grp], float)
            fin = vals[np.isfinite(vals)]
            r[k] = float(fin.mean()) if fin.size else float("nan")
        r.pop("seed", None)
        r["n_seeds"] = len(grp)
        r["summary"] = dict(grp[0]["summary"], events=sum(
            g["summary"]["events"] for g in grp))
        out.append(r)
    return out


def _loadlat_rate(frac: float) -> float:
    """wl_rate that offers ``frac`` of lock capacity: bisect the
    utilization model U(r) = sum_c cs_c / (cs_c + think_c / r), with the
    per-core cs/think times derived from the same ``_cfg`` calibration
    the sweep runs (so a calibration change cannot desynchronize the
    load labels)."""
    cfg = _cfg("fifo", 8)
    cs = [sum(d * cfg.speed_cs[c] for d in cfg.seg_cs_us)
          for c in range(cfg.n_cores)]
    think = [(sum(cfg.seg_noncrit_us) + cfg.inter_epoch_us)
             * cfg.speed_nc[c] for c in range(cfg.n_cores)]

    def util(r):
        return sum(c / (c + th / r) for c, th in zip(cs, think))

    lo, hi = 1e-4, 1e4
    for _ in range(80):
        mid = (lo * hi) ** 0.5
        if util(mid) < frac:
            lo = mid
        else:
            hi = mid
    return float((lo * hi) ** 0.5)


def loadlat_sweep(slo=200.0):
    """Throughput + tail latency vs offered load, one curve per policy —
    the macro-benchmark shape of the paper's Table 1 databases.  The
    load grid is shared with the dispatch-fleet sweep
    (serving_bench.LOAD_FRACS).

    The whole policy x load grid is ONE merged multi-policy executable
    (cfg.policy_set): the policy rides traced in SimParams.pol_id, so
    the figure costs 1 compilation instead of one per policy.  Each cell
    runs LOADLAT_SEEDS replica lanes with horizon-equalized per-cell sim
    times (_LOADLAT_EV8MS) and _seed_mean folds them to one row."""
    from benchmarks.serving_bench import LOAD_FRACS
    # The shared grid plus two saturated points — the regime where the
    # policies separate (queue_flex's "excess tail latency" knee).
    fracs = tuple(LOAD_FRACS) + (1.5, 3.0)
    rates = [_loadlat_rate(f) for f in fracs]
    wl = dict(wl=True, wl_process="poisson", wl_service="lognormal",
              wl_cv=1.0, sim_time_us=80_000.0)
    specs = (("fifo", 1.0, 1e9), ("tas", 8.0, 1e9),
             ("prop", 1.0, 1e9), ("libasl", 1.0, slo))
    cfg = _cfg("fifo", 8, **wl, policy_set=tuple(p for p, _, _ in specs))
    emax = max(_LOADLAT_EV8MS.values())
    axes = {"policy": [], "arrival_rate": [], "w_big": [], "slo_us": [],
            "seed": [], "sim_time_us": []}
    for pol, w_big, slo_us in specs:
        for f, r in zip(fracs, rates):
            for seed in range(LOADLAT_SEEDS):
                axes["policy"].append(pol)
                axes["arrival_rate"].append(r)
                axes["w_big"].append(w_big)
                axes["slo_us"].append(slo_us)
                axes["seed"].append(seed)
                axes["sim_time_us"].append(
                    cfg.sim_time_us * emax / _LOADLAT_EV8MS[pol, f])
    rows = _sweep_rows(
        cfg, axes,
        lambda c: (f"loadlat/{c['policy']}/"
                   f"f{fracs[rates.index(c['arrival_rate'])]:.2f}"),
        product=False,
        extra=lambda c, s: dict(
            load_frac=fracs[rates.index(c["arrival_rate"])]))
    return _seed_mean(rows)


# ---------------------------------------------------------------------------
# Open-loop load-latency sweep: arrivals as events (cfg.wl_open), not
# think-scaling — each core runs an open queue, so epoch latency is the
# full sojourn from arrival and the curves show the classic open-loop
# knee (latency diverges at the saturation point instead of the
# closed-loop's self-throttled plateau).  The load axis is the traced
# ``arrival_rate`` — one executable per policy for the whole curve.
# ---------------------------------------------------------------------------

def _openloop_rate(frac: float) -> float:
    """wl_rate that offers ``frac`` of lock capacity in open-loop mode:
    core ``c`` contributes ``rate / base_c`` arrivals per us (base = its
    closed-loop think budget ``(noncrit0 + inter) * speed_nc``), each
    holding the lock for its CS time."""
    cfg = _cfg("fifo", 8)
    cs = [sum(d * cfg.speed_cs[c] for d in cfg.seg_cs_us)
          for c in range(cfg.n_cores)]
    base = [(cfg.seg_noncrit_us[0] + cfg.inter_epoch_us) * cfg.speed_nc[c]
            for c in range(cfg.n_cores)]
    return frac / sum(c / b for c, b in zip(cs, base))


def openloop_loadlat(slo=300.0):
    """Open-loop offered load -> throughput + sojourn P99 per policy
    (fifo baseline, the paper's libasl, and the shfl plugin — the two
    throughput-first points bracket the AIMD policy).

    Like loadlat_sweep, the whole grid is ONE merged multi-policy
    executable with horizon-equalized seed-replica lanes (the open-loop
    figures are the bench harness's device events/s acceptance floor)."""
    from benchmarks.serving_bench import LOAD_FRACS
    fracs = tuple(LOAD_FRACS) + (1.1,)     # one past-saturation point
    rates = [_openloop_rate(f) for f in fracs]
    wl = dict(wl=True, wl_open=True, wl_process="poisson",
              wl_service="lognormal", wl_cv=1.0, sim_time_us=60_000.0)
    specs = (("fifo", 1e9), ("shfl", 1e9), ("libasl", slo))
    cfg = _cfg("fifo", 8, **wl, policy_set=tuple(p for p, _ in specs))
    emax = max(_OPENLOOP_EV8MS.values())
    axes = {"policy": [], "arrival_rate": [], "slo_us": [],
            "seed": [], "sim_time_us": []}
    for pol, slo_us in specs:
        for f, r in zip(fracs, rates):
            for seed in range(OPENLOOP_SEEDS):
                axes["policy"].append(pol)
                axes["arrival_rate"].append(r)
                axes["slo_us"].append(slo_us)
                axes["seed"].append(seed)
                axes["sim_time_us"].append(
                    cfg.sim_time_us * emax / _OPENLOOP_EV8MS[pol, f])
    rows = _sweep_rows(
        cfg, axes,
        lambda c: (f"openloop/{c['policy']}/"
                   f"f{fracs[rates.index(c['arrival_rate'])]:.2f}"),
        product=False,
        extra=lambda c, s: dict(
            load_frac=fracs[rates.index(c["arrival_rate"])]))
    return _seed_mean(rows)


# ---------------------------------------------------------------------------
# Chaos collapse: throughput / P99 / goodput vs lock-holder preemption
# rate, one curve per registered policy (docs/faults.md).  Preemption is
# asymmetric — ``fault_mask`` makes only the little cores preemptible
# (scheduler pressure lands on the efficiency cores) — so FIFO craters
# (its round-robin handoff parks the lock on a preemptible core 1/2 the
# time and the whole convoy eats each stall) while policies that keep
# the lock on big cores inside their SLO slack (LibASL, TAS-big) dodge
# the stalls and degrade gracefully.  The preemption axis rides traced
# (sweep() flips the static gate): the whole grid is one executable per
# policy.
# ---------------------------------------------------------------------------

CHAOS_RATES = (0.0, 0.02, 0.05, 0.1, 0.2)


def chaos_collapse(slo=300.0):
    from repro.core.policies import REGISTRY
    rows = []
    for pol in REGISTRY:
        base = _cfg(pol, 8)
        cfg = _cfg(pol, 8, sim_time_us=60_000.0,
                   preempt_scale_us=50.0,
                   fault_mask=tuple(0.0 if b else 1.0 for b in base.big),
                   **FIG1_KW.get(pol, {}))
        rows += _sweep_rows(
            cfg, {"preempt_rate": list(CHAOS_RATES)},
            lambda c, p=pol: f"chaos/{p}/pr{c['preempt_rate']:g}",
            slo_us=slo,
            extra=lambda c, s: dict(
                slo_us=slo, goodput_eps=s["goodput_eps"],
                slo_good_frac=s["slo_good_frac"]))
    return rows


# ---------------------------------------------------------------------------
# Energy efficiency: throughput-per-watt + EDP vs big:little mix, one
# curve per registered policy (docs/energy.md).  Little cores draw a
# fraction of a big core's watts (energy.amp_power, Cortex-A15/A7
# class) but also retire CS work 3.75x slower — whether racing the lock
# onto big cores wins on J/op is the question this figure answers per
# policy.  Every per-core table of a mix — the big bit, both speed
# tables and the four phase-power tables — rides as one zipped traced
# table axis, so the whole mix column is ONE executable per policy.
# ---------------------------------------------------------------------------

ENERGY_MIXES = (8, 6, 4, 2, 0)       # n_big of 8 cores


def energy_efficiency(sim_time_us=60_000.0):
    from repro.core.policies import REGISTRY
    mixes = []
    for n_big in ENERGY_MIXES:
        big = (1,) * n_big + (0,) * (8 - n_big)
        mixes.append(dict(
            big=big,
            speed_cs=tuple(1.0 if b else CS_RATIO for b in big),
            speed_nc=tuple(1.0 if b else NC_RATIO for b in big),
            **energy.amp_power(big)))
    axes = {k: [m[k] for m in mixes] for k in mixes[0]}
    rows = []
    for pol in REGISTRY:
        cfg = _cfg(pol, 8, sim_time_us=sim_time_us,
                   **FIG1_KW.get(pol, {}))
        rows += _sweep_rows(
            cfg, axes,
            lambda c, p=pol: f"energy/{p}/big{sum(c['big'])}",
            slo_us=FIG1_SLO.get(pol, 1e9), product=False,
            extra=lambda c, s: dict(
                n_big=int(sum(c["big"])),
                energy_j=s["energy_j"], power_w=s.get("power_w"),
                tput_per_watt=s.get("tput_per_watt"),
                edp=s.get("edp")))
    return rows


# ---------------------------------------------------------------------------
# Bench-6: blocking locks / oversubscription — wakeup latency on the
# FIFO handoff path; LibASL standbys dodge it (wakeup is a traced axis)
# ---------------------------------------------------------------------------

def bench6_blocking():
    """Blocking locks: FIFO handoff pays the parked-waiter wakeup latency on
    *every* transfer; LibASL standby grabs (busy-poll during the window)
    dodge it.  The simulator models the wakeup cost, not the full OS
    scheduler, so this shows the degradation *trend* rather than the
    paper's 96% pthread-vs-MCS gap (limitation noted in EXPERIMENTS.md)."""
    wk = [0.0, 8.0, 20.0]
    rows = _sweep_rows(
        _bench1_cfg("fifo", wakeup_us=20.0), {"wakeup_us": wk},
        lambda c: f"bench6/mcs-park/w{c['wakeup_us']:.0f}")
    rows += _sweep_rows(
        _bench1_cfg("libasl", wakeup_us=20.0), {"wakeup_us": wk},
        lambda c: f"bench6/libasl-block/w{c['wakeup_us']:.0f}",
        slo_us=1e5)
    return rows


# ---------------------------------------------------------------------------
# Key-sharded datastore: hot-key contention collapse per dispatch policy
# + throughput vs Zipf exponent (docs/workloads.md §Key-sharded traffic).
# One ZIPPED sweep per policy — the theta column (5 exponents at 16
# locks) and the lock-count column (1..8 locks at YCSB theta 0.99) ride
# in the same batched call, so the whole figure is ONE executable per
# policy.  Plain fifo under the keyed config IS the CRCW baseline (any
# core may access any bucket, strict arrival order) — labeled ``crcw``.
# ---------------------------------------------------------------------------

KEYSHARD_THETAS = (0.0, 0.5, 0.9, 0.99, 1.2)
KEYSHARD_LOCKS = (1, 2, 4, 8)
KEYSHARD_POLICIES = (("fifo", "crcw"), ("ks_erew", "erew"),
                     ("ks_crew", "crew"), ("ks_jbsq", "jbsq"))


def keyshard(n_keys=4096, n_locks=16):
    axes = {
        "zipf_theta": list(KEYSHARD_THETAS) + [0.99] * len(KEYSHARD_LOCKS),
        "n_locks": [n_locks] * len(KEYSHARD_THETAS) + list(KEYSHARD_LOCKS),
    }
    rows = []
    for pol, label in KEYSHARD_POLICIES:
        cfg = _cfg(pol, 8, n_locks=n_locks, n_keys=n_keys)
        rows += _sweep_rows(
            cfg, axes,
            lambda c, p=label: (f"keyshard/{p}/th{c['zipf_theta']:g}"
                                f"_l{int(c['n_locks'])}"),
            product=False,
            extra=lambda c, s, p=label: dict(
                label=p, zipf_theta=float(c["zipf_theta"]),
                n_locks=int(c["n_locks"]), n_keys=n_keys))
    return rows


# ---------------------------------------------------------------------------
# Excess tail beyond the SLO vs offered load — the streaming-histogram
# figure (docs/simulator.md §Streaming metrics).  P99/P999 come from the
# constant-memory on-device histograms (cfg.hist), so the tail covers the
# FULL run history even where the per-core sample rings wrapped; each row
# records how far the percentile overshoots the SLO
# (``excess_p99 = max(0, P99/SLO - 1)``).  The whole policy x load grid
# is ONE merged multi-policy executable (cfg.policy_set), matching the
# loadlat figures' protocol.
# ---------------------------------------------------------------------------

EXCESS_TAIL_SLO = 200.0


def excess_tail(slo=EXCESS_TAIL_SLO):
    from benchmarks.serving_bench import LOAD_FRACS
    fracs = tuple(LOAD_FRACS) + (1.5,)     # one saturated point: the knee
    rates = [_loadlat_rate(f) for f in fracs]
    specs = (("fifo", 1.0, 1e9), ("tas", 8.0, 1e9), ("libasl", 1.0, slo))
    cfg = _cfg("fifo", 8, sim_time_us=40_000.0, wl=True,
               wl_process="poisson", wl_service="lognormal", wl_cv=1.0,
               hist=True, policy_set=tuple(p for p, _, _ in specs))
    axes = {"policy": [], "arrival_rate": [], "w_big": [], "slo_us": []}
    for pol, w_big, slo_us in specs:
        for r in rates:
            axes["policy"].append(pol)
            axes["arrival_rate"].append(r)
            axes["w_big"].append(w_big)
            axes["slo_us"].append(slo_us)

    def _extra(c, s):
        p99, p999 = s["ep_p99_hist_all_us"], s["ep_p999_hist_all_us"]
        return dict(
            load_frac=fracs[rates.index(c["arrival_rate"])],
            slo_us=slo,
            ep_p99_hist_us=p99, ep_p999_hist_us=p999,
            excess_p99=max(0.0, p99 / slo - 1.0),
            excess_p999=max(0.0, p999 / slo - 1.0),
            hist_rel_err_bound=s["hist_rel_err_bound"],
            tail_truncated=bool(s.get("tail_truncated", False)))

    return _sweep_rows(
        cfg, axes,
        lambda c: (f"excess/{c['policy']}/"
                   f"f{fracs[rates.index(c['arrival_rate'])]:.2f}"),
        product=False, extra=_extra)


ALL = {
    "fig1_collapse": fig1_collapse,
    "fig4_big_affinity": fig4_big_affinity,
    "fig5_proportional": fig5_proportional,
    "bench1_contended": bench1_contended,
    "bench1_slo_sweep": bench1_slo_sweep,
    "bench2_variable": bench2_variable,
    "bench3_mixed": bench3_mixed,
    "bench4_scalability": bench4_scalability,
    "bench5_contention": bench5_contention,
    "bench6_blocking": bench6_blocking,
    "loadlat_sweep": loadlat_sweep,
    "openloop_loadlat": openloop_loadlat,
    "chaos_collapse": chaos_collapse,
    "energy_efficiency": energy_efficiency,
    "keyshard": keyshard,
    "excess_tail": excess_tail,
}
