"""Discrete-event AMP lock simulator — the paper's experiments as a JAX module.

This container has one CPU core, so the paper's wall-clock experiments on
asymmetric silicon cannot be re-run directly.  Instead we reproduce them on a
deterministic discrete-event simulation of an AMP: ``N`` cores with per-core
speed factors run (non-critical section → acquire → critical section →
release) loops against ``L`` shared locks under a pluggable lock policy.

Policies are plugins (:mod:`repro.core.policies`): the event loop here is
policy-agnostic — it looks the policy up in the registry and dispatches
the ``on_acquire`` / ``on_standby_expiry`` / ``on_release`` / ``pick_next``
hooks.  Registered out of the box: the paper's baselines ``fifo`` (MCS),
``tas`` (asymmetric test-and-set), ``prop`` (ShflLock-PB analogue) and
``libasl`` (the paper's AIMD reorder window), plus ``edf``
(earliest-deadline grant off the per-core SLO table) and ``shfl``
(ShflLock-style bounded big-forward shuffling).  ``POLICIES`` ids derive
from the registry; docs/simulator.md §Adding a lock policy has the
plugin contract.

Event model (one pending event per core; the phase of the core at the
head of the event clock selects the handler from the dispatch table):
  NONCRIT end  → acquire attempt (policy hook)
  STANDBY end  → reorder window expired (policy hook; only compiled in
                 for policies that declare ``uses_standby``)
  HOLDER end   → release: record latencies, advance epoch, pick next holder
  ARRIVAL due  → open-loop mode (``wl_open``): the next request arrives —
                 start the epoch at its true arrival time, draw the
                 following arrival from the workload's arrival process
QUEUED / SPIN cores carry t_ready=INF and are woken by the releaser.

Batched sweep engine (docs/simulator.md):

The simulator is *one compiled executable per (policy, shape)*, not per
parameter point.  Everything numeric that the paper sweeps — SLO, ``w_big``,
``prop_n``, seed, initial reorder windows, active core count, segment
durations, the long-epoch mix and the wakeup cost — is carried in two traced
pytrees (:class:`SimTables` from the static program, :class:`SimParams` per
run) threaded through the event handlers, while :class:`SimConfig` is
*canonicalized* before being used as the jit static argument.  Thread-count
scaling runs padded to ``cfg.n_cores`` with an active-core mask, so fig1's
n=1..8 share one executable.  ``sweep(cfg, axes)`` runs one whole figure
as a single ``lax.map``-batched call; the inner loop retires ``cfg.chunk``
events per ``lax.scan`` chunk inside the outer ``while_loop`` to amortize
dispatch.

Stochastic workloads (``wl=True``; repro.workloads, docs/workloads.md)
scale each epoch's think and service segments by counter-based draws —
offered load (``arrival_rate``), service shape (``cv``/``mix``) and
burstiness sweep as traced axes too, and the per-core ``slo_scale``
table models multi-class tenants side by side.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aimd, policies
from repro.core.policies.base import (ARRIVAL, HOLDER, INF, NONCRIT, QUEUED,
                                      SPIN, STANDBY, US)
# Queue/grant helpers live next to the policy contract now; the old
# underscored names stay importable here (tests / downstream callers).
from repro.core.policies.base import deq as _deq
from repro.core.policies.base import enq as _enq
from repro.core.policies.base import grant as _grant
from repro.core.policies.base import qlen as _qlen
from repro.core.policies.base import ticks as _ticks
from repro.core.policies.base import weighted_pick as _weighted_pick
from repro.core import columns as colreg
from repro.core import energy as _energy  # registers the DVFS/power columns
from repro.dist.hlo_analysis import collective_stats
from repro.core.policies.base import lock_of as _lock_of
from repro.core import calllog, stats
from repro.faults import model as flt
from repro.workloads import generators as wlg
from repro.workloads import keys as wlk

# name -> stable integer id, derived from the policy registry
# (registration order; the first four match the pre-registry constants).
POLICIES = policies.policy_ids()


def _validate_config(cfg) -> None:
    """Reject NaN / negative / out-of-range fields and unknown policy
    names at construction (``SimConfig.__post_init__``) — a bad knob
    must raise here, not produce a silent garbage sweep.  Every bound
    admits the `_canon` replacement values (canonicalized configs pass
    through this too)."""
    if cfg.policy not in POLICIES:
        import difflib
        hint = difflib.get_close_matches(cfg.policy, POLICIES, n=1)
        raise ValueError(
            f"unknown lock policy {cfg.policy!r}; registered: "
            f"{sorted(POLICIES)}"
            + (f" -- did you mean {hint[0]!r}?" if hint else ""))
    if cfg.policy_set:
        for p in cfg.policy_set:
            if p not in POLICIES:
                raise ValueError(
                    f"policy_set entry {p!r} is not registered; "
                    f"registered: {sorted(POLICIES)}")
        if len(set(cfg.policy_set)) != len(cfg.policy_set):
            raise ValueError(
                f"policy_set has duplicates: {cfg.policy_set!r}")
        if cfg.policy not in cfg.policy_set:
            raise ValueError(
                f"policy {cfg.policy!r} is not in "
                f"policy_set {cfg.policy_set!r}")

    def chk(name, lo=None, hi=None, lo_open=False):
        v = getattr(cfg, name)
        if v != v:  # NaN (ints compare equal to themselves)
            raise ValueError(f"SimConfig.{name} is NaN")
        if lo is not None and (v < lo or (lo_open and v == lo)):
            raise ValueError(f"SimConfig.{name} must be "
                             f"{'>' if lo_open else '>='} {lo}, got {v!r}")
        if hi is not None and v > hi:
            raise ValueError(f"SimConfig.{name} must be <= {hi}, got {v!r}")

    for name in ("long_epoch_prob", "wl_mix", "wl_amp",
                 "preempt_rate", "churn_rate", "straggle_rate"):
        chk(name, 0.0, 1.0)
    for name in ("inter_epoch_us", "wakeup_us", "default_window_us",
                 "max_window_us", "w_big", "wl_cv", "wl_period_us",
                 "preempt_scale_us", "long_epoch_scale"):
        chk(name, 0.0)
    for name in ("sim_time_us", "wl_rate", "wl_burst", "wl_mix_scale",
                 "churn_period_us"):
        chk(name, 0.0, lo_open=True)
    chk("wl_burst_len", 0.0)
    chk("straggle_scale", 1.0)
    chk("pct", 0.0, 100.0, lo_open=True)
    for name in ("n_cores", "n_locks", "epcap", "max_events", "chunk",
                 "prop_n"):
        chk(name, 1)
    chk("n_keys", 0)
    chk("hist_buckets", 4)
    chk("hist_lo_us", 0.0, lo_open=True)
    chk("hist_warmup", 0)
    if not cfg.hist_hi_us > cfg.hist_lo_us:
        raise ValueError(
            f"SimConfig.hist_hi_us must be > hist_lo_us, got "
            f"hi={cfg.hist_hi_us!r} lo={cfg.hist_lo_us!r}")
    import math
    if not math.isfinite(cfg.zipf_theta) or cfg.zipf_theta < 0.0:
        raise ValueError("SimConfig.zipf_theta must be finite and >= 0, "
                         f"got {cfg.zipf_theta!r}")
    if cfg.n_keys > wlk.MAX_KEYS:
        raise ValueError(f"SimConfig.n_keys={cfg.n_keys} exceeds 2**24: "
                         f"{wlk.TOO_MANY_KEYS}")
    if 0 < cfg.n_keys < cfg.n_locks:
        raise ValueError(
            f"SimConfig.n_keys={cfg.n_keys} is smaller than "
            f"n_locks={cfg.n_locks}: every lock needs at least one key "
            f"(raise n_keys or lower n_locks)")
    if len(cfg.seg_cs_us) != len(cfg.seg_noncrit_us) or \
            len(cfg.seg_cs_us) != len(cfg.seg_lock):
        raise ValueError("seg_noncrit_us / seg_cs_us / seg_lock must have "
                         "equal lengths")
    if not cfg.seg_cs_us:
        raise ValueError("epoch program needs at least one segment")
    for name in ("seg_noncrit_us", "seg_cs_us", "big", "speed_cs",
                 "speed_nc"):
        vals = getattr(cfg, name)
        if any(v != v or v < 0 for v in vals):
            raise ValueError(f"SimConfig.{name} has a NaN/negative entry: "
                             f"{vals!r}")
    # Registered per-core columns (repro.core.columns): numeric specs
    # reject NaN/negative entries; ``positive`` specs (dvfs divides the
    # segment durations) additionally reject zero.
    for name, _ in cfg.columns:
        spec = colreg.lookup(name)      # did-you-mean on unknown names
        if spec.field:
            raise ValueError(
                f"column {name!r} has a dedicated SimConfig field "
                f"{spec.field!r}; set that (or use with_columns)")
    for spec in colreg.COLUMNS.values():
        if not spec.numeric:
            continue
        vals = spec.raw_values(cfg)
        if any(v != v or v < 0 for v in vals):
            raise ValueError(f"SimConfig.{spec.axis} has a NaN/negative "
                             f"entry: {vals!r}")
        if spec.positive and any(v == 0 for v in vals):
            raise ValueError(f"SimConfig.{spec.axis} entries must be "
                             f"> 0, got {vals!r}")
    for name in ("big", "speed_cs", "speed_nc"):
        if len(getattr(cfg, name)) < cfg.n_cores:
            raise ValueError(f"SimConfig.{name} has "
                             f"{len(getattr(cfg, name))} entries for "
                             f"{cfg.n_cores} cores")
    if any(not 0 <= l < cfg.n_locks for l in cfg.seg_lock):
        raise ValueError(f"seg_lock ids must be in [0, {cfg.n_locks}), "
                         f"got {cfg.seg_lock!r}")


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Static simulator configuration (hashable -> usable as jit static arg).

    ``n_cores`` is the *padded* core count of the compiled executable; runs
    may activate fewer cores (``n_cores`` sweep axis / ``n_active`` param).
    Numeric fields that are sweepable (w_big, prop_n, default_window_us,
    long_epoch_*, wakeup_us, segment durations) are defaults only — they are
    canonicalized out of the jit key and traced, so two configs differing
    only in those share one executable.
    """

    policy: str = "fifo"
    # Merged multi-policy executable (docs/simulator.md §Fused step
    # kernel & multi-policy executables): a non-empty tuple of
    # registered policy names compiles ONE executable dispatching all
    # of them on the *traced* ``SimParams.pol_id`` — a policy x load
    # sweep grid then costs ~1 compilation instead of n_policies.
    # ``policy`` must be a member (it picks this run's id); results are
    # bit-identical to the single-policy executable (hooks are fully
    # conditional, so masked-off members commit nothing).  Usually set
    # via a ``"policy"`` sweep axis rather than by hand.
    policy_set: tuple = ()
    # Route the per-event fused step through the Pallas kernel
    # (repro.kernels.simstep) instead of the plain jnp/XLA lowering.
    # Bit-identical results either way (the kernel runs the same traced
    # step); _canon keeps the bit in the jit key (different lowering ->
    # different executable) but nothing else about sweep semantics
    # changes.  The kernel is interpreted only where it is lowered for the
    # CPU; on TPU, Mosaic refuses the step today and the compile raises.
    use_pallas: bool = False
    n_cores: int = 8
    big: tuple = (1, 1, 1, 1, 0, 0, 0, 0)          # 4 big + 4 little (M1)
    speed_cs: tuple = (1.0,) * 4 + (3.75,) * 4     # CS slowdown (Sysbench gap)
    speed_nc: tuple = (1.0,) * 4 + (1.8,) * 4      # non-CS slowdown (NOP gap)
    # Epoch program: S segments of (noncrit_us, cs_us, lock_id)
    seg_noncrit_us: tuple = (1.0,)
    seg_cs_us: tuple = (3.0,)
    seg_lock: tuple = (0,)
    inter_epoch_us: float = 5.0
    n_locks: int = 1
    # Key-sharded datastore mode (repro.workloads.keys, docs/
    # workloads.md §Key-sharded traffic): ``n_keys > 0`` switches every
    # epoch's lock from the static per-segment program (``seg_lock``) to
    # a per-(core, epoch) Zipf(``zipf_theta``)-drawn key bucketed over
    # the first ``n_locks`` locks (key % n_locks — rank-preserving, so
    # lock 0 is the hot bucket).  Only the on/off bit is jit-static; the
    # key count, exponent and active lock count ride traced in
    # SimParams, so ``n_keys`` / ``zipf_theta`` / ``n_locks`` sweep as
    # batch axes (``n_locks`` stays the padded state shape).
    n_keys: int = 0
    zipf_theta: float = 0.99      # YCSB-default skew; 0 = uniform
    pct: float = 99.0
    w_big: float = 1.0            # TAS affinity weight
    prop_n: int = 10              # proportional policy ratio
    default_window_us: float = 10.0
    max_window_us: float = 100_000.0   # 100 ms upper bound (starvation-free)
    sim_time_us: float = 100_000.0
    epcap: int = 8192             # latency ring size
    # Constant-memory streaming tail metrics (docs/simulator.md
    # §Streaming metrics).  ``hist`` is the single jit-static on/off bit:
    # when set, every epoch/CS latency sample is also scatter-added into
    # fixed-size log-bucketed ``u32[N, hist_buckets]`` histograms
    # (``SimState.ep_hist`` / ``cs_hist``), so tail percentiles stay
    # bounded-error at ANY run length — the ``f32[N, epcap]`` rings
    # silently overwrite history once a core retires > epcap samples.
    # ``hist_buckets`` is shape-static (like ``epcap``); the bucket
    # range [hist_lo_us, hist_hi_us) and the warmup cutoff ride traced
    # (SimTables / SimParams), so gate-off runs are bit-identical to
    # pre-histogram builds and bucket-range variants share executables.
    hist: bool = False
    hist_buckets: int = 512
    hist_lo_us: float = 0.1
    hist_hi_us: float = 1e6
    hist_warmup: int = 32         # samples/core skipped (match summarize)
    max_events: int = 5_000_000
    # Bench-3: heterogeneous epochs — with prob p the next epoch's
    # non-critical work is scale x longer (long request mixed with short).
    long_epoch_prob: float = 0.0
    long_epoch_scale: float = 100.0
    # Bench-6: blocking locks — FIFO handoff to a parked waiter pays a
    # wakeup latency; a standby grabbing a free lock (spinning) does not.
    wakeup_us: float = 0.0
    # Fault injection (repro.faults, docs/faults.md): lock-holder
    # preemption (the holder is descheduled mid-CS for an Exp(scale)
    # stall), core churn (during an "off" slot a core's acquire attempts
    # bounce to the next slot boundary — leave/rejoin on a schedule) and
    # straggler CS spikes (a critical section runs scale x long).  Only
    # the on/off bit of each rate is jit-static; the values ride traced
    # in SimParams, so preempt_rate / preempt_scale / churn_rate /
    # straggle_rate / straggle_scale sweep as batch axes.
    preempt_rate: float = 0.0
    preempt_scale_us: float = 50.0
    churn_rate: float = 0.0
    churn_period_us: float = 500.0
    straggle_rate: float = 0.0
    straggle_scale: float = 10.0
    # Per-core fault eligibility (1 = faults may hit this core; () ->
    # all eligible).  Rides traced in SimTables as a multiplier on the
    # fault rates, so it is a sweepable table axis and an all-zero mask
    # is bit-identical to a fault-free run.
    fault_mask: tuple = ()
    # DVFS + power model (repro.core.energy, docs/energy.md).  ``dvfs``
    # is the per-core frequency multiplier (() -> all 1.0): it divides
    # the host-built segment durations (a faster clock shortens work)
    # and rides traced so power can scale with f^3.  The four power
    # tables are per-core watts by phase (active CS / busy-wait spin /
    # parked in queue / idle); any non-empty table flips the single
    # static energy gate on (``_energy_on``) — the in-sim energy
    # integration only exists in the HLO when some power is modeled.
    # All five ride as registered SimTables columns (sweepable).
    dvfs: tuple = ()
    p_cs: tuple = ()
    p_spin: tuple = ()
    p_park: tuple = ()
    p_idle: tuple = ()
    # Stochastic workload model (repro.workloads.generators): per-epoch
    # think (arrival) and service-time scaling.  ``wl`` is the single
    # on/off jit-static bit (it gates whether the draws exist in the HLO
    # at all); every other field below is traced via SimParams, so
    # arrival_rate / cv / mix / burstiness sweep as batch axes.
    wl: bool = False
    wl_process: str = "poisson"   # ARRIVALS: closed|poisson|mmpp|diurnal
    wl_service: str = "det"       # SERVICES: det|exp|lognormal|bimodal
    # Open-loop mode (second static workload bit): arrivals are *events*
    # — each core runs an open queue whose requests arrive at absolute
    # times drawn from ``wl_process`` (mean gap = wl_rate x the closed-
    # loop think budget inter+noncrit), independent of completions, and
    # epoch latency is the full sojourn from arrival (queueing included).
    # Implies ``wl``; think-scaling is replaced by the pending-ARRIVAL
    # event (docs/workloads.md §Open-loop simlock).
    wl_open: bool = False
    # Per-core service-distribution override (multi-class tenants): a
    # SERVICES name per core, or None/"" to inherit ``wl_service``.
    # Rides as the traced ``wl_service`` id column of SimTables, so
    # mixed-shape cells share one executable (sweepable table axis).
    wl_service_per_core: tuple = ()
    wl_rate: float = 1.0          # offered load: mean think x= 1/rate
    wl_cv: float = 1.0            # lognormal service cv
    wl_mix: float = 0.0           # bimodal Get/Put long-mode probability
    wl_mix_scale: float = 10.0    # bimodal long/short ratio
    wl_burst: float = 1.0         # MMPP on/off rate ratio (1 = plain)
    wl_burst_len: float = 8.0     # mean epochs per MMPP phase
    wl_amp: float = 0.0           # diurnal ramp amplitude in [0,1)
    wl_period_us: float = 0.0     # diurnal period (0 -> sim_time_us)
    # Per-core SLO scale (multi-class tenancy; () -> all ones).  Rides
    # traced in SimTables, so mixed-tenant cells share one executable.
    slo_scale: tuple = ()
    # Policy-owned numeric knobs, as a hashable (name, value) tuple —
    # read by the registered policy's ``init_params`` into the traced
    # ``SimParams.pol`` dict (canonicalized out of the jit key), e.g.
    # ``policy_kw=(("shfl_bound", 8),)`` for the shfl policy.
    policy_kw: tuple = ()
    # Values for registered per-core columns that have NO dedicated
    # SimConfig field (plugin-owned columns, e.g. dvfs_race's
    # ``race_w``), as a hashable ((name, per-core tuple), ...) tuple.
    # Prefer ``with_columns(cfg, name=values)``, which routes dedicated-
    # field columns to their field and validates names (did-you-mean).
    columns: tuple = ()
    # Events retired per lax.scan chunk inside the outer while_loop
    # (amortizes the loop-condition check; results are chunk-invariant —
    # the live-guard in _step retires partial tails as no-ops).  128
    # measured best on CPU for both the single and the batched path.
    chunk: int = 128

    def __post_init__(self):
        _validate_config(self)

    @property
    def policy_id(self) -> int:
        return POLICIES[self.policy]


class SimTables(NamedTuple):
    """Per-program arrays, precomputed once and threaded through handlers
    (traced, so segment-duration sweeps share one executable)."""

    big: jnp.ndarray       # i32[N] 1 = big core
    cs_dur: jnp.ndarray    # i32[N,S] CS ticks per (core, segment)
    nc_dur: jnp.ndarray    # i32[N,S] non-CS ticks per (core, segment)
    inter: jnp.ndarray     # i32[N] inter-epoch ticks per core
    seg_lock: jnp.ndarray  # i32[S] lock id per segment
    # Streaming-histogram bucket layout (repro.core.stats.layout), the
    # host-precomputed log-spaced edge parameterization: log2 of the
    # lowest finite edge (ticks) and 1/log2 of the bucket growth factor.
    # Traced scalars — dead code unless ``cfg.hist`` (the static gate).
    hist_log2_lo: jnp.ndarray   # f32 log2(hist_lo_us * US)
    hist_inv_log2g: jnp.ndarray  # f32 1 / log2(g)
    # Registered per-core columns (repro.core.columns): every declared
    # ColumnSpec — the tenancy/fault/energy built-ins (slo_scale,
    # wl_service, ft_mask, dvfs, p_*) plus policy-owned ones — as
    # name -> [N] arrays.  Each is a sweepable table axis.
    col: dict


class SimParams(NamedTuple):
    """Per-run traced scalars — the sweepable batch axes."""

    slo: jnp.ndarray         # f32 ticks
    # Registry id of the policy THIS run dispatches (POLICIES[policy]).
    # Traced, so a merged multi-policy executable (cfg.policy_set)
    # selects each cell's member without recompiling; ignored by
    # single-policy executables (whose hooks never read it).
    pol_id: jnp.ndarray      # i32
    w_big: jnp.ndarray       # f32 TAS affinity weight
    prop_n: jnp.ndarray      # i32 proportional ratio
    n_active: jnp.ndarray    # i32 cores actually running (<= N padded)
    seed: jnp.ndarray        # i32 PRNG seed
    # Sim horizon in ticks.  Traced (a ``sim_time_us`` sweep axis), so
    # lanes of one batched executable may run *different* durations —
    # the step-utilization lever: a vmapped while_loop steps every lane
    # until the LAST one finishes, so giving low-rate lanes
    # proportionally longer horizons means each lane-step retires a
    # real event instead of a live-guard no-op.  Summaries normalize by
    # the cell's own final clock, so per-cell metrics are unaffected.
    horizon: jnp.ndarray     # i32 ticks
    long_prob: jnp.ndarray   # f32 long-epoch probability
    long_scale: jnp.ndarray  # f32 long-epoch noncrit scale
    wakeup: jnp.ndarray      # i32 parked-waiter handoff ticks
    # Initial AIMD additive unit (ticks).  Seeded from the *default*
    # window, NOT the carried windows0: a resumed run whose windows
    # collapsed to ~0 must keep a regrowth floor, or zero becomes an
    # absorbing state (window only ever shrinks).
    unit0: jnp.ndarray       # f32 ticks
    # Stochastic workload knobs (all traced; live ops only when cfg.wl)
    wl_process: jnp.ndarray   # i32 ARRIVALS id
    wl_service: jnp.ndarray   # i32 SERVICES id
    wl_rate: jnp.ndarray      # f32 offered-load scale
    wl_cv: jnp.ndarray        # f32 service cv
    wl_mix: jnp.ndarray       # f32 bimodal long-mode probability
    wl_mix_scale: jnp.ndarray  # f32 bimodal long/short ratio
    wl_burst: jnp.ndarray     # f32 MMPP on/off rate ratio
    wl_burst_len: jnp.ndarray  # f32 mean epochs per MMPP phase
    wl_amp: jnp.ndarray       # f32 diurnal amplitude
    wl_period: jnp.ndarray    # f32 diurnal period (ticks)
    # Fault-injection knobs (repro.faults; live ops only when the
    # matching cfg rate's static on/off bit is set)
    preempt_rate: jnp.ndarray    # f32 P(holder preempted) per CS
    preempt_scale: jnp.ndarray   # f32 mean stall (ticks)
    churn_rate: jnp.ndarray      # f32 P(core off) per churn slot
    churn_period: jnp.ndarray    # i32 churn slot length (ticks, >= 1)
    straggle_rate: jnp.ndarray   # f32 P(CS spike)
    straggle_scale: jnp.ndarray  # f32 CS spike multiplier
    # Key-sharded traffic (repro.workloads.keys; live ops only when
    # cfg.n_keys > 0, the static key-shard gate).  The three sampler
    # constants are host-precomputed per cell by zipf_consts — they are
    # pure functions of (ks_keys, ks_theta), carried traced so key-count
    # and exponent sweeps share one executable.
    ks_keys: jnp.ndarray     # i32 active key count
    ks_theta: jnp.ndarray    # f32 Zipf exponent (pole-nudged)
    ks_zeta: jnp.ndarray     # f32 harmonic H_{n,theta}
    ks_eta: jnp.ndarray      # f32 Gray/YCSB eta constant
    ks_alpha: jnp.ndarray    # f32 1/(1-theta)
    ks_locks: jnp.ndarray    # i32 active lock count (<= L padded)
    # Streaming-histogram warmup: per-core sample index below which a
    # sample is NOT bucketed (matches summarize's ring warmup, so ring
    # and histogram quantiles agree on un-wrapped runs).  Traced; dead
    # unless ``cfg.hist``.
    hist_warmup: jnp.ndarray  # i32
    # Policy-owned traced knobs (LockPolicy.init_params; {} for the
    # built-in four) — swept via the policy's declared sweep_axes.
    pol: dict


class SimState(NamedTuple):
    t: jnp.ndarray
    key: jnp.ndarray
    phase: jnp.ndarray        # i32[N]
    t_ready: jnp.ndarray      # i32[N]
    seg: jnp.ndarray          # i32[N]
    epoch_start: jnp.ndarray  # i32[N]
    attempt_t: jnp.ndarray    # i32[N]
    window: jnp.ndarray       # f32[N] (ticks)
    unit: jnp.ndarray         # f32[N]
    scale: jnp.ndarray        # f32[N] current epoch noncrit scale (Bench-3/wl)
    svc_scale: jnp.ndarray    # f32[N] current epoch CS scale (wl service)
    wl_on: jnp.ndarray        # i32[N] MMPP on/off phase bit (wl)
    q: jnp.ndarray            # i32[L,2,N] ring buffers (0=main/big, 1=little)
    q_head: jnp.ndarray       # i32[L,2]
    q_tail: jnp.ndarray       # i32[L,2]
    holder: jnp.ndarray       # i32[L]
    prop_ctr: jnp.ndarray     # i32[L]
    ep_lat: jnp.ndarray       # f32[N,EPCAP] epoch latencies (ticks)
    ep_cnt: jnp.ndarray       # i32[N]
    cs_lat: jnp.ndarray       # f32[N,EPCAP] acquire->release latencies
    cs_cnt: jnp.ndarray       # i32[N]
    events: jnp.ndarray       # i32
    arr_t: jnp.ndarray        # i32[N] next open-loop arrival (wl_open)
    energy: jnp.ndarray       # f32[N] accumulated energy (watt-ticks;
    #                           stays all-zero unless a power table is
    #                           set — the static _energy_on gate)
    cur_lock: jnp.ndarray     # i32[N] this epoch's key-drawn lock (all
    #                           zero unless cfg.n_keys > 0 — _ks_on)
    cur_rw: jnp.ndarray       # f32[N] this epoch's read/write uniform
    #                           (CREW policies; 1.0 = read when unused)
    # Constant-memory streaming latency histograms (cfg.hist gate):
    # log-bucketed u32 counts per metric family, merged across cores /
    # cells / shards / devices by plain summation.  Shape [N, 1] when
    # the gate is off (the leaves exist but stay empty and untouched).
    ep_hist: jnp.ndarray      # u32[N, B] epoch-latency counts
    cs_hist: jnp.ndarray      # u32[N, B] acquire->release counts
    # Policy-owned state slots (LockPolicy.init_state; {} for policies
    # that need none — e.g. shfl's per-lock shuffle counter).
    pol: dict


# --------------------------------------------------------------------------
# Static-arg canonicalization: every field that now rides in SimTables /
# SimParams is wiped from the jit key, so numeric variants share executables.
# --------------------------------------------------------------------------

def _canon(cfg: SimConfig) -> SimConfig:
    n, s = cfg.n_cores, len(cfg.seg_cs_us)
    return dataclasses.replace(
        cfg,
        # Merged mode: the member actually run rides traced in
        # SimParams.pol_id, so ``policy`` is wiped to the set's first
        # member — every cell of a policy sweep shares one executable.
        # (``policy_set`` itself stays: it fixes which handlers are in
        # the HLO.  ``use_pallas`` also stays: a different lowering is
        # a different executable, but never different results.)
        policy=cfg.policy_set[0] if cfg.policy_set else cfg.policy,
        big=(0,) * n, speed_cs=(1.0,) * n, speed_nc=(1.0,) * n,
        seg_noncrit_us=(0.0,) * s, seg_cs_us=(0.0,) * s, seg_lock=(0,) * s,
        inter_epoch_us=0.0, w_big=1.0, prop_n=1, default_window_us=0.0,
        # Only the on/off bit of the mix/wakeup/workload features is
        # static (it gates whether the RNG draw / handoff add exist in
        # the HLO at all); the actual values are traced.
        long_epoch_prob=1.0 if cfg.long_epoch_prob > 0.0 else 0.0,
        long_epoch_scale=1.0,
        wakeup_us=1.0 if cfg.wakeup_us > 0.0 else 0.0,
        wl=bool(cfg.wl or cfg.wl_open), wl_open=bool(cfg.wl_open),
        wl_process="poisson", wl_service="det",
        wl_rate=1.0, wl_cv=1.0, wl_mix=0.0, wl_mix_scale=1.0,
        wl_burst=1.0, wl_burst_len=1.0, wl_amp=0.0, wl_period_us=0.0,
        preempt_rate=1.0 if cfg.preempt_rate > 0.0 else 0.0,
        preempt_scale_us=1.0,
        churn_rate=1.0 if cfg.churn_rate > 0.0 else 0.0,
        churn_period_us=1.0,
        straggle_rate=1.0 if cfg.straggle_rate > 0.0 else 0.0,
        straggle_scale=1.0,
        # Key sharding: one static gate bit (do the per-epoch key draws
        # exist in the HLO?).  The canonical on-value is n_locks, not 1,
        # so the canonicalized config still satisfies the key-count >=
        # lock-count validation; the real count rides in SimParams.
        n_keys=cfg.n_locks if cfg.n_keys > 0 else 0,
        zipf_theta=0.0,
        slo_scale=(), wl_service_per_core=(), fault_mask=(),
        dvfs=(), columns=(),
        # Energy: one static on/off bit (whether the integration ops
        # exist in the HLO at all); the watt values ride in SimTables.
        p_cs=(0.0,) if _energy_on(cfg) else (),
        p_spin=(), p_park=(), p_idle=(),
        # Streaming histograms: ``hist`` is the static gate and
        # ``hist_buckets`` the static state shape (only meaningful when
        # on — wiped to the default otherwise so gate-off configs share
        # executables); the bucket range and warmup ride traced.
        hist_buckets=cfg.hist_buckets if cfg.hist else 512,
        hist_lo_us=1.0, hist_hi_us=2.0, hist_warmup=0,
        policy_kw=())


def _ks_on(cfg: SimConfig) -> bool:
    """The single static key-shard gate: are epochs' locks drawn from
    the Zipf key stream (vs the static segment program)?"""
    return cfg.n_keys > 0


def _energy_on(cfg: SimConfig) -> bool:
    """The single static energy gate: is any per-core power table set?
    (Zero-valued tables still flip it on — they compile the integration
    ops but accumulate exact zeros, which is what the zero-power
    bit-purity probe asserts.)"""
    return bool(cfg.p_cs or cfg.p_spin or cfg.p_park or cfg.p_idle)


def _active_policy(cfg: SimConfig):
    """The policy object the compiled step dispatches through: the
    registered singleton, or — merged mode — the cached
    :class:`~repro.core.policies.MergedPolicy` for ``cfg.policy_set``
    (hooks fan out over members masked on the traced pol_id)."""
    if cfg.policy_set:
        return policies.merged(cfg.policy_set)
    return policies.get(cfg.policy)


def _rw_draw_gate(cfg: SimConfig, pm) -> object:
    """Does THIS run consume the per-epoch read/write uniform?

    Single-policy configs return the policy's Python-literal
    ``uses_rw`` (HLO-preserving: the draw ops only exist when True).
    Merged sets return a traced mask over ``pm.pol_id`` so a non-rw
    cell (e.g. fifo) sharing an executable with ks_crew keeps
    ``cur_rw == 1.0`` bit-identically to its own executable."""
    if not cfg.policy_set:
        return policies.get(cfg.policy).uses_rw
    ids = _active_policy(cfg).rw_member_ids()
    if not ids:
        return False
    m = pm.pol_id == ids[0]
    for pid in ids[1:]:
        m = jnp.logical_or(m, pm.pol_id == pid)
    return m


def _and_gate(cond, gate):
    """cond AND a _rw_draw_gate result (which may be the Python literal
    True on the single-policy path — where the AND must vanish)."""
    return cond if gate is True else jnp.logical_and(cond, gate)


def build_tables(cfg: SimConfig) -> SimTables:
    """Precompute the per-(core, segment) duration tables once per run,
    as host arrays.

    Every registered :class:`~repro.core.columns.ColumnSpec` is
    materialized into ``SimTables.col`` — encoded, then padded with its
    *neutral default* (a short f32[k] table would be index-*clamped*
    inside jit, silently giving high cores the last entry's value).
    The ``dvfs`` column additionally divides the segment durations
    host-side (frequency scaling; f=1.0 is bitwise exact, so default-
    DVFS tables are bit-identical to pre-DVFS ones).  The inter-epoch
    gap is application pacing, not compute — it stays
    frequency-independent so DVFS sweeps change service capacity, not
    offered load."""
    n = cfg.n_cores
    s = len(cfg.seg_cs_us)
    f = colreg.COLUMNS["dvfs"].host_values(cfg, n)
    col = {spec.name: np.asarray(
        spec.host_values(cfg, n),
        np.int32 if spec.dtype == "i32" else np.float32)
        for spec in colreg.COLUMNS.values()}
    # Streaming-histogram edge parameterization, precomputed host-side
    # in TICKS (the unit latency samples are recorded in).  Always
    # materialized (two dead scalars when cfg.hist is off).
    h_log2_lo, h_inv_log2g = stats.layout(
        cfg.hist_lo_us * US, cfg.hist_hi_us * US, max(cfg.hist_buckets, 4))
    return SimTables(
        big=np.asarray(cfg.big[:n], np.int32),
        cs_dur=np.asarray(
            [[_ticks(cfg.seg_cs_us[j] * cfg.speed_cs[c] / f[c])
              for j in range(s)] for c in range(n)], np.int32),
        nc_dur=np.asarray(
            [[_ticks(cfg.seg_noncrit_us[j] * cfg.speed_nc[c] / f[c])
              for j in range(s)] for c in range(n)], np.int32),
        inter=np.asarray(
            [_ticks(cfg.inter_epoch_us * cfg.speed_nc[c]) for c in range(n)],
            np.int32),
        seg_lock=np.asarray(cfg.seg_lock, np.int32),
        hist_log2_lo=np.float32(h_log2_lo),
        hist_inv_log2g=np.float32(h_inv_log2g),
        col=col)


def table_columns(cfg: SimConfig) -> dict:
    """Host-side view of every registered column exactly as
    ``build_tables`` materializes it (encoded + padded), keyed by
    column name — the host-reconstruction counterpart of
    ``SimTables.col`` (pairs with ``generators.epoch_scale_tables``)."""
    return {spec.name: spec.host_values(cfg, cfg.n_cores)
            for spec in colreg.COLUMNS.values()}


def with_columns(cfg: SimConfig, **cols) -> SimConfig:
    """Set registered per-core columns on a config by *column name*:
    dedicated-field columns (``slo_scale``, ``fault_mask``, ``dvfs``,
    the power tables ...) route to their SimConfig field; plugin-owned
    columns land in the generic ``cfg.columns`` tuple.  Unknown names
    raise with a did-you-mean."""
    for name, vals in cols.items():
        spec = colreg.lookup(name)
        if spec.field:
            cfg = dataclasses.replace(cfg, **{spec.field: tuple(vals)})
        else:
            d = dict(cfg.columns)
            d[name] = tuple(vals)
            cfg = dataclasses.replace(cfg, columns=tuple(sorted(d.items())))
    return cfg


def build_params(cfg: SimConfig, slo_us, seed=0, n_active=None) -> SimParams:
    """SimParams from config defaults (each field is a sweep axis), as
    host scalars; a traced ``slo_us`` or ``seed`` stays traced."""
    pol_params = _active_policy(cfg).init_params(cfg)
    # Every policy_kw key must land in a traced pol slot — a typo'd knob
    # silently running with its default would be the one misconfiguration
    # here that doesn't raise.
    unknown = set(dict(cfg.policy_kw)) - set(pol_params)
    if unknown:
        raise ValueError(
            f"unknown policy_kw {sorted(unknown)} for policy "
            f"{cfg.policy!r}; known knobs: {sorted(pol_params)}")
    slo = (slo_us * US).astype(np.float32) if hasattr(slo_us, "astype") \
        else np.float32(_ticks(slo_us))
    ks_theta, ks_zeta, ks_eta, ks_alpha = _zipf_consts(cfg.n_keys,
                                                       cfg.zipf_theta)
    return SimParams(
        slo=slo,
        pol_id=np.int32(POLICIES[cfg.policy]),
        w_big=np.float32(cfg.w_big),
        prop_n=np.int32(cfg.prop_n),
        n_active=np.int32(cfg.n_cores if n_active is None else n_active),
        seed=np.int32(seed) if not hasattr(seed, "dtype")
        else seed.astype(np.int32),
        horizon=np.int32(_ticks(cfg.sim_time_us)),
        long_prob=np.float32(cfg.long_epoch_prob),
        long_scale=np.float32(cfg.long_epoch_scale),
        wakeup=np.int32(_ticks(cfg.wakeup_us)),
        unit0=np.float32(aimd.unit_for(_ticks(cfg.default_window_us),
                                       cfg.pct)),
        wl_process=np.int32(wlg.ARRIVALS[cfg.wl_process]),
        wl_service=np.int32(wlg.SERVICES[cfg.wl_service]),
        wl_rate=np.float32(cfg.wl_rate),
        wl_cv=np.float32(cfg.wl_cv),
        wl_mix=np.float32(cfg.wl_mix),
        wl_mix_scale=np.float32(cfg.wl_mix_scale),
        wl_burst=np.float32(cfg.wl_burst),
        wl_burst_len=np.float32(cfg.wl_burst_len),
        wl_amp=np.float32(cfg.wl_amp),
        wl_period=np.float32(_ticks(
            cfg.wl_period_us if cfg.wl_period_us > 0.0
            else cfg.sim_time_us)),
        preempt_rate=np.float32(cfg.preempt_rate),
        preempt_scale=np.float32(_ticks(cfg.preempt_scale_us)),
        churn_rate=np.float32(cfg.churn_rate),
        churn_period=np.int32(max(_ticks(cfg.churn_period_us), 1)),
        straggle_rate=np.float32(cfg.straggle_rate),
        straggle_scale=np.float32(cfg.straggle_scale),
        ks_keys=np.int32(cfg.n_keys),
        ks_theta=np.float32(ks_theta),
        ks_zeta=np.float32(ks_zeta),
        ks_eta=np.float32(ks_eta),
        ks_alpha=np.float32(ks_alpha),
        ks_locks=np.int32(cfg.n_locks),
        hist_warmup=np.int32(cfg.hist_warmup),
        pol=pol_params)


def _zipf_consts(n_keys: int, theta: float):
    """The Zipf sampler's host constants; with keys on, inside the open
    call's ``keys`` span (key-off params carry those of one key)."""
    if n_keys < 1:
        return wlk.zipf_consts(1, theta)
    with calllog.span("keys"):
        return wlk.zipf_consts(n_keys, theta)


def _default_windows(cfg: SimConfig) -> np.ndarray:
    return np.full(cfg.n_cores, _ticks(cfg.default_window_us), np.float32)


def _init_state(cfg: SimConfig, tb: SimTables, pm: SimParams,
                windows0) -> SimState:
    n, l, cap = cfg.n_cores, cfg.n_locks, cfg.epcap
    active = jnp.arange(n, dtype=jnp.int32) < pm.n_active
    # Stagger initial arrivals slightly so ties don't all collapse to core 0.
    stagger = jnp.arange(n, dtype=jnp.int32)
    windows0 = jnp.asarray(windows0, jnp.float32)
    if cfg.wl:
        # Epoch-0 workload draws — counter-based (pure in (seed, core, 0)),
        # so padded / batched / sharded runs see identical values.
        cores = jnp.arange(n, dtype=jnp.int32)
        u_t = jax.vmap(lambda c: wlg.epoch_think_u(pm.seed, c, 0))(cores)
        u_s, z_s = jax.vmap(
            lambda c: wlg.epoch_service_uz(pm.seed, c, 0))(cores)
        u_p = jax.vmap(lambda c: wlg.epoch_phase_u(pm.seed, c, 0))(cores)
        wl_on0 = (u_p < 0.5).astype(jnp.int32)
        think0 = wlg.think_gap(u_t, pm.wl_process, pm.wl_rate, wl_on0,
                               pm.wl_burst, 0.0, pm.wl_amp)
        svc0 = wlg.service_unit(u_s, z_s, _svc_dist(tb, pm), pm.wl_cv,
                                pm.wl_mix, pm.wl_mix_scale)
        scale0 = jnp.ones(n, jnp.float32) if cfg.wl_open else think0
        nc0 = (tb.nc_dur[:, 0].astype(jnp.float32)
               * scale0).astype(jnp.int32)
    else:
        wl_on0 = jnp.zeros(n, jnp.int32)
        think0 = scale0 = jnp.ones(n, jnp.float32)
        svc0 = jnp.ones(n, jnp.float32)
        nc0 = tb.nc_dur[:, 0]
    if cfg.wl_open:
        # Open-loop: every core starts parked on its pending-ARRIVAL
        # event.  Arrival 0 is drawn from the same think stream a
        # closed-loop run would consume (gap base = the closed-loop
        # think budget inter+noncrit); the stagger keeps clock ties off
        # core 0 exactly as in closed-loop mode.
        base = (tb.inter + tb.nc_dur[:, 0]).astype(jnp.float32)
        arr0 = jnp.maximum((base * think0).astype(jnp.int32), 1) + stagger
        phase0 = jnp.full(n, ARRIVAL, jnp.int32)
        ready0 = jnp.where(active, arr0, INF)
    else:
        arr0 = jnp.zeros(n, jnp.int32)
        phase0 = jnp.zeros(n, jnp.int32)
        ready0 = jnp.where(active, nc0 + stagger, INF)
    if _ks_on(cfg):
        # Epoch-0 key draws (repro.workloads.keys) — counter-pure in
        # (seed, core, 0) like every workload draw.  Open-loop runs
        # redraw index 0 at the first ARRIVAL event (same value).
        cores = jnp.arange(n, dtype=jnp.int32)
        cur_lock0 = jax.vmap(lambda c: wlk.epoch_lock(
            pm.seed, c, 0, pm.ks_keys, pm.ks_theta, pm.ks_zeta,
            pm.ks_eta, pm.ks_alpha, pm.ks_locks))(cores)
        gate = _rw_draw_gate(cfg, pm)
        if gate is False:
            cur_rw0 = jnp.ones(n, jnp.float32)
        else:
            draws = jax.vmap(
                lambda c: wlk.epoch_rw_u(pm.seed, c, 0))(cores)
            cur_rw0 = draws if gate is True else \
                jnp.where(gate, draws, jnp.ones(n, jnp.float32))
    else:
        cur_lock0 = jnp.zeros(n, jnp.int32)
        cur_rw0 = jnp.ones(n, jnp.float32)
    return SimState(
        t=jnp.int32(0),
        key=jax.random.PRNGKey(pm.seed),
        phase=phase0,
        t_ready=ready0,
        seg=jnp.zeros(n, jnp.int32),
        epoch_start=jnp.zeros(n, jnp.int32),
        attempt_t=jnp.zeros(n, jnp.int32),
        window=windows0,
        unit=jnp.full(n, pm.unit0, jnp.float32),
        q=jnp.full((l, 2, n), -1, jnp.int32),
        q_head=jnp.zeros((l, 2), jnp.int32),
        q_tail=jnp.zeros((l, 2), jnp.int32),
        holder=jnp.full(l, -1, jnp.int32),
        prop_ctr=jnp.zeros(l, jnp.int32),
        scale=scale0,
        svc_scale=svc0,
        wl_on=wl_on0,
        ep_lat=jnp.zeros((n, cap), jnp.float32),
        ep_cnt=jnp.zeros(n, jnp.int32),
        cs_lat=jnp.zeros((n, cap), jnp.float32),
        cs_cnt=jnp.zeros(n, jnp.int32),
        events=jnp.int32(0),
        arr_t=arr0,
        ep_hist=jnp.zeros((n, cfg.hist_buckets if cfg.hist else 1),
                          jnp.uint32),
        cs_hist=jnp.zeros((n, cfg.hist_buckets if cfg.hist else 1),
                          jnp.uint32),
        energy=jnp.zeros(n, jnp.float32),
        cur_lock=cur_lock0,
        cur_rw=cur_rw0,
        pol=_active_policy(cfg).init_state(cfg, tb, pm),
    )


def init_state(cfg: SimConfig, seed: int = 0, windows0=None) -> SimState:
    """Back-compat single-run initializer."""
    tb = build_tables(cfg)
    pm = build_params(cfg, 0.0, seed)
    w0 = _default_windows(cfg) if windows0 is None else windows0
    return _init_state(cfg, tb, pm, w0)


# --------------------------------------------------------------------------
# Event handlers.
#
# Every handler is *fully conditional*: it takes a ``cond`` and commits no
# state when it is false.  The single-run path dispatches via ``lax.switch``
# with ``cond=True`` (the masks constant-fold away, so it pays nothing);
# the batched sweep path applies all handlers as one branchless masked step
# so ``vmap`` lowers to in-place batched scatters instead of
# select-over-every-branch full-state copies.
# ``cond`` must only be combined via logical_and/where (it may be the
# Python literal True on the switch path).
#
# Policy decisions live in repro.core.policies plugins; the handlers here
# are policy-agnostic (they dispatch the registry hooks — no policy-name
# branches).  Queue/grant/pick helpers are shared with the policies via
# repro.core.policies.base (re-exported above under their old names).
# --------------------------------------------------------------------------

def _svc_dist(tb: SimTables, pm: SimParams, c=None):
    """Effective SERVICES id: the per-core table override (multi-class
    tenants), falling back to the run-wide traced id."""
    per_core = tb.col["wl_service"] if c is None else tb.col["wl_service"][c]
    return jnp.where(per_core >= 0, per_core, pm.wl_service)


def _power_draw(tb: SimTables, pm: SimParams, st: SimState):
    """Per-core instantaneous watts from phase + DVFS state: compute
    (NONCRIT/HOLDER) and busy-wait (SPIN/STANDBY) draws scale with
    dvfs^3 (P_dyn ~ f^3, the DVFS cube law); parked (QUEUED) and idle
    (ARRIVAL wait) are frequency-independent floor draws.  Inactive
    padded cores draw idle power."""
    ph = st.phase
    f3 = tb.col["dvfs"] ** 3
    p = jnp.where(
        jnp.logical_or(ph == NONCRIT, ph == HOLDER), tb.col["p_cs"] * f3,
        jnp.where(jnp.logical_or(ph == SPIN, ph == STANDBY),
                  tb.col["p_spin"] * f3,
                  jnp.where(ph == QUEUED, tb.col["p_park"],
                            tb.col["p_idle"])))
    active = jnp.arange(ph.shape[0], dtype=jnp.int32) < pm.n_active
    return jnp.where(active, p, tb.col["p_idle"])


def _handle_acquire(st: SimState, cfg: SimConfig, tb: SimTables,
                    pm: SimParams, c, t, cond) -> SimState:
    """A core's non-critical section ended: record the attempt time and
    let the policy decide grab / queue / standby / spin."""
    if cfg.churn_rate > 0.0:
        # Core churn: during an "off" slot the core is descheduled — the
        # acquire attempt bounces to the next slot boundary (strictly
        # future, so churn can never deadlock) and the policy never sees
        # it.  One counter-pure decision per (core, slot); the rate is
        # multiplied by the per-core eligibility mask so an ineligible
        # core (or rate 0) is bit-identical to fault-free.
        off = flt.churn_off(pm.seed, c, t,
                            pm.churn_rate * tb.col["ft_mask"][c],
                            pm.churn_period)
        bounce = jnp.logical_and(cond, off)
        st = st._replace(t_ready=st.t_ready.at[c].set(
            jnp.where(bounce, flt.churn_rejoin(t, pm.churn_period),
                      st.t_ready[c])))
        cond = jnp.logical_and(cond, jnp.logical_not(off))
    st = st._replace(attempt_t=st.attempt_t.at[c].set(
        jnp.where(cond, t, st.attempt_t[c])))
    return _active_policy(cfg).on_acquire(st, cfg, tb, pm, c, t, cond)


def _record(buf, cnt, c, value, cond):
    cap = buf.shape[1]
    pos = cnt[c] % cap
    val = jnp.where(cond, value, buf[c, pos])
    return buf.at[c, pos].set(val), cnt.at[c].add(jnp.where(cond, 1, 0))


def _hist_record(hist, tb: SimTables, c, value, cond):
    """Scatter one latency sample (ticks) into core ``c``'s log-bucketed
    histogram row: one log2, one clipped floor, one masked add — fully
    conditional like every handler op (``cond`` False commits nothing).
    Bucket layout lives in repro.core.stats; the two edge scalars are
    host-precomputed in SimTables."""
    nb = hist.shape[1]
    lg = (jnp.log2(jnp.maximum(value, jnp.float32(1e-6)))
          - tb.hist_log2_lo) * tb.hist_inv_log2g
    idx = jnp.clip(1 + jnp.floor(lg).astype(jnp.int32), 0, nb - 1)
    return hist.at[c, idx].add(
        jnp.where(cond, jnp.uint32(1), jnp.uint32(0)))


def _handle_arrival(st: SimState, cfg: SimConfig, tb: SimTables,
                    pm: SimParams, c, t, cond) -> SimState:
    """Open-loop mode (``wl_open``): the pending-ARRIVAL event fired.

    Begin the epoch at its *true* arrival time ``arr_t[c]`` (which may be
    in the past when the core is backlogged — epoch latency then includes
    the queueing delay, the open-loop load-latency knee), and draw the
    next arrival gap from the workload's arrival process.  Draws are
    counter-pure in (seed, core, arrival index), so sweeps, sharding and
    event interleaving cannot perturb the arrival stream."""
    a = st.arr_t[c]
    nxt_ix = st.ep_cnt[c] + 1          # arrivals consumed so far + 1
    u_t = wlg.epoch_think_u(pm.seed, c, nxt_ix)
    u_p = wlg.epoch_phase_u(pm.seed, c, nxt_ix)
    on = wlg.phase_flip(u_p, st.wl_on[c], pm.wl_burst_len)
    phase01 = jnp.mod(t.astype(jnp.float32)
                      / jnp.maximum(pm.wl_period, 1.0), 1.0)
    gap = wlg.think_gap(u_t, pm.wl_process, pm.wl_rate, on,
                        pm.wl_burst, phase01, pm.wl_amp)
    base = (tb.inter[c] + tb.nc_dur[c, 0]).astype(jnp.float32)
    nxt = a + jnp.maximum((base * gap).astype(jnp.int32), 1)
    nc0 = (tb.nc_dur[c, 0].astype(jnp.float32)
           * st.scale[c]).astype(jnp.int32)
    if _ks_on(cfg):
        # The epoch starting at this arrival touches key index
        # ep_cnt[c] (arrival i begins epoch i) — counter-pure, so the
        # key stream is independent of backlog and event interleaving.
        ep = st.ep_cnt[c]
        lk = wlk.epoch_lock(pm.seed, c, ep, pm.ks_keys, pm.ks_theta,
                            pm.ks_zeta, pm.ks_eta, pm.ks_alpha,
                            pm.ks_locks)
        st = st._replace(cur_lock=st.cur_lock.at[c].set(
            jnp.where(cond, lk, st.cur_lock[c])))
        gate = _rw_draw_gate(cfg, pm)
        if gate is not False:
            rw = wlk.epoch_rw_u(pm.seed, c, ep)
            st = st._replace(cur_rw=st.cur_rw.at[c].set(
                jnp.where(_and_gate(cond, gate), rw, st.cur_rw[c])))
    return st._replace(
        arr_t=st.arr_t.at[c].set(jnp.where(cond, nxt, st.arr_t[c])),
        wl_on=st.wl_on.at[c].set(jnp.where(cond, on, st.wl_on[c])),
        epoch_start=st.epoch_start.at[c].set(
            jnp.where(cond, a, st.epoch_start[c])),
        phase=st.phase.at[c].set(jnp.where(cond, NONCRIT, st.phase[c])),
        t_ready=st.t_ready.at[c].set(
            jnp.where(cond, t + nc0, st.t_ready[c])))


def _handle_release(st: SimState, cfg: SimConfig, tb: SimTables,
                    pm: SimParams, c, t, cond) -> SimState:
    pol = _active_policy(cfg)
    s = st.seg[c]
    l = _lock_of(st, cfg, tb, c)    # key-drawn lock when _ks_on, else
    n_seg = len(cfg.seg_cs_us)      # the static segment program's

    # acquire->release latency (paper Figure 1 metric)
    cs_latency = (t - st.attempt_t[c]).astype(jnp.float32)
    if cfg.hist:
        # Streaming histogram (pre-increment count = this sample's
        # index; gated on the traced warmup so histogram and ring
        # quantiles agree on un-wrapped runs).
        st = st._replace(cs_hist=_hist_record(
            st.cs_hist, tb, c, cs_latency,
            jnp.logical_and(cond, st.cs_cnt[c] >= pm.hist_warmup)))
    cs_lat, cs_cnt = _record(st.cs_lat, st.cs_cnt, c, cs_latency, cond)
    st = st._replace(cs_lat=cs_lat, cs_cnt=cs_cnt)

    last = s == n_seg - 1
    # Epoch end: record latency; the policy runs its feedback (e.g.
    # LibASL's AIMD window update — little cores only).
    ep_latency = (t - st.epoch_start[c]).astype(jnp.float32)
    ep_cond = jnp.logical_and(last, cond)
    if cfg.hist:
        st = st._replace(ep_hist=_hist_record(
            st.ep_hist, tb, c, ep_latency,
            jnp.logical_and(ep_cond, st.ep_cnt[c] >= pm.hist_warmup)))
    ep_lat, ep_cnt = _record(st.ep_lat, st.ep_cnt, c, ep_latency, ep_cond)
    st = st._replace(ep_lat=ep_lat, ep_cnt=ep_cnt)

    st = pol.on_release(st, cfg, tb, pm, c, t, ep_latency, last, cond)

    # Sample the next epoch's workload: the Bench-3 long-epoch mix and/or
    # the repro.workloads stochastic model.  Both are statically gated on
    # their canonicalized on/off bits — the RNG draws only exist in the
    # HLO when the feature is enabled; all values are traced (sweepable).
    new_scale = None
    if cfg.long_epoch_prob > 0.0:
        key, sub = jax.random.split(st.key)
        u = jax.random.uniform(sub)
        new_scale = jnp.where(u < pm.long_prob, pm.long_scale,
                              jnp.float32(1.0))
        st = st._replace(key=jnp.where(cond, key, st.key))
    if cfg.wl:
        # Counter-based draws (repro.workloads.generators): pure in
        # (seed, core, epoch-index), so batching/sharding/event order
        # cannot perturb the workload, and the host can reconstruct it
        # (generators.epoch_scale_tables).  st.ep_cnt[c] was already
        # bumped above, so it is the *next* epoch's index.
        ep = st.ep_cnt[c]
        u_s, z_s = wlg.epoch_service_uz(pm.seed, c, ep)
        svc = wlg.service_unit(u_s, z_s, _svc_dist(tb, pm, c), pm.wl_cv,
                               pm.wl_mix, pm.wl_mix_scale)
        upd = jnp.logical_and(last, cond)
        st = st._replace(svc_scale=st.svc_scale.at[c].set(
            jnp.where(upd, svc, st.svc_scale[c])))
        if not cfg.wl_open:
            # Closed loop: the think draw scales the next epoch's
            # non-critical segments.  (Open loop consumes the think
            # stream in _handle_arrival instead — as arrival gaps.)
            u_t = wlg.epoch_think_u(pm.seed, c, ep)
            u_p = wlg.epoch_phase_u(pm.seed, c, ep)
            on = wlg.phase_flip(u_p, st.wl_on[c], pm.wl_burst_len)
            phase01 = jnp.mod(t.astype(jnp.float32)
                              / jnp.maximum(pm.wl_period, 1.0), 1.0)
            think = wlg.think_gap(u_t, pm.wl_process, pm.wl_rate, on,
                                  pm.wl_burst, phase01, pm.wl_amp)
            new_scale = think if new_scale is None else new_scale * think
            st = st._replace(
                wl_on=st.wl_on.at[c].set(jnp.where(upd, on, st.wl_on[c])))
    if new_scale is not None:
        scale_c = jnp.where(jnp.logical_and(last, cond), new_scale,
                            st.scale[c])
        st = st._replace(scale=st.scale.at[c].set(scale_c))

        def _sc(d):
            return (d.astype(jnp.float32) * scale_c).astype(jnp.int32)
    else:
        def _sc(d):
            return d

    if _ks_on(cfg) and not cfg.wl_open:
        # Closed loop: draw the NEXT epoch's key at epoch end (ep_cnt
        # was bumped above, so it is the next epoch's index; epoch 0 was
        # drawn in _init_state).  Open loop draws at the true arrival in
        # _handle_arrival instead.  Updating cur_lock here is safe: the
        # releaser's old lock ``l`` was captured above, and the waiter
        # scans in pick_next never include the releaser (it is not
        # parked).
        ep = st.ep_cnt[c]
        upd = jnp.logical_and(last, cond)
        lk = wlk.epoch_lock(pm.seed, c, ep, pm.ks_keys, pm.ks_theta,
                            pm.ks_zeta, pm.ks_eta, pm.ks_alpha,
                            pm.ks_locks)
        st = st._replace(cur_lock=st.cur_lock.at[c].set(
            jnp.where(upd, lk, st.cur_lock[c])))
        gate = _rw_draw_gate(cfg, pm)
        if gate is not False:
            rw = wlk.epoch_rw_u(pm.seed, c, ep)
            st = st._replace(cur_rw=st.cur_rw.at[c].set(
                jnp.where(_and_gate(upd, gate), rw, st.cur_rw[c])))

    # Advance the program: next segment, or — epoch done — the closed-loop
    # think gap (inter-epoch + segment-0 noncrit), or the open-loop
    # pending-ARRIVAL event at the next arrival (possibly already past).
    s_next = jnp.where(last, 0, s + 1)
    mid_ready = t + _sc(tb.nc_dur[c, jnp.minimum(s + 1, n_seg - 1)])
    if cfg.wl_open:
        ep_start_next = st.epoch_start[c]      # set by _handle_arrival
        ready = jnp.where(last, jnp.maximum(t, st.arr_t[c]), mid_ready)
        phase_next = jnp.where(last, ARRIVAL, NONCRIT)
    else:
        ep_start_next = jnp.where(last, t + _sc(tb.inter[c]),
                                  st.epoch_start[c])
        ready = jnp.where(last,
                          t + _sc(tb.inter[c]) + _sc(tb.nc_dur[c, 0]),
                          mid_ready)
        phase_next = jnp.int32(NONCRIT)
    st = st._replace(
        seg=st.seg.at[c].set(jnp.where(cond, s_next, st.seg[c])),
        epoch_start=st.epoch_start.at[c].set(
            jnp.where(cond, ep_start_next, st.epoch_start[c])),
        phase=st.phase.at[c].set(jnp.where(cond, phase_next, st.phase[c])),
        t_ready=st.t_ready.at[c].set(jnp.where(cond, ready, st.t_ready[c])))

    # Hand the lock over.
    st = st._replace(holder=st.holder.at[l].set(
        jnp.where(cond, -1, st.holder[l])))
    return pol.pick_next(st, cfg, tb, pm, l, t, cond)


# --------------------------------------------------------------------------
# Main loop
# --------------------------------------------------------------------------

def _dispatch_table(cfg: SimConfig):
    """Phase id -> handler, built per trace from the registry policy.

    The table is the single source of event dispatch for both step modes:
    phases a config cannot reach (STANDBY without ``uses_standby``,
    ARRIVAL without ``wl_open``) are simply absent, so their handlers
    never enter the compiled HLO."""
    pol = _active_policy(cfg)
    table = [(NONCRIT, _handle_acquire), (HOLDER, _handle_release)]
    if pol.uses_standby:
        table.append((STANDBY, lambda st, cfg, tb, pm, c, t, cond:
                      pol.on_standby_expiry(st, cfg, tb, pm, c, t, cond)))
    if cfg.wl_open:
        table.append((ARRIVAL, _handle_arrival))
    return table


# Phase id -> the name scope its handler's device ops carry.
_HANDLER_SCOPES = {NONCRIT: "simlock/acquire", HOLDER: "simlock/release",
                   STANDBY: "simlock/standby", ARRIVAL: "simlock/arrival"}


def _step(cfg: SimConfig, tb: SimTables, pm: SimParams, horizon,
          st: SimState, masked: bool) -> SimState:
    """One event — or nothing, when the run is already past its horizon
    (`live` guard: lets a fixed-size scan chunk retire a partial tail).

    ``masked=False``: dispatch one handler via ``lax.switch`` (cheapest for
    a single run).  ``masked=True``: apply every handler under its phase
    mask — branchless, so a ``vmap`` over sweep lanes lowers to batched
    in-place scatters instead of per-branch full-state selects."""
    c = jnp.argmin(st.t_ready).astype(jnp.int32)
    t = st.t_ready[c]                       # == min(t_ready)
    live = jnp.logical_and(t < horizon, st.events < cfg.max_events)
    if _energy_on(cfg):
        # Energy integrates exactly over global time: this event
        # advances the clock st.t -> t, and every core spends that dt
        # in its *current* phase.  The update is passive (reads state,
        # perturbs nothing downstream) and statically gated, so
        # power-free runs compile no energy ops and zero-power runs
        # accumulate exact zeros.
        dt = jnp.where(live, (t - st.t).astype(jnp.float32),
                       jnp.float32(0.0))
        st = st._replace(energy=st.energy + dt * _power_draw(tb, pm, st))
    st = st._replace(t=jnp.where(live, t, st.t),
                     events=st.events + jnp.where(live, 1, 0))
    table = _dispatch_table(cfg)

    # Each handler's device ops carry its name scope (``simlock/acquire``
    # ...) in both lowerings: a profiler trace then says which handler
    # the loop's time went to.  Metadata only.
    if masked:
        ph = st.phase[c]
        for phase, fn in table:
            with jax.named_scope(_HANDLER_SCOPES[phase]):
                st = fn(st, cfg, tb, pm, c, t,
                        jnp.logical_and(live, ph == phase))
        # QUEUED/SPIN at the head of the clock: defensive re-park.
        with jax.named_scope("simlock/park"):
            park = jnp.logical_and(live,
                                   jnp.logical_or(ph == QUEUED, ph == SPIN))
            return st._replace(t_ready=st.t_ready.at[c].set(
                jnp.where(park, INF, st.t_ready[c])))

    def noop(s):
        with jax.named_scope("simlock/park"):
            return s._replace(t_ready=s.t_ready.at[c].set(INF))

    def dead(s):
        return s

    def bind(phase, fn):
        def branch(s):
            with jax.named_scope(_HANDLER_SCOPES[phase]):
                return fn(s, cfg, tb, pm, c, t, True)
        return branch

    by_phase = dict(table)
    n_phases = ARRIVAL + 1
    branches = [bind(p, by_phase[p]) if p in by_phase else noop
                for p in range(n_phases)] + [dead]
    branch = jnp.where(live, st.phase[c], n_phases)
    return jax.lax.switch(branch, branches, st)


def _simulate(cfg: SimConfig, tb: SimTables, pm: SimParams,
              windows0, masked: bool = False) -> SimState:
    st = _init_state(cfg, tb, pm, windows0)
    horizon = pm.horizon

    def cond(s):
        return jnp.logical_and(jnp.min(s.t_ready) < horizon,
                               s.events < cfg.max_events)

    if cfg.use_pallas:
        # Fused path (repro.kernels.simstep): the whole chunk retires
        # inside one Pallas kernel with the packed state VMEM-resident.
        # Same _step closure -> bit-identical to the jnp body below
        # wherever it compiles (interpret mode on the CPU only).
        from repro.kernels import simstep

        def body(s):
            return simstep.fused_chunk(
                lambda t_, p_, s_: _step(cfg, t_, p_, horizon, s_, masked),
                tb, pm, s, cfg.chunk)

        return jax.lax.while_loop(cond, body, st)

    def body(s):
        def chunk_step(s, _):
            return _step(cfg, tb, pm, horizon, s, masked), None
        return jax.lax.scan(chunk_step, s, None, length=max(cfg.chunk, 1))[0]

    return jax.lax.while_loop(cond, body, st)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
def _run_single(ccfg: SimConfig, tb: SimTables, pm: SimParams, windows0):
    return _simulate(ccfg, tb, pm, windows0, masked=False)


# --------------------------------------------------------------------------
# Batched executables: AOT-compiled (lower -> compile -> call) instead of a
# plain jit so every executable's collective schedule (nonzero only for
# mesh-sharded sweeps) is read off the compiled HLO at compile time.
# Cache key = (canon cfg, arg shapes/dtypes/shardings): the same one-
# executable-per-(policy, program) discipline as the jit it replaces.
# --------------------------------------------------------------------------

_BATCH_EXECS: dict = {}          # key -> (compiled, record)
_BATCH_LOCK = calllog.LOCK       # dict and call-log access; compiles overlap
_EXE_ORDER = itertools.count()   # compile order of the simulator's programs
_RUN_EXES: dict = {}             # canon cfg -> its _run_single's place


def _leaf_sig(x):
    sh = x.sharding if isinstance(x, jax.Array) else None
    return (tuple(x.shape), jnp.dtype(x.dtype).name, sh)


def _batched(ccfg: SimConfig):
    """The sweep program: ``(tb, pm, windows0) -> state``, every leaf with
    a leading sweep-cell axis.  The masked (branchless) step keeps the
    vmap scatter-shaped — a vmapped ``lax.switch`` would select over every
    branch's full state."""
    return jax.vmap(lambda a, b, c: _simulate(ccfg, a, b, c, masked=True))


def _batch_executable(ccfg: SimConfig, tb: SimTables, pm: SimParams,
                      windows0):
    """``(compiled, record, hit)`` for these inputs; a miss compiles inside
    a ``compile`` span.  The record is the executable's: ``exe`` (its place
    in compile order), ``collectives``, ``n_cells`` and ``devices``."""
    key = (ccfg, tuple(_leaf_sig(x)
                       for x in jax.tree.leaves((tb, pm, windows0))))
    with _BATCH_LOCK:
        found = _BATCH_EXECS.get(key)
    if found is not None:
        return found + (True,)
    with calllog.span("compile"):
        # NO donation here (unlike _run_single, where bench2's window
        # carry makes it worth it): the windows0 buffer is tiny, and
        # donating it lets the output `window` leaf alias an input whose
        # host memory XLA CPU occasionally reuses while a *different*
        # executable (e.g. a mesh-sharded sweep) runs concurrently —
        # observed as flaky single-leaf corruption of async results.
        compiled = jax.jit(_batched(ccfg)).lower(tb, pm, windows0).compile()
        rec = {"collectives": collective_stats(compiled.as_text()),
               "n_cells": int(np.shape(pm.slo)[0]),
               "devices": max((x.sharding.num_devices
                               for x in jax.tree.leaves((tb, pm, windows0))
                               if isinstance(x, jax.Array)), default=1)}
        with _BATCH_LOCK:
            if key not in _BATCH_EXECS:
                rec["exe"] = next(_EXE_ORDER)
                _BATCH_EXECS[key] = (compiled, rec)
            return _BATCH_EXECS[key] + (False,)


def _call_batch(ccfg: SimConfig, tb: SimTables, pm: SimParams, windows0):
    """Run the batched executable inside a ``dispatch`` span and note it
    in the open call's record."""
    with calllog.span("dispatch"):
        compiled, erec, hit = _batch_executable(ccfg, tb, pm, windows0)
        st = compiled(tb, pm, windows0)
    calllog.current().update(erec, hit=hit)
    return st


def n_batch_executables() -> int:
    """Distinct batched-sweep executables compiled so far (perf protocol:
    fig1's 24 cells must stay at 3 — one per policy)."""
    return len(_BATCH_EXECS)


def executable_records() -> list:
    """Per-executable records in compile order: the place in compile
    order, the collective schedule (nonzero only for mesh-sharded sweeps),
    cell count and device count."""
    with _BATCH_LOCK:
        return [rec for _, rec in _BATCH_EXECS.values()]


MAX_SWEEP_LOG = calllog.MAX_RECORDS


def sweep_log() -> list:
    """The call log, oldest first: one record per :func:`sweep` call (per
    computed slice on the resumable path), :func:`run`,
    :func:`sweep_summaries` and :func:`summarize` of device state, with
    the seconds of each phase, compile seconds and input arrays
    (:mod:`repro.core.calllog`; docs/simulator.md §Observing a run).  A
    sweep's record also holds its executable's ``devices``, ``n_cells`` and
    ``collectives``.  Holds the most recent ``MAX_SWEEP_LOG`` records; each
    has its ``seq``, the call's place in the process."""
    return calllog.records()


def _place(inputs, where=None):
    """Put a call's host inputs ``(tb, pm, windows0)`` on the device in one
    transfer (onto ``where``, a sharding, when given) and count the placed
    leaves in the open call's record.  Inputs that hold a tracer (a traced
    ``slo_us`` or ``seed`` of :func:`run`) are left to the caller's trace."""
    leaves = jax.tree.leaves(inputs)
    calllog.current()["arrays"] = len(leaves)
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return inputs
    return jax.device_put(inputs, where)


def run(cfg: SimConfig, slo_us, seed=0, windows0=None) -> SimState:
    """Run one simulation; slo_us/seed may be traced scalars.
    ``windows0`` carries AIMD state across phases (Bench-2) and is DONATED —
    pass a fresh array (reuse the returned ``state.window`` instead)."""
    with calllog.call("run") as rec:
        with calllog.span("build"):
            if windows0 is None:
                w0 = _default_windows(cfg)
            elif isinstance(windows0, jax.Array):   # donated as it is
                w0 = jnp.asarray(windows0, jnp.float32)
            else:
                w0 = np.asarray(windows0, np.float32)
            tb, pm, w0 = _place(
                (build_tables(cfg), build_params(cfg, slo_us, seed), w0))
            ccfg = _canon(cfg)
        # The first call of a program compiles inside its dispatch.
        with calllog.span("dispatch"):
            n0 = _run_single._cache_size()
            st = _run_single(ccfg, tb, pm, w0)
            hit = _run_single._cache_size() == n0
        if not hit:
            _RUN_EXES[ccfg] = next(_EXE_ORDER)
        rec.update(exe=_RUN_EXES.get(ccfg), hit=hit)
    return st


# --------------------------------------------------------------------------
# Batched sweeps: one compiled executable for a whole figure
# --------------------------------------------------------------------------

# axis name -> SimParams field (values in natural units; converted below)
_PARAM_AXES = {
    "slo_us": "slo",
    "w_big": "w_big",
    "prop_n": "prop_n",
    "seed": "seed",
    "n_cores": "n_active",
    "long_epoch_prob": "long_prob",
    "long_epoch_scale": "long_scale",
    "wakeup_us": "wakeup",
    # Stochastic workload axes (repro.workloads; require cfg.wl — sweep()
    # flips the static bit on automatically when one is present)
    "arrival_rate": "wl_rate",
    "cv": "wl_cv",
    "mix": "wl_mix",
    "mix_scale": "wl_mix_scale",
    "burstiness": "wl_burst",
    "burst_len": "wl_burst_len",
    # Fault-injection axes (repro.faults; sweep() flips the matching
    # static rate gate on when the axis has a nonzero value)
    "preempt_rate": "preempt_rate",
    "preempt_scale": "preempt_scale",
    "churn_rate": "churn_rate",
    "straggle_rate": "straggle_rate",
    "straggle_scale": "straggle_scale",
    # Key-sharded datastore axes (repro.workloads.keys; require
    # cfg.n_keys > 0 — sweep() flips the static gate on automatically
    # when the n_keys axis is present).  n_locks cells run against the
    # padded cfg.n_locks vectors with the effective count traced in
    # SimParams.ks_locks, mirroring the n_cores active-mask trick.
    "n_keys": "ks_keys",
    "zipf_theta": "ks_theta",
    "n_locks": "ks_locks",
}
_WL_AXES = ("arrival_rate", "cv", "mix", "mix_scale", "burstiness",
            "burst_len")
_KS_AXES = ("n_keys", "zipf_theta", "n_locks")
# Statically-gated features: sweeping the axis must flip the gate field
# on in the template config (the on/off bit is part of the jit key).
_GATE_AXES = ("long_epoch_prob", "wakeup_us", "preempt_rate",
              "churn_rate", "straggle_rate")
# Program axes: SimConfig fields rebuilt through build_tables per cell.
_PROGRAM_AXES = ("seg_noncrit_us", "seg_cs_us", "seg_lock",
                 "inter_epoch_us", "big", "speed_cs", "speed_nc")


def table_axes() -> tuple:
    """Axes that rebuild ``SimTables`` per cell (still one executable):
    the program axes plus every *registered* sweepable column's axis
    name (repro.core.columns) — recomputed so late-registered plugin
    columns sweep without touching the engine."""
    return _PROGRAM_AXES + tuple(colreg.axis_to_spec())


def _sweepable() -> tuple:
    # "policy" is the merged-executable axis: string-valued, dispatched
    # on the traced SimParams.pol_id (sweep() builds the policy_set).
    # "sim_time_us" rides traced in SimParams.horizon — per-cell
    # durations inside one executable (the step-utilization lever).
    return tuple(_PARAM_AXES) + table_axes() + (
        "window0_us", "policy", "sim_time_us")


# Import-time snapshot for docs/introspection; sweep() itself recomputes.
SWEEPABLE = _sweepable()


def sweepable_axes(cfg: SimConfig) -> tuple:
    """All sweep axes valid for ``cfg`` — the engine's plus the
    registered policy's declared ``sweep_axes``."""
    base = _sweepable()
    return base + tuple(
        a for a in _active_policy(cfg).sweep_axes if a not in base)


def _cell_tables_cfg(cfg: SimConfig, cell: dict, table_keys) -> SimConfig:
    """Apply a cell's table-axis values onto the template config:
    program axes replace their field directly; column axes route
    through ``with_columns`` (field-backed or plugin-owned alike)."""
    by_axis = colreg.axis_to_spec()
    for k in table_keys:
        if k in _PROGRAM_AXES:
            cfg = dataclasses.replace(cfg, **{k: cell[k]})
        else:
            v = cell[k]
            cfg = with_columns(cfg, **{by_axis[k].name: tuple(v)})
    return cfg


def _cell_params(cfg: SimConfig, cell: dict, slo_us, seed) -> SimParams:
    pm = build_params(cfg, cell.get("slo_us", slo_us),
                      cell.get("seed", seed),
                      n_active=cell.get("n_cores", cfg.n_cores))
    if "policy" in cell:
        pm = pm._replace(pol_id=np.int32(POLICIES[cell["policy"]]))
    if "sim_time_us" in cell:
        pm = pm._replace(horizon=np.int32(_ticks(cell["sim_time_us"])))
    if "w_big" in cell:
        pm = pm._replace(w_big=np.float32(cell["w_big"]))
    if "prop_n" in cell:
        pm = pm._replace(prop_n=np.int32(cell["prop_n"]))
    if "long_epoch_prob" in cell:
        pm = pm._replace(long_prob=np.float32(cell["long_epoch_prob"]))
    if "long_epoch_scale" in cell:
        pm = pm._replace(long_scale=np.float32(cell["long_epoch_scale"]))
    if "wakeup_us" in cell:
        pm = pm._replace(wakeup=np.int32(_ticks(cell["wakeup_us"])))
    for axis in _WL_AXES:
        if axis in cell:
            pm = pm._replace(
                **{_PARAM_AXES[axis]: np.float32(cell[axis])})
    for axis in ("preempt_rate", "churn_rate", "straggle_rate",
                 "straggle_scale"):
        if axis in cell:
            pm = pm._replace(**{axis: np.float32(cell[axis])})
    if "preempt_scale" in cell:
        pm = pm._replace(preempt_scale=np.float32(
            _ticks(cell["preempt_scale"])))
    if any(a in cell for a in _KS_AXES):
        # n_keys / zipf_theta change the Zipf sampler constants, which
        # are host-derived (repro.workloads.keys.zipf_consts) — rebuild
        # the whole constant block so every cell's traced values agree
        # with what build_params would produce for that config.
        nk = int(cell.get("n_keys", cfg.n_keys))
        th = float(cell.get("zipf_theta", cfg.zipf_theta))
        ks_th, ks_ze, ks_et, ks_al = _zipf_consts(nk, th)
        pm = pm._replace(
            ks_keys=np.int32(nk), ks_theta=np.float32(ks_th),
            ks_zeta=np.float32(ks_ze), ks_eta=np.float32(ks_et),
            ks_alpha=np.float32(ks_al),
            ks_locks=np.int32(cell.get("n_locks", cfg.n_locks)))
    if "window0_us" in cell:
        # A swept initial window plays the role of default_window_us (the
        # seed's LibASL-MAX cells set both), so the unit floor follows it.
        pm = pm._replace(unit0=np.float32(
            aimd.unit_for(_ticks(cell["window0_us"]), cfg.pct)))
    # Policy-declared axes land in the traced SimParams.pol slots (the
    # built-in fields above are already covered by _PARAM_AXES).
    for axis, slot in _active_policy(cfg).sweep_axes.items():
        if axis in cell and slot in pm.pol:
            pm = pm._replace(pol=dict(pm.pol, **{
                slot: np.asarray(cell[axis], pm.pol[slot].dtype)}))
    return pm


def _sweep_resumable(ccfg: SimConfig, tb: SimTables, pm: SimParams, w0,
                     resume_dir, chunk: int, calls) -> SimState:
    """Run the batched sweep in ``chunk``-cell slices, checkpointing
    each completed slice atomically (repro.ckpt.checkpointer) so an
    interrupted long sweep resumes from the last completed chunk
    instead of recomputing from cell 0.  Per-cell results are
    bit-identical to the one-shot path: vmap lanes are independent (the
    live-guard no-ops finished lanes), so slicing the cell axis cannot
    perturb any cell's trajectory.

    Every computed slice logs a call record of its own: ``calls`` (an
    ``ExitStack``) holds the sweep's open record, which the first computed
    slice completes; later slices open theirs."""
    import json
    from pathlib import Path

    from repro.ckpt import checkpointer as ckpt

    n_cells = int(np.shape(pm.slo)[0])
    chunk = max(int(chunk), 1)
    bounds = [(lo, min(lo + chunk, n_cells))
              for lo in range(0, n_cells, chunk)]
    # Fingerprint the sweep: resuming into a directory holding a
    # different config/grid would silently splice unrelated results.
    # The digest covers the actual traced values (two grids with equal
    # shapes but different cells must not match).
    import hashlib
    h = hashlib.sha256()
    for x in jax.tree.leaves((tb, pm, w0)):
        h.update(np.ascontiguousarray(np.asarray(x)).tobytes())
    # The digest already covers every traced value — SimTables.col
    # leaves (column drift) and SimParams.pol leaves (policy_kw drift)
    # included; the explicit name lists catch key-set changes whose
    # values happen to collide.
    fp = {"canon": repr(ccfg), "n_cells": n_cells, "chunk": chunk,
          "digest": h.hexdigest(),
          "columns": sorted(tb.col), "pol": sorted(pm.pol),
          "leaves": [[list(np.shape(x)), jnp.dtype(x.dtype).name]
                     for x in jax.tree.leaves((tb, pm))]}
    d = Path(resume_dir)
    d.mkdir(parents=True, exist_ok=True)
    fp_path = d / "sweep.json"
    if fp_path.exists():
        if json.loads(fp_path.read_text()) != fp:
            raise ValueError(
                f"resume_dir {str(resume_dir)!r} holds a different sweep "
                f"(config or grid changed); use a fresh directory")
    else:
        fp_path.write_text(json.dumps(fp))
    done = ckpt.latest_step(d)          # chunks 0..done are on disk
    parts = []
    for k, (lo, hi) in enumerate(bounds):
        if done is not None and k <= done:
            target = jax.eval_shape(_batched(ccfg), *jax.tree.map(
                lambda x: x[lo:hi], (tb, pm, w0)))
            parts.append(ckpt.restore(d, k, target))
            continue
        rec = calllog.current() or calls.enter_context(calllog.call("sweep"))
        with calllog.span("build"):
            tb_k, pm_k, w_k = _place(
                jax.tree.map(lambda x: x[lo:hi], (tb, pm, w0)))
        rec["lanes"] = hi - lo
        st_k = _call_batch(ccfg, tb_k, pm_k, w_k)
        calls.close()                   # the slice's record joins the log
        ckpt.save(d, k, st_k)
        parts.append(st_k)
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)


def _sweep_inputs(cfg: SimConfig, axes: dict, *, slo_us=1e9, seed=0,
                  windows0=None, product: bool = True, mesh=None,
                  data_axis="data"):
    """Validate a :func:`sweep` grid and build its traced inputs on the host.

    Returns ``(cfg, cells, tb, pm, w0, where)``: the template with every
    gate the axes need switched on (its ``_canon`` is the executable's key),
    one dict per cell, the stacked per-cell tables, params and initial
    windows as numpy arrays (padded to the shard count on ``mesh``), and
    the sharding they go to: the cells' ``NamedSharding`` on ``mesh``, else
    None (the default device)."""
    if not axes:
        raise ValueError("empty sweep: pass at least one axis")
    # A "policy" axis merges its values into ONE multi-policy
    # executable: the template grows a ``policy_set`` (jit-static — it
    # fixes the handler union compiled into the HLO) while each cell's
    # member id rides traced in ``SimParams.pol_id``.  This must happen
    # before ``sweepable_axes`` so member-declared axes (e.g.
    # ``shfl_bound``) validate against the whole set.
    if "policy" in axes:
        if not axes["policy"]:
            raise ValueError("policy axis needs at least one name")
        pset = tuple(dict.fromkeys(
            tuple(cfg.policy_set) + tuple(axes["policy"])))
        cfg = dataclasses.replace(cfg, policy_set=pset, policy=pset[0])
    allowed = sweepable_axes(cfg)
    for name in axes:
        if name not in allowed:
            raise ValueError(f"unknown sweep axis {name!r}; "
                             f"sweepable: {allowed}")
    # Sweeping a statically-gated feature must switch its gate on in the
    # template config (the gate is part of the canonical jit key).
    for gate in _GATE_AXES:
        if gate in axes and max(axes[gate]) > 0.0:
            cfg = dataclasses.replace(cfg, **{gate: max(axes[gate])})
    if not cfg.wl and any(a in axes for a in _WL_AXES):
        cfg = dataclasses.replace(cfg, wl=True)
    # Sweeping n_keys flips the key-shard gate on (the on/off bit is
    # part of the canonical jit key); the per-cell counts then ride
    # traced.  The other key axes only make sense with the gate on.
    if "n_keys" in axes:
        if any(int(v) < 1 for v in axes["n_keys"]):
            raise ValueError("n_keys axis values must be >= 1")
        if not _ks_on(cfg):
            cfg = dataclasses.replace(
                cfg, n_keys=int(max(int(v) for v in axes["n_keys"])))
    if not _ks_on(cfg) and any(a in axes for a in _KS_AXES):
        bad = [a for a in _KS_AXES if a in axes]
        raise ValueError(
            f"sweep axes {bad} need the key-shard gate on: set "
            f"SimConfig.n_keys > 0 (or include an n_keys axis)")
    if "n_locks" in axes:
        if any(not 1 <= int(v) <= cfg.n_locks for v in axes["n_locks"]):
            raise ValueError(
                f"n_locks axis values must lie in [1, cfg.n_locks="
                f"{cfg.n_locks}] (the padded lock-vector size)")
    # Sweeping a power column with any nonzero watts must flip the
    # static energy gate on: the swept values ride in the per-cell
    # tables; the template only needs a non-empty power field so _canon
    # keeps the integration ops ((0.0,) pads to the all-zero default —
    # bit-identical tables for cells that don't sweep it).
    if not _energy_on(cfg) and any(
            a in axes and any(any(float(x) != 0.0 for x in v)
                              for v in axes[a])
            for a in _energy.POWER_COLUMNS):
        cfg = dataclasses.replace(cfg, p_idle=(0.0,))
    names = list(axes)
    vals = [list(axes[k]) for k in names]
    if product:
        idx = list(itertools.product(*(range(len(v)) for v in vals)))
    else:
        if len({len(v) for v in vals}) > 1:
            raise ValueError("product=False requires equal-length axes")
        idx = [(i,) * len(vals) for i in range(len(vals[0]))] \
            if vals else [()]
    cells = [{k: vals[j][ii[j]] for j, k in enumerate(names)} for ii in idx]
    if not cells:
        raise ValueError("empty sweep")
    if "n_cores" in axes and max(axes["n_cores"]) > cfg.n_cores:
        raise ValueError("n_cores axis exceeds the padded cfg.n_cores")
    if any(a in axes for a in _KS_AXES):
        for cell in cells:
            nk = int(cell.get("n_keys", cfg.n_keys))
            nl = int(cell.get("n_locks", cfg.n_locks))
            if nk < nl:
                raise ValueError(
                    f"sweep cell pairs n_keys={nk} with n_locks={nl}: "
                    f"every lock needs at least one key")

    # Per-cell tables (rebuilt only when a program/column axis is swept).
    tbl_axes = table_axes()
    table_keys = [k for k in names if k in tbl_axes]
    if table_keys:
        tbs = [build_tables(_cell_tables_cfg(cfg, cell, table_keys))
               for cell in cells]
        tb = jax.tree.map(lambda *xs: np.stack(xs), *tbs)
    else:
        tb1 = build_tables(cfg)
        tb = jax.tree.map(
            lambda x: np.broadcast_to(x, (len(cells),) + np.shape(x)), tb1)

    pms = [_cell_params(cfg, cell, slo_us, seed) for cell in cells]
    pm = jax.tree.map(lambda *xs: np.stack(xs), *pms)

    base_w = _default_windows(cfg) if windows0 is None else \
        np.asarray(windows0, np.float32)
    w0 = np.stack([
        np.full(cfg.n_cores, _ticks(cell["window0_us"]), np.float32)
        if "window0_us" in cell else base_w for cell in cells])

    where = None
    if mesh is not None:
        from repro.dist.sharding import build_sweep_rules
        from jax.sharding import NamedSharding
        rules = build_sweep_rules(mesh, data_axis=data_axis)
        pad = (-len(cells)) % rules.num_shards("cells")
        if pad:  # equal row splits: duplicate the last cell (sweep trims)
            tb, pm, w0 = jax.tree.map(
                lambda x: np.concatenate([x, np.repeat(x[-1:], pad, 0)]),
                (tb, pm, w0))
        where = NamedSharding(mesh, rules.spec(("cells",),
                                               (len(cells) + pad,)))

    return cfg, cells, tb, pm, w0, where


def sweep(cfg: SimConfig, axes: dict, *, slo_us=1e9, seed=0,
          windows0=None, product: bool = True,
          mesh=None, data_axis="data",
          resume_dir=None, resume_chunk: int = 8):
    """Run a whole parameter sweep as ONE vmapped, compiled call.

    ``axes`` maps axis names (see ``SWEEPABLE``) to value lists.  With
    ``product=True`` (default) the grid is the cross-product in the dict's
    key order; with ``product=False`` all lists must have equal length and
    are zipped (pre-flattened grids, e.g. paired slo/window cells).

    ``n_cores`` cells run padded to ``cfg.n_cores`` with an active-core
    mask — identical results to an unpadded run, one executable for all.

    ``mesh`` (a ``jax.sharding.Mesh``) shards the cell dimension over the
    mesh's ``data_axis`` (``repro.dist.sharding.build_sweep_rules``); cells
    are padded to the next multiple of the shard count (duplicates of the
    last cell, trimmed from the result), so every device carries an equal
    contiguous row split and results stay bit-identical to the unsharded
    run (docs/simulator.md §Sharded sweeps).

    ``resume_dir`` makes a long sweep resumable: cells run in
    ``resume_chunk``-sized slices, each checkpointed atomically on
    completion (``repro.ckpt.checkpointer``); re-running the same sweep
    with the same directory restores completed chunks and continues,
    bit-identical to an uninterrupted run.  Not composable with
    ``mesh``.

    Returns ``(state, grid)``: ``state`` leaves have a leading cell axis;
    ``grid`` maps axis name -> np.ndarray of per-cell values.  Non-swept
    values come from ``cfg`` / ``slo_us`` / ``seed`` / ``windows0``.
    """
    if resume_dir is not None and mesh is not None:
        raise ValueError("resume_dir does not compose with mesh-sharded "
                         "sweeps; run chunked-resumable sweeps unsharded")
    with contextlib.ExitStack() as calls:
        rec = calls.enter_context(calllog.call("sweep"))
        with calllog.span("build"):
            cfg, cells, tb, pm, w0, where = _sweep_inputs(
                cfg, axes, slo_us=slo_us, seed=seed, windows0=windows0,
                product=product, mesh=mesh, data_axis=data_axis)
            if resume_dir is None:      # the resumable path places slices
                tb, pm, w0 = _place((tb, pm, w0), where)
        n_cells = len(cells)
        rec["lanes"] = n_cells
        if resume_dir is not None:
            st = _sweep_resumable(_canon(cfg), tb, pm, w0, resume_dir,
                                  resume_chunk, calls)
        else:
            st = _call_batch(_canon(cfg), tb, pm, w0)
            if np.shape(pm.slo)[0] > n_cells:   # mesh padding: trim
                with calllog.span("dispatch"):
                    st = jax.tree.map(lambda x: x[:n_cells], st)
    tbl_axes = table_axes()
    grid = {k: np.asarray([cell[k] for cell in cells], dtype=object)
            if k in tbl_axes else np.asarray([cell[k] for cell in cells])
            for k in axes}
    return st, grid


def sweep_slo(cfg: SimConfig, slo_us_values, seed=0) -> SimState:
    """Paper Figure 8b in one call (thin wrapper over :func:`sweep`)."""
    st, _ = sweep(cfg, {"slo_us": list(np.asarray(slo_us_values, float))},
                  seed=seed)
    return st


def sweep_summaries(cfg: SimConfig, st: SimState, grid: dict,
                    warmup: int = 32, slo_us=None) -> list:
    """Host-side per-cell summaries of a sweep result (one transfer of the
    leaves the summaries read).  ``slo_us`` (or a swept ``slo_us`` axis)
    adds the goodput metrics — see :func:`summarize`."""
    with calllog.call("sweep_summaries") as rec:
        host = _to_host(cfg, st)
        with calllog.span("reduce"):
            n_cells = len(next(iter(grid.values()))) if grid else \
                host.events.shape[0]
            rec["lanes"] = n_cells
            names = _summary_leaves(cfg)
            keyed = _ks_on(cfg) or "n_keys" in grid
            out, draws = [], 0
            for i in range(n_cells):
                cell_st = host._replace(
                    **{k: getattr(host, k)[i] for k in names})
                n_act = int(grid["n_cores"][i]) if "n_cores" in grid \
                    else None
                if keyed:
                    draws += _key_draws(cfg, cell_st, n_act)
                cell_slo = float(grid["slo_us"][i]) if "slo_us" in grid \
                    else slo_us
                s = summarize(cfg, cell_st, warmup, n_active=n_act,
                              slo_us=cell_slo)
                s.update({k: grid[k][i] for k in grid})
                out.append(s)
            rec["key_draws"] = draws
    return out


def _key_draws(cfg: SimConfig, st: SimState, n_active=None) -> int:
    """Epochs whose key was drawn in one lane's active cores: epoch 0's at
    the start and one more at each epoch's end, ``ep_cnt + 1`` a core."""
    n = cfg.n_cores if n_active is None else int(n_active)
    return int(np.asarray(st.ep_cnt)[:n].sum()) + n


def _summary_leaves(cfg: SimConfig) -> tuple:
    """The state leaves :func:`summarize` reads."""
    names = ("t", "events", "ep_lat", "ep_cnt", "cs_lat", "cs_cnt",
             "window", "energy")
    return names + ("ep_hist", "cs_hist") if cfg.hist else names


def _to_host(cfg: SimConfig, st: SimState) -> SimState:
    """``st`` with the leaves the summaries read on the host: the wait for
    the device loop and the copy, each in its span of the open call."""
    names = _summary_leaves(cfg)
    with calllog.span("wait"):
        jax.block_until_ready([getattr(st, k) for k in names])
    with calllog.span("transfer"):
        got = jax.device_get([getattr(st, k) for k in names])
    return st._replace(**dict(zip(names, got)))


# --------------------------------------------------------------------------
# Host-side summaries
# --------------------------------------------------------------------------

def _ring_values(buf: np.ndarray, cnt: int, warmup: int = 32) -> np.ndarray:
    """A core's recorded latency samples minus the first ``warmup``.

    When ``cnt <= warmup`` the result is EMPTY — every sample is warmup
    (the old ``min(warmup, cnt - 1)`` slice kept exactly one contaminated
    sample).  When the ring wrapped (``cnt > cap``) it holds the most
    recent ``cap`` samples in ring order: unroll oldest-first and trim
    the warmup samples still present, i.e. the first
    ``warmup - (cnt - cap)`` when the wrap hasn't yet evicted them all.
    Order is oldest-to-newest either way (percentiles don't care; tests
    do)."""
    cap = buf.shape[0]
    if cnt <= cap:
        return buf[min(warmup, cnt):cnt]
    pos = cnt % cap
    vals = np.concatenate([buf[pos:], buf[:pos]])
    return vals[max(0, warmup - (cnt - cap)):]


def hist_tail(cfg: SimConfig, ep_hist, cs_hist, slo_us=None,
              slo_scale=None, prefix: str = "hist_") -> dict:
    """Tail metrics from per-core streaming histograms (``cfg.hist``).

    ``ep_hist`` / ``cs_hist`` are ``[n, B]`` u32 count arrays (already
    sliced to the active cores); merging across cores is a plain sum —
    see repro.core.stats.  Returns p50/p99/p999 epoch and p99 CS
    quantiles per core class in microseconds (each within the documented
    ``sqrt(g) - 1`` relative-error bound of exact), plus the
    histogram-side SLO-good fraction when ``slo_us`` is given."""
    n = ep_hist.shape[0]
    big = np.asarray(cfg.big[:n], bool)
    lo_t, hi_t = cfg.hist_lo_us * US, cfg.hist_hi_us * US
    out = {}
    for name, mask in (("all", np.ones_like(big)), ("big", big),
                       ("little", ~big)):
        he = stats.merge(ep_hist[mask]) if mask.any() else \
            np.zeros(ep_hist.shape[1], np.uint64)
        hc = stats.merge(cs_hist[mask]) if mask.any() else \
            np.zeros(cs_hist.shape[1], np.uint64)
        for q, tag in ((50, "p50"), (99, "p99"), (99.9, "p999")):
            out[f"ep_{tag}_{prefix}{name}_us"] = \
                stats.quantile(he, q, lo_t, hi_t) / US
        out[f"cs_p99_{prefix}{name}_us"] = \
            stats.quantile(hc, 99, lo_t, hi_t) / US
    out[f"{prefix}rel_err_bound"] = stats.rel_err_bound(
        lo_t, hi_t, ep_hist.shape[1])
    if slo_us is not None:
        scl = np.ones(n) if slo_scale is None else np.asarray(slo_scale)
        good = tot = 0.0
        for c in range(n):
            good += stats.good_count(ep_hist[c], slo_us * scl[c] * US,
                                     lo_t, hi_t)
            tot += float(np.asarray(ep_hist[c], np.uint64).sum())
        out[f"slo_good_frac_{prefix.rstrip('_')}"] = \
            good / tot if tot else float("nan")
    return out


def fleet_tail(cfg: SimConfig, st: SimState, slo_us=None) -> dict:
    """Fleet-wide tail metrics from a (possibly batched / sharded)
    sweep state: merge the streaming histograms across EVERY leading
    axis — sweep cells, shards, devices — and all cores with one
    sum-reduction, then reconstruct quantiles host-side.  The only host
    transfer is the two ``[B]`` count vectors, never raw samples.

    The device-side partial sum is u32 (JAX default-x64-off); each
    merged bucket must stay < 2^32 counts, which a 5M-event-per-cell cap
    comfortably guarantees up to ~800 cells per bucket-dominating
    workload — the host-side final merge is u64 either way."""
    if not cfg.hist:
        raise ValueError("fleet_tail needs a cfg with hist=True")
    merged = jax.jit(
        lambda e, c: (jnp.sum(e.reshape(-1, e.shape[-1]), axis=0),
                      jnp.sum(c.reshape(-1, c.shape[-1]), axis=0)))(
        st.ep_hist, st.cs_hist)
    eph, csh = (np.asarray(h, np.uint64)[None] for h in merged)
    # Class masks don't survive the cross-core merge — fleet view only.
    cfg1 = dataclasses.replace(cfg, n_cores=1, big=(0,),
                               speed_cs=(1.0,), speed_nc=(1.0,))
    out = {k: v for k, v in hist_tail(cfg1, eph, csh, slo_us).items()
           if "_big_" not in k and "_little_" not in k}
    return out


def summarize(cfg: SimConfig, st: SimState, warmup: int = 32,
              n_active: int = None, slo_us: float = None) -> dict:
    """Throughput + tail latency per core class (all values in us).
    ``n_active`` slices per-core outputs for padded sweep cells.
    ``slo_us`` adds goodput: the fraction of sampled epochs within the
    per-core SLO (``slo_us * slo_scale[c]``) and the epochs/s that
    fraction represents — the chaos figures' useful-work metric.
    Given device state it logs a call record (:func:`sweep_log`)."""
    if any(isinstance(getattr(st, k), jax.Array)
           for k in _summary_leaves(cfg)):
        with calllog.call("summarize") as rec:
            st = _to_host(cfg, st)
            with calllog.span("reduce"):
                rec["key_draws"] = _key_draws(cfg, st, n_active) \
                    if _ks_on(cfg) else 0
                return _summary(cfg, st, warmup, n_active, slo_us)
    return _summary(cfg, st, warmup, n_active, slo_us)


def _summary(cfg: SimConfig, st: SimState, warmup: int, n_active,
             slo_us) -> dict:
    """:func:`summarize` of a state whose read leaves are on the host."""
    n = cfg.n_cores if n_active is None else int(n_active)
    big = np.asarray(cfg.big[:n], bool)
    ep_lat = np.asarray(st.ep_lat)[:n]
    ep_cnt = np.asarray(st.ep_cnt)[:n]
    cs_lat = np.asarray(st.cs_lat)[:n]
    cs_cnt = np.asarray(st.cs_cnt)[:n]
    t_end = float(np.asarray(st.t)) / US
    sim_s = max(t_end, 1e-9) / 1e6
    cap = ep_lat.shape[1]
    wrapped = bool((ep_cnt > cap).any() or (cs_cnt > cap).any())

    # One O(n*cap) collection pass, shared by the percentile AND goodput
    # paths below — the two can never disagree on the sample set.
    ep_vals = [_ring_values(ep_lat[c], int(ep_cnt[c]), warmup)
               for c in range(n)]
    cs_vals = [_ring_values(cs_lat[c], int(cs_cnt[c]), warmup)
               for c in range(n)]

    def collect(vals, mask):
        sel = [vals[c] for c in range(n) if mask[c]]
        v = np.concatenate(sel) if sel else np.zeros(0)
        return v / US  # -> microseconds

    out = {
        "sim_time_us": t_end,
        "events": int(np.asarray(st.events)),
        "throughput_cs_per_s": float(cs_cnt.sum()) / sim_s,
        "throughput_epochs_per_s": float(ep_cnt.sum()) / sim_s,
        "cs_per_core": cs_cnt.tolist(),
        "epochs_per_core": ep_cnt.tolist(),
    }
    for name, mask in (("all", np.ones_like(big)), ("big", big),
                       ("little", ~big)):
        ep = collect(ep_vals, mask)
        cs = collect(cs_vals, mask)
        out[f"ep_p99_{name}_us"] = stats.percentile(ep, 99)
        out[f"ep_p50_{name}_us"] = stats.percentile(ep, 50)
        out[f"cs_p99_{name}_us"] = stats.percentile(cs, 99)
    if wrapped:
        # A ring overwrote history: the exact percentiles above only see
        # the most recent `epcap` samples (recency-biased).  The flag is
        # emitted ONLY when it fires, so un-wrapped (e.g. golden-digest)
        # summaries are byte-identical to pre-histogram builds.
        out["tail_truncated"] = True
    if cfg.hist:
        # Streaming-histogram tail: full-history quantiles at bounded
        # relative error, any run length (docs/simulator.md §Streaming
        # metrics).  Keyed ep_*_hist_* alongside the ring-exact keys;
        # when the ring wrapped, the histogram values REPLACE the
        # primary ep/cs percentile keys — bounded error beats silently
        # truncated history.  NOTE the histogram warmup is the traced
        # ``cfg.hist_warmup`` (recorded on device), not this function's
        # ``warmup`` argument.
        eph = np.asarray(st.ep_hist, np.uint64)[:n]
        csh = np.asarray(st.cs_hist, np.uint64)[:n]
        out.update(hist_tail(cfg, eph, csh))
        if wrapped:
            for name in ("all", "big", "little"):
                out[f"ep_p99_{name}_us"] = out[f"ep_p99_hist_{name}_us"]
                out[f"ep_p50_{name}_us"] = out[f"ep_p50_hist_{name}_us"]
                out[f"cs_p99_{name}_us"] = out[f"cs_p99_hist_{name}_us"]
    out["final_window_us"] = (np.asarray(st.window)[:n] / US).tolist()
    # Energy (repro.core.energy): the accumulator is in watt-ticks and
    # 1 tick = 10 ns, so 1 watt-tick = 10 nJ.  The derived efficiency
    # metrics only appear when some energy was actually modeled.
    e_j = np.asarray(st.energy)[:n].astype(float) * 1e-8
    out["energy_per_core_j"] = e_j.tolist()
    out["energy_j"] = float(e_j.sum())
    if out["energy_j"] > 0.0:
        out["power_w"] = out["energy_j"] / sim_s
        out["tput_per_watt"] = (out["throughput_cs_per_s"]
                                / out["power_w"])
        p50 = out["ep_p50_all_us"]
        # EDP = energy x delay (J*s); delay = the median epoch latency.
        out["edp"] = out["energy_j"] * p50 * 1e-6 if np.isfinite(p50) \
            else float("nan")
    if slo_us is not None:
        # The registered column is the one source of truth for the
        # per-core SLO multiplier (encoding + neutral padding).
        scl = colreg.COLUMNS["slo_scale"].np_values(cfg, n)
        good = tot = 0
        for c in range(n):
            v = ep_vals[c]  # the same samples the percentiles used
            good += int(np.sum(v / US <= slo_us * scl[c]))
            tot += v.size
        frac = good / tot if tot else 0.0
        if cfg.hist:
            hg = hist_tail(cfg, eph, csh, slo_us=slo_us, slo_scale=scl)
            out["slo_good_frac_hist"] = hg["slo_good_frac_hist"]
            if wrapped:
                # Ring history truncated -> the ring fraction only sees
                # the most recent epcap epochs; report the full-history
                # histogram fraction as the primary goodput.
                frac = out["slo_good_frac_hist"]
        out["slo_good_frac"] = frac
        out["goodput_eps"] = out["throughput_epochs_per_s"] * frac
    return out
