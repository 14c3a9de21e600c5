"""Plain reference of the lock simulator's semantics, for deciding `correct`.

A straightforward sequential discrete-event loop, one sweep lane at a time,
written from the semantics the configuration files state and imports nothing
of the program under test.  Time is integer ticks (1 tick = 10 ns), exactly
as the program keeps it; the few float quantities (the LibASL reorder
window and its AIMD unit, the TAS draw, the Zipf key and read/write draws)
are computed in ``dtype``: float32 for the reference, bfloat16 for the
control (``CONTROL_DTYPE``).  Epochs are closed-loop: a core starts its next
epoch when the last one ends.

Random draws are threefry draws from ``jax.random`` (the PRNG that defines
the configuration's traffic); LibASL's window halving and the Zipf inverse
CDF are evaluated with ``jax.numpy`` on ``device``, so that a chip run
compares against the chip's own float arithmetic (a TPU flushes subnormals
to zero, and its ``pow`` may round otherwise than the CPU's).

A lane with ``n_keys`` draws, for each core and epoch, a key from YCSB's
Zipf(``n_keys``, ``zipf_theta``) (Gray et al.'s inverse CDF, as YCSB's
``ZipfianGenerator`` computes it) and contends the bucket lock
``key % n_locks``; ``ks_crew`` lanes also draw the epoch's read/write
uniform.  Both are counter draws: ``uniform(fold_in(fold_in(fold_in(
PRNGKey(seed), stream), core), epoch))`` with the streams ``STREAM_KEY`` and
``STREAM_RW``.  Epoch 0 is drawn for every core at the start, the next
epoch's at each epoch's end.

Per lane the reference returns every leaf of the simulator's final state and
the host summary built from it (``summarize``); ``leaves_differing`` and
``summary_gap`` read how far another result lies from them.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

US = 100                          # ticks per microsecond
INF = 1 << 30
NONCRIT, STANDBY, QUEUED, HOLDER, SPIN = 0, 1, 2, 3, 4
POL_SLOTS = {"shfl": "shfl_ctr", "dvfs_race": "race_ctr",
             "ks_erew": "erew_ctr", "ks_crew": "crew_ctr",
             "ks_jbsq": "jbsq_ctr"}
CONTROL_DTYPE = ml_dtypes.bfloat16
BLOCK = 1024                       # draws computed per jax call
STREAM_KEY, STREAM_RW = 0x778A, 0x778B
RW_POLICIES = ("ks_crew",)         # policies whose epochs read or write
ZIPF_POLE_EPS = 1e-4               # theta this close to 1 moves off the pole


def ticks(us) -> int:
    return int(round(us * US))


def _argmin(vals) -> int:
    """First index of the minimum (the program's ``argmin``)."""
    best, ix = vals[0], 0
    for i in range(1, len(vals)):
        if vals[i] < best:
            best, ix = vals[i], i
    return ix


# --------------------------------------------------------------------------
# Draws
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(1,))
def _split_chain(key, n):
    """Keys and uniforms of ``n`` successive ``split`` steps of a run key."""
    def step(k, _):
        k2, sub = jax.random.split(k)
        return k2, (k2, jax.random.uniform(sub))
    _, (keys, us) = jax.lax.scan(step, key, None, length=n)
    return keys, us


class KeyChain:
    """The run key of one lane, split once per release of a splitting
    policy; ``next()`` returns the uniform the release draws."""

    def __init__(self, seed):
        self.key0 = np.asarray(jax.random.PRNGKey(seed))
        self.keys = np.zeros((0, 2), np.uint32)
        self.us = np.zeros(0, np.float32)
        self.used = 0

    def next(self) -> np.float32:
        if self.used == len(self.us):
            base = self.key0 if self.used == 0 else self.keys[-1]
            keys, us = _split_chain(jax.device_put(base, _CPU()), BLOCK)
            self.keys = np.concatenate([self.keys, np.asarray(keys)])
            self.us = np.concatenate([self.us, np.asarray(us)])
        self.used += 1
        return self.us[self.used - 1]

    def final(self) -> np.ndarray:
        return self.key0 if self.used == 0 else self.keys[self.used - 1]


def _CPU():
    return jax.devices("cpu")[0]


def zipf_consts(n_keys: int, theta: float) -> np.ndarray:
    """Gray et al.'s constants of Zipf(``n_keys``, ``theta``) over ranks
    1..n, computed in float64 and kept as float32: ``(n, theta', zeta,
    eta, alpha)`` with ``theta'`` moved ``ZIPF_POLE_EPS`` off the pole of
    ``alpha = 1 / (1 - theta)``, ``zeta`` the harmonic number
    H(n, theta') and ``eta = (1 - (2/n)**(1 - theta')) / (1 - zeta(2) /
    zeta)`` (1 where n <= 2, whose tail is never reached)."""
    if abs(theta - 1.0) < ZIPF_POLE_EPS:
        theta = 1.0 - ZIPF_POLE_EPS if theta <= 1.0 else 1.0 + ZIPF_POLE_EPS
    ranks = np.arange(1, n_keys + 1, dtype=np.float64)
    zeta = float(np.sum(ranks ** -theta))
    zeta2 = 1.0 + 0.5 ** theta if n_keys >= 2 else zeta
    denom = 1.0 - zeta2 / zeta
    eta = (1.0 - (2.0 / n_keys) ** (1.0 - theta)) / denom \
        if n_keys > 2 and abs(denom) > 1e-12 else 1.0
    return np.asarray([n_keys, theta, zeta, eta, 1.0 / (1.0 - theta)],
                      np.float32)


@partial(jax.jit, static_argnums=(5, 6))
def _epoch_draws(key_stream, rw_stream, core, e0, consts, dtype, rw):
    """Key ranks and read/write uniforms of epochs ``e0 .. e0 + BLOCK - 1``
    of one core, from the streams' base keys.  The ranks follow Gray et
    al.'s inverse CDF in ``dtype``: rank 0 below 1/zeta, rank 1 below
    zeta(2)/zeta, else floor(n (eta u - eta + 1)**alpha), clipped to
    [0, n).  The uniforms are None where ``rw`` is off."""
    es = e0 + jnp.arange(BLOCK, dtype=jnp.int32)

    def uniforms(stream):
        base = jax.random.fold_in(stream, core)
        return jax.vmap(lambda e: jax.random.uniform(
            jax.random.fold_in(base, e)))(es)
    n, theta, zeta, eta, alpha = (consts[i].astype(dtype) for i in range(5))
    u = uniforms(key_stream).astype(dtype)
    uz = u * zeta
    zeta2 = 1.0 + 0.5 ** theta
    tail = jnp.floor(n * (eta * u - eta + 1.0) ** alpha)
    k = jnp.where(uz < 1.0, 0.0, jnp.where(uz < zeta2, 1.0, tail))
    ranks = jnp.clip(k, 0.0, n - 1.0).astype(jnp.int32)
    return ranks, uniforms(rw_stream) if rw else None


class EpochDraws:
    """The per-(core, epoch) key-drawn lock and read/write uniform of one
    keyed lane, computed ``BLOCK`` epochs of a core per device call."""

    def __init__(self, p, dtype, device):
        self.F, self.device = dtype, device
        self.consts = zipf_consts(p["n_keys"], p["zipf_theta"])
        self.n_locks = p["n_locks"]
        self.rw = p["policy"] in RW_POLICIES
        base = jax.random.PRNGKey(p["seed"])
        self.streams = [np.asarray(jax.random.fold_in(base, s))
                        for s in (STREAM_KEY, STREAM_RW)]
        self.blocks = {}

    def __call__(self, c: int, e: int):
        """(lock, read/write uniform) of core ``c``'s epoch ``e``; the
        uniform is 1.0, a read, where the policy has no read/write
        stream."""
        b = e // BLOCK
        if (c, b) not in self.blocks:
            with jax.default_device(self.device):
                ranks, rw = _epoch_draws(*self.streams, np.int32(c),
                                         np.int32(b * BLOCK), self.consts,
                                         self.F, self.rw)
            self.blocks[c, b] = (np.asarray(ranks) % self.n_locks,
                                 None if rw is None else np.asarray(rw))
        locks, rws = self.blocks[c, b]
        i = e % BLOCK
        return int(locks[i]), self.F(1.0) if rws is None else self.F(rws[i])


@partial(jax.jit, static_argnums=(1, 2))
def _violated_step(w, pct, max_window):
    w = w * 0.5
    u = w * (100.0 - pct) / 100.0
    return jnp.clip(w + u, 0.0, max_window), u


def _violated(device, w, pct, max_window):
    """Algorithm 2 after a violation, as float arithmetic of ``device``:
    the halved window's unit and the window one unit later.  The window
    can halve into the subnormal range, which a TPU flushes to zero and
    numpy keeps, so the step runs where the result is checked."""
    with jax.default_device(device):
        w2, u = _violated_step(np.asarray(w), pct, max_window)
    return type(w)(np.asarray(w2)), type(w)(np.asarray(u))


# --------------------------------------------------------------------------
# The event loop
# --------------------------------------------------------------------------

class Lane:
    """One sweep lane's simulation state and event handlers."""

    def __init__(self, lane, dtype=np.float32, device=None):
        F = self.F = dtype
        self.p = p = lane
        n, L, cap = p["n"], p["n_locks"], p["epcap"]
        self.n, self.cap = n, cap
        self.pol = p["policy"]
        self.big = [int(b) for b in p["big"][:n]]
        S = len(p["seg_cs_us"])
        self.n_seg = S
        self.cs_dur = [[ticks(p["seg_cs_us"][j] * p["speed_cs"][c])
                        for j in range(S)] for c in range(n)]
        self.nc_dur = [[ticks(p["seg_noncrit_us"][j] * p["speed_nc"][c])
                        for j in range(S)] for c in range(n)]
        self.inter = [ticks(p["inter_epoch_us"] * p["speed_nc"][c])
                      for c in range(n)]
        self.seg_lock = list(p["seg_lock"])
        self.horizon = ticks(p["sim_time_us"])
        self.max_events = p["max_events"]
        self.slo = F(np.float32(ticks(p["slo_us"])))
        self.w_big = F(np.float32(p["w_big"]))
        self.max_window = ticks(p["max_window_us"])
        self.pct = p["pct"]
        self.keys = KeyChain(p["seed"])
        self.device = device or _CPU()
        act = p["n_active"]
        self.active = [c < act for c in range(n)]

        self.t, self.events = 0, 0
        self.seg = [0] * n
        self.epoch_start = [0] * n
        self.attempt_t = [0] * n
        w0 = F(np.float32(ticks(p["default_window_us"])))
        self.window = [w0] * n
        unit0 = ticks(p["default_window_us"]) * (100.0 - self.pct) / 100.0
        self.unit = [F(np.float32(unit0))] * n
        self.scale = [F(1.0)] * n
        self.q = [[[-1] * n for _ in range(2)] for _ in range(L)]
        self.q_head = [[0, 0] for _ in range(L)]
        self.q_tail = [[0, 0] for _ in range(L)]
        self.holder = [-1] * L
        self.prop_ctr = [0] * L
        self.ctr = [0] * L                   # the policy's bypass counter
        self.ep_lat = np.zeros((n, cap), np.float32)
        self.cs_lat = np.zeros((n, cap), np.float32)
        self.ep_cnt = [0] * n
        self.cs_cnt = [0] * n
        self.phase = [NONCRIT] * n
        self.t_ready = [self.nc_dur[c][0] + c if self.active[c] else INF
                        for c in range(n)]
        # Keyed lanes: every core's epoch-0 lock and read/write uniform,
        # padded cores included.
        self.draws = EpochDraws(p, F, self.device) if p.get("n_keys") \
            else None
        first = [self.draws(c, 0) if self.draws else (0, F(1.0))
                 for c in range(n)]
        self.cur_lock = [lk for lk, _ in first]
        self.cur_rw = [rw for _, rw in first]

    # -- helpers ----------------------------------------------------------
    def lock(self, c):
        """The lock core ``c`` contends: its epoch's drawn bucket lock on a
        keyed lane, else its segment's."""
        if self.draws:
            return self.cur_lock[c]
        return self.seg_lock[self.seg[c]]

    def waiting(self, l, phase=QUEUED):
        return [self.phase[c] == phase and self.lock(c) == l
                for c in range(self.n)]

    def grant(self, c, t):
        dur = self.cs_dur[c][self.seg[c]]
        self.holder[self.lock(c)] = c
        self.phase[c] = HOLDER
        self.t_ready[c] = t + dur

    def park(self, c, phase):
        self.phase[c] = phase
        self.t_ready[c] = INF

    def enq(self, l, b, c):
        self.q[l][b][self.q_tail[l][b] % self.n] = c
        self.q_tail[l][b] += 1

    def deq(self, l, b):
        c = self.q[l][b][self.q_head[l][b] % self.n]
        self.q_head[l][b] += 1
        return c

    def qlen(self, l, b):
        return self.q_tail[l][b] - self.q_head[l][b]

    def weighted_pick(self, weights):
        """One uniform of the run key over ``weights``: the first index
        whose running sum exceeds u x total."""
        F = self.F
        u = F(self.keys.next())
        cum, acc = [], F(0.0)
        for w in weights:
            acc = F(acc + w)
            cum.append(acc)
        total = cum[-1]
        x = F(u * total)
        pick = next((i for i, v in enumerate(cum) if v > x), 0)
        return pick, total > 0

    def head_of(self, mask):
        return _argmin([self.attempt_t[c] if mask[c] else INF
                        for c in range(self.n)])

    # -- acquire ----------------------------------------------------------
    def on_acquire(self, c, t):
        self.attempt_t[c] = t
        pol, l = self.pol, self.lock(c)
        free = self.holder[l] == -1
        if pol in ("fifo", "prop", "libasl"):
            empty = self.qlen(l, 0) == 0
            if pol == "prop":
                empty = empty and self.qlen(l, 1) == 0
            if free and empty:
                self.grant(c, t)
            elif pol == "libasl" and not self.big[c]:
                win = int(min(self.window[c], self.F(self.max_window)))
                self.phase[c] = STANDBY
                self.t_ready[c] = t + max(win, 0)
            else:
                b = 0 if (pol != "prop" or self.big[c]) else 1
                self.enq(l, b, c)
                self.park(c, QUEUED)
        elif pol == "tas":
            if free:
                self.grant(c, t)
            else:
                self.park(c, SPIN)
        else:                              # the queue-less policies
            if free and not any(self.waiting(l)):
                self.grant(c, t)
            else:
                self.park(c, QUEUED)

    def on_standby_expiry(self, c, t):
        l = self.lock(c)
        if self.holder[l] == -1 and self.qlen(l, 0) == 0:
            self.grant(c, t)
        else:
            self.enq(l, 0, c)
            self.park(c, QUEUED)

    # -- release ----------------------------------------------------------
    def on_release(self, c, t):
        F, s = self.F, self.seg[c]
        l = self.lock(c)
        pos = self.cs_cnt[c] % self.cap
        self.cs_lat[c, pos] = np.float32(t - self.attempt_t[c])
        self.cs_cnt[c] += 1
        last = s == self.n_seg - 1
        ep_latency = t - self.epoch_start[c]
        if last:
            self.ep_lat[c, self.ep_cnt[c] % self.cap] = np.float32(ep_latency)
            self.ep_cnt[c] += 1
            if self.pol == "libasl" and not self.big[c]:
                self.aimd(c, F(np.float32(ep_latency)))
            if self.draws:             # the next epoch's lock; l is kept
                self.cur_lock[c], self.cur_rw[c] = self.draws(
                    c, self.ep_cnt[c])
        if last:
            self.epoch_start[c] = t + self.inter[c]
            self.t_ready[c] = t + self.inter[c] + self.nc_dur[c][0]
            self.phase[c] = NONCRIT
        else:
            self.t_ready[c] = t + self.nc_dur[c][min(s + 1, self.n_seg - 1)]
            self.phase[c] = NONCRIT
        self.seg[c] = 0 if last else s + 1
        self.holder[l] = -1
        self.pick_next(l, t)

    def aimd(self, c, latency):
        """Algorithm 2: on an SLO violation halve the window and reset the
        unit to (100 - pct)% of it (evaluated on the device, see
        ``_violated``); then add one unit, clipped."""
        F = self.F
        w, u = self.window[c], self.unit[c]
        if latency > F(self.slo * F(1.0)):
            w, u = _violated(self.device, w, self.pct, self.max_window)
        else:
            w = min(max(F(w + u), F(0.0)), F(self.max_window))
        self.window[c], self.unit[c] = w, u

    def pick_next(self, l, t):
        pol, n = self.pol, self.n
        if pol == "fifo":
            if self.qlen(l, 0) > 0:
                self.grant(self.deq(l, 0), t)
        elif pol == "prop":
            nb, nl = self.qlen(l, 0), self.qlen(l, 1)
            if nb > 0 and (self.prop_ctr[l] < self.p["prop_n"] or nl == 0):
                self.prop_ctr[l] += 1
                self.grant(self.deq(l, 0), t)
            elif nl > 0:
                self.prop_ctr[l] = 0
                self.grant(self.deq(l, 1), t)
        elif pol == "libasl":
            queued = self.qlen(l, 0) > 0
            if queued:
                self.grant(self.deq(l, 0), t)
            standby = self.waiting(l, STANDBY)
            pick, any_sb = self.weighted_pick(
                [self.F(1.0) if s else self.F(0.0) for s in standby])
            if any_sb and not queued:
                self.grant(pick, t)
        elif pol == "tas":
            spin = self.waiting(l, SPIN)
            w = [(self.w_big if self.big[c] else self.F(1.0))
                 if spin[c] else self.F(0.0) for c in range(n)]
            pick, any_spin = self.weighted_pick(w)
            if any_spin:
                self.grant(pick, t)
        elif pol == "edf":
            waiting = self.waiting(l)
            slo_t = int(min(self.slo, self.F(self.max_window)))
            dl = [self.epoch_start[c] + slo_t if waiting[c] else INF
                  for c in range(n)]
            tie = [waiting[c] and dl[c] == min(dl) for c in range(n)]
            if any(waiting):
                self.grant(self.head_of(tie), t)
        else:
            self.bounded_pick(l, t)

    def bounded_pick(self, l, t):
        """shfl, dvfs_race and the three key-aware policies: grant a
        preferred waiter ahead of the FIFO head, at most ``bound``
        consecutive times."""
        pol, n, p = self.pol, self.n, self.p
        waiting = self.waiting(l)
        if not any(waiting):
            return
        head = self.head_of(waiting)
        ctr = self.ctr[l]
        if pol == "shfl":
            big_wait = [waiting[c] and self.big[c] for c in range(n)]
            use = any(big_wait) and ctr < p["shfl_bound"]
            pick = self.head_of(big_wait) if use else head
        elif pol == "dvfs_race":
            score = [(2.0 if self.big[c] else 1.0) if waiting[c] else -1.0
                     for c in range(n)]
            tie = [waiting[c] and score[c] == max(score) for c in range(n)]
            use = ctr < p["race_bound"]
            pick = self.head_of(tie) if use else head
        else:
            owner = self.owner(l)
            if pol == "ks_erew":
                use, prefer = waiting[owner], owner
                bound = p["erew_bound"]
            elif pol == "ks_crew":
                # readers first, the earliest; else the owner's write
                writer = [self.cur_rw[c] < self.F(np.float32(
                    p["crew_wfrac"])) for c in range(n)]
                readers = [waiting[c] and not writer[c] for c in range(n)]
                owner_writes = waiting[owner] and writer[owner]
                use = any(readers) or owner_writes
                prefer = self.head_of(readers) if any(readers) else owner
                bound = p["crew_bound"]
            else:                       # ks_jbsq: the least served waiter
                served = min(self.ep_cnt[c] for c in range(n) if waiting[c])
                prefer = self.head_of([waiting[c] and
                                       self.ep_cnt[c] == served
                                       for c in range(n)])
                use, bound = True, p["jbsq_k"]
            use = use and ctr < bound
            pick = prefer if use else head
        self.ctr[l] = ctr + 1 if (use and pick != head) else 0
        self.grant(pick, t)

    def owner(self, l):
        """Active big cores own the lowest lock ids, then active littles."""
        act = self.p["n_active"]
        rank = [(1 - self.big[c]) if c < act else 2 for c in range(self.n)]
        pref = sorted(range(self.n), key=lambda c: rank[c])
        return pref[l % max(act, 1)]

    def run(self):
        while True:
            c = _argmin(self.t_ready)
            t = self.t_ready[c]
            if t >= self.horizon or self.events >= self.max_events:
                return self
            self.t = t
            self.events += 1
            ph = self.phase[c]
            if ph == NONCRIT:
                self.on_acquire(c, t)
            elif ph == HOLDER:
                self.on_release(c, t)
            elif ph == STANDBY:
                self.on_standby_expiry(c, t)
            else:
                self.t_ready[c] = INF

    # -- result -------------------------------------------------------------
    def leaves(self) -> dict:
        n, L = self.n, len(self.holder)
        f32 = np.float32
        out = {
            "t": np.int32(self.t), "key": self.keys.final().astype(np.uint32),
            "phase": np.asarray(self.phase, np.int32),
            "t_ready": np.asarray(self.t_ready, np.int32),
            "seg": np.asarray(self.seg, np.int32),
            "epoch_start": np.asarray(self.epoch_start, np.int32),
            "attempt_t": np.asarray(self.attempt_t, np.int32),
            "window": np.asarray(self.window, f32),
            "unit": np.asarray(self.unit, f32),
            "scale": np.asarray(self.scale, f32),
            "svc_scale": np.ones(n, f32),
            "wl_on": np.zeros(n, np.int32),
            "q": np.asarray(self.q, np.int32),
            "q_head": np.asarray(self.q_head, np.int32),
            "q_tail": np.asarray(self.q_tail, np.int32),
            "holder": np.asarray(self.holder, np.int32),
            "prop_ctr": np.asarray(self.prop_ctr, np.int32),
            "ep_lat": self.ep_lat, "ep_cnt": np.asarray(self.ep_cnt, np.int32),
            "cs_lat": self.cs_lat, "cs_cnt": np.asarray(self.cs_cnt, np.int32),
            "events": np.int32(self.events),
            "arr_t": np.zeros(n, np.int32),
            "energy": np.zeros(n, f32),
            "cur_lock": np.asarray(self.cur_lock, np.int32),
            "cur_rw": np.asarray(self.cur_rw, f32),
            "ep_hist": np.zeros((n, 1), np.uint32),
            "cs_hist": np.zeros((n, 1), np.uint32),
        }
        for pol in self.p["policies"]:
            if pol in POL_SLOTS:
                own = self.ctr if pol == self.pol else [0] * L
                out["pol." + POL_SLOTS[pol]] = np.asarray(own, np.int32)
        return out


def simulate(lane: dict, dtype=np.float32, device=None) -> dict:
    """Every state leaf of one lane after its horizon, plus its summary."""
    sim = Lane(lane, dtype, device).run()
    leaves = sim.leaves()
    return {"leaves": leaves, "summary": summarize(lane, leaves)}


# --------------------------------------------------------------------------
# Host summary
# --------------------------------------------------------------------------

def _ring(buf, cnt, warmup):
    cap = buf.shape[0]
    if cnt <= cap:
        return buf[min(warmup, cnt):cnt]
    pos = cnt % cap
    vals = np.concatenate([buf[pos:], buf[:pos]])
    return vals[max(0, warmup - (cnt - cap)):]


def _pct(v, q):
    v = np.asarray(v, float).ravel()
    return float(np.percentile(v, q)) if v.size else float("nan")


def summarize(lane: dict, leaves: dict, warmup: int = 32) -> dict:
    """Throughput, per-class tail latencies (microseconds) and SLO goodput
    of one lane, from its final state."""
    n = lane["n_active"]
    big = np.asarray(lane["big"][:n], bool)
    ep_cnt, cs_cnt = leaves["ep_cnt"][:n], leaves["cs_cnt"][:n]
    t_end = float(leaves["t"]) / US
    sim_s = max(t_end, 1e-9) / 1e6
    cap = leaves["ep_lat"].shape[1]
    ep = [_ring(leaves["ep_lat"][c], int(ep_cnt[c]), warmup)
          for c in range(n)]
    cs = [_ring(leaves["cs_lat"][c], int(cs_cnt[c]), warmup)
          for c in range(n)]
    out = {"sim_time_us": t_end, "events": int(leaves["events"]),
           "throughput_cs_per_s": float(cs_cnt.sum()) / sim_s,
           "throughput_epochs_per_s": float(ep_cnt.sum()) / sim_s,
           "cs_per_core": cs_cnt.tolist(),
           "epochs_per_core": ep_cnt.tolist()}
    for name, mask in (("all", np.ones_like(big)), ("big", big),
                       ("little", ~big)):
        def cat(vals):
            sel = [vals[c] for c in range(n) if mask[c]]
            return (np.concatenate(sel) if sel else np.zeros(0)) / US
        out[f"ep_p99_{name}_us"] = _pct(cat(ep), 99)
        out[f"ep_p50_{name}_us"] = _pct(cat(ep), 50)
        out[f"cs_p99_{name}_us"] = _pct(cat(cs), 99)
    if (ep_cnt > cap).any() or (cs_cnt > cap).any():
        out["tail_truncated"] = True
    out["final_window_us"] = (leaves["window"][:n] / US).tolist()
    out["energy_j"] = 0.0
    slo = lane["slo_us"]
    good = sum(int(np.sum(v / US <= slo)) for v in ep)
    tot = sum(v.size for v in ep)
    out["slo_good_frac"] = good / tot if tot else 0.0
    out["goodput_eps"] = out["throughput_epochs_per_s"] * out["slo_good_frac"]
    return out


# --------------------------------------------------------------------------
# Comparison
# --------------------------------------------------------------------------

def _flat(v):
    if isinstance(v, bool):
        return [float(v)]
    if isinstance(v, (list, tuple)):
        return [float(x) for x in v]
    return [float(v)]


def summary_gap(got: dict, want: dict) -> float:
    """Largest relative gap over every number of the reference summary
    (a key missing from ``got`` counts as a gap of 1; NaN equals NaN)."""
    worst = 0.0
    for k, w in want.items():
        if k not in got:
            return 1.0
        a, b = _flat(got[k]), _flat(w)
        if len(a) != len(b):
            return 1.0
        for x, y in zip(a, b):
            if math.isnan(x) and math.isnan(y):
                continue
            if math.isnan(x) or math.isnan(y):
                return 1.0
            worst = max(worst, abs(x - y) / max(abs(y), 1e-30))
    return worst


def leaves_differing(got: dict, want: dict) -> list:
    """Names of the reference's state leaves that ``got`` does not hold
    bit for bit (a missing leaf, or one of another shape or type,
    differs)."""
    bad = []
    for k, w in want.items():
        w = np.ascontiguousarray(w)
        g = got.get(k)
        g = None if g is None else np.ascontiguousarray(g)
        if g is None or g.shape != w.shape or g.dtype != w.dtype or \
                g.tobytes() != w.tobytes():
            bad.append(k)
    return bad
