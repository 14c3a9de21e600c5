"""Key-aware dispatch policies for the key-sharded datastore axis.

Three plugins that read the per-epoch Zipf-drawn lock
(``repro.workloads.keys`` via ``SimState.cur_lock``) and exploit the
key->lock bucketing (bucket = key mod n_locks, rank-preserving — lock 0
is the hot bucket):

* ``ks_erew`` — EREW key affinity: every lock has a static *owner*
  core, active **big cores first** (the headline scenario: hot keys
  pinned to big cores).  The owner is shuffled ahead of the FIFO head,
  bounded by ``erew_bound`` consecutive bypasses (shfl-style
  starvation-free).
* ``ks_crew`` — CREW: the per-epoch STREAM_RW uniform classifies each
  epoch read (``cur_rw >= crew_wfrac``) or write; readers are served
  first (earliest-reader), writes are owner-exclusive (routed to the
  owner core when it is waiting-to-write), bounded by ``crew_bound``.
* ``ks_jbsq`` — bounded JBSQ(k): grant the *least-served* waiter
  (minimum epoch count, earliest-arrival tie-break) — the
  fairness-first anti-asymmetry contrast — forced back to the true
  FIFO head after ``jbsq_k`` consecutive head-bypasses.

CRCW has no plugin: plain ``fifo`` under a keyed config *is* the CRCW
baseline (any core may read or write any bucket, strict arrival
order); the keyshard figures label it ``crcw``.

All three are queue-less (edf/shfl-style waiting-mask scans) and
shape-independent: the owner map ranks inactive (padded) cores last,
so the owner of any lock is always an *active* core and padded runs
stay bit-identical to unpadded ones.  With the key gate off they
degrade to well-defined single-lock policies (owner = first big core,
every epoch a read), so the registry-wide conformance suite runs them
unchanged.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.policies import register
from repro.core.policies.base import (INF, LockPolicy, grant, policy_opts,
                                      queueless_acquire, waiting_mask)

DEFAULT_BOUND = 4       # erew/crew/jbsq head-bypass bound
DEFAULT_WFRAC = 0.5     # crew write fraction threshold


def _owner_of(tb, pm, l):
    """Static owner core of lock ``l``: active big cores claim the low
    (hot, because bucketing is rank-preserving) lock ids first, then
    active littles; inactive padded cores rank last so the owner is
    always active regardless of padding (shape-independence)."""
    n = tb.big.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    rank = jnp.where(idx < pm.n_active, 1 - tb.big, 2)
    pref = jnp.argsort(rank, stable=True).astype(jnp.int32)
    return pref[l % jnp.maximum(pm.n_active, 1)]


def _fifo_head(st, waiting):
    """Earliest attempt among the waiting set (argmin tie-break)."""
    return jnp.argmin(
        jnp.where(waiting, st.attempt_t, INF)).astype(jnp.int32)


def _bounded_grant(st, cfg, tb, pm, l, t, cond, waiting, prefer,
                   use_pref, ctr_slot, bound):
    """Grant ``prefer`` while the per-lock bypass counter is under
    ``bound``, else the true FIFO head; count consecutive bypasses
    (granting the head resets).  The shfl starvation bound, shared by
    all three keyshard policies."""
    head = _fifo_head(st, waiting)
    ctr = st.pol[ctr_slot][l]
    use = jnp.logical_and(use_pref, ctr < bound)
    pick = jnp.where(use, prefer, head)
    bypassed = jnp.logical_and(use, pick != head)
    has = jnp.logical_and(jnp.any(waiting), cond)
    new_ctr = jnp.where(bypassed, ctr + 1, 0)
    st = st._replace(pol=dict(st.pol, **{
        ctr_slot: st.pol[ctr_slot].at[l].set(
            jnp.where(has, new_ctr, ctr))}))
    return grant(st, cfg, tb, pm, has, pick, t, wakeup=True)


@register
class KsErewPolicy(LockPolicy):
    name = "ks_erew"
    table_slots = ("big",)
    param_slots = ("n_active", "pol.erew_bound")
    state_slots = ("erew_ctr",)
    sweep_axes = {"erew_bound": "erew_bound"}
    host_dispatch = "key-erew"

    def init_params(self, cfg):
        return {"erew_bound": np.int32(
            policy_opts(cfg).get("erew_bound", DEFAULT_BOUND))}

    def init_state(self, cfg, tb, pm):
        return {"erew_ctr": jnp.zeros(cfg.n_locks, jnp.int32)}

    def on_acquire(self, st, cfg, tb, pm, c, t, cond):
        return queueless_acquire(st, cfg, tb, pm, c, t, cond)

    def pick_next(self, st, cfg, tb, pm, l, t, cond):
        waiting = waiting_mask(st, cfg, tb, l)
        owner = _owner_of(tb, pm, l)
        return _bounded_grant(st, cfg, tb, pm, l, t, cond, waiting,
                              owner, waiting[owner], "erew_ctr",
                              pm.pol["erew_bound"])


@register
class KsCrewPolicy(LockPolicy):
    name = "ks_crew"
    uses_rw = True
    table_slots = ("big",)
    param_slots = ("n_active", "pol.crew_wfrac", "pol.crew_bound")
    state_slots = ("crew_ctr",)
    sweep_axes = {"crew_wfrac": "crew_wfrac", "crew_bound": "crew_bound"}
    host_dispatch = "key-crew"

    def init_params(self, cfg):
        kw = policy_opts(cfg)
        return {"crew_wfrac": np.float32(kw.get("crew_wfrac",
                                                DEFAULT_WFRAC)),
                "crew_bound": np.int32(kw.get("crew_bound",
                                              DEFAULT_BOUND))}

    def init_state(self, cfg, tb, pm):
        return {"crew_ctr": jnp.zeros(cfg.n_locks, jnp.int32)}

    def on_acquire(self, st, cfg, tb, pm, c, t, cond):
        return queueless_acquire(st, cfg, tb, pm, c, t, cond)

    def pick_next(self, st, cfg, tb, pm, l, t, cond):
        waiting = waiting_mask(st, cfg, tb, l)
        # Epoch class: write when the STREAM_RW uniform falls under the
        # write fraction (cur_rw init/default is 1.0 = read, so the
        # key-off degenerate run is all-readers — plain earliest-first).
        writer = st.cur_rw < pm.pol["crew_wfrac"]
        readers = jnp.logical_and(waiting, jnp.logical_not(writer))
        r_head = _fifo_head(st, readers)
        owner = _owner_of(tb, pm, l)
        owner_writes = jnp.logical_and(waiting[owner], writer[owner])
        any_r = jnp.any(readers)
        # Readers first (earliest reader); else a write, owner-exclusive
        # when the owner wants it.  use_pref=False (no reader, owner
        # idle) falls through to the FIFO head — an ordinary writer.
        prefer = jnp.where(any_r, r_head,
                           jnp.where(owner_writes, owner, 0))
        use_pref = jnp.logical_or(any_r, owner_writes)
        return _bounded_grant(st, cfg, tb, pm, l, t, cond, waiting,
                              prefer, use_pref, "crew_ctr",
                              pm.pol["crew_bound"])


@register
class KsJbsqPolicy(LockPolicy):
    name = "ks_jbsq"
    param_slots = ("pol.jbsq_k",)
    state_slots = ("jbsq_ctr",)
    sweep_axes = {"jbsq_k": "jbsq_k"}
    host_dispatch = "key-jbsq"

    def init_params(self, cfg):
        return {"jbsq_k": np.int32(
            policy_opts(cfg).get("jbsq_k", DEFAULT_BOUND))}

    def init_state(self, cfg, tb, pm):
        return {"jbsq_ctr": jnp.zeros(cfg.n_locks, jnp.int32)}

    def on_acquire(self, st, cfg, tb, pm, c, t, cond):
        return queueless_acquire(st, cfg, tb, pm, c, t, cond)

    def pick_next(self, st, cfg, tb, pm, l, t, cond):
        waiting = waiting_mask(st, cfg, tb, l)
        # Least-served waiter: minimum completed-epoch count, earliest
        # arrival among the tied (two-stage argmin keeps i32 exact).
        served = jnp.where(waiting, st.ep_cnt, INF)
        tied = jnp.logical_and(waiting, st.ep_cnt == jnp.min(served))
        least = _fifo_head(st, tied)
        return _bounded_grant(st, cfg, tb, pm, l, t, cond, waiting,
                              least, jnp.any(waiting), "jbsq_ctr",
                              pm.pol["jbsq_k"])
