"""ShflLock-style queue shuffling: big cores are shuffled ahead of
little waiters, bounded by a per-lock starvation counter — no AIMD, no
SLO feedback (the static throughput-first point the paper's Figure 5
proportional policy approximates).

The shuffle bound is a policy-owned knob: ``shfl_bound`` consecutive
head-bypasses force the true FIFO head through (so a little waiter is
bypassed at most ``shfl_bound`` grants — starvation-free by
construction).  It rides in ``SimParams.pol`` (traced, sweepable as the
``shfl_bound`` axis) and defaults from ``SimConfig.policy_kw``.

Queue-less like edf: FIFO order is the arrival order of the waiting
set (``attempt_t``; argmin index tie-break), big-forward shuffling is
the same scan restricted to big waiters.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.policies import register
from repro.core.policies.base import (INF, LockPolicy, grant, policy_opts,
                                      queueless_acquire, waiting_mask)

DEFAULT_BOUND = 4


@register
class ShflPolicy(LockPolicy):
    name = "shfl"
    table_slots = ("big",)
    state_slots = ("shfl_ctr",)
    param_slots = ("pol.shfl_bound",)
    sweep_axes = {"shfl_bound": "shfl_bound"}

    def init_params(self, cfg):
        return {"shfl_bound": np.int32(
            policy_opts(cfg).get("shfl_bound", DEFAULT_BOUND))}

    def init_state(self, cfg, tb, pm):
        return {"shfl_ctr": jnp.zeros(cfg.n_locks, jnp.int32)}

    def on_acquire(self, st, cfg, tb, pm, c, t, cond):
        return queueless_acquire(st, cfg, tb, pm, c, t, cond)

    def pick_next(self, st, cfg, tb, pm, l, t, cond):
        waiting = waiting_mask(st, cfg, tb, l)
        arr = jnp.where(waiting, st.attempt_t, INF)
        head = jnp.argmin(arr).astype(jnp.int32)
        big_wait = jnp.logical_and(waiting, tb.big == 1)
        big_head = jnp.argmin(
            jnp.where(big_wait, st.attempt_t, INF)).astype(jnp.int32)
        ctr = st.pol["shfl_ctr"][l]
        shuffle = jnp.logical_and(jnp.any(big_wait),
                                  ctr < pm.pol["shfl_bound"])
        pick = jnp.where(shuffle, big_head, head)
        # Count consecutive head-bypasses; granting the head (shuffled
        # or not) resets the bound.
        bypassed = jnp.logical_and(shuffle, pick != head)
        has = jnp.logical_and(jnp.any(waiting), cond)
        new_ctr = jnp.where(bypassed, ctr + 1, 0)
        st = st._replace(pol=dict(
            st.pol, shfl_ctr=st.pol["shfl_ctr"].at[l].set(
                jnp.where(has, new_ctr, ctr))))
        return grant(st, cfg, tb, pm, has, pick, t, wakeup=True)
