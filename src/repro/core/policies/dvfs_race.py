"""dvfs_race — asymmetry- and DVFS-aware race-to-idle granting.

Race-to-idle (Saez 2024; Costero 2015 big.LITTLE schedulers): under
contention, hand the lock to the core that retires critical sections
fastest — big cores and high-DVFS cores — so the contention burst
finishes early and the slow cores can sit in their low-power wait
states instead of prolonging the busy period.  The alternative
("slow and steady") runs littles at low frequency and accepts the
longer busy period; which wins on energy-delay is exactly what the
``energy_efficiency`` figure measures across policies.

The grant priority is ``race_w * dvfs * (1 + big)``:

* ``dvfs`` — the energy layer's per-core frequency column
  (repro.core.energy): a core racing at 2x clock is twice as attractive.
* ``big`` — the static asymmetry bit doubles a big core's weight
  (its CS speedup is the paper's Sysbench gap).
* ``race_w`` — this policy's OWN registered column (declared here via
  :func:`repro.core.columns.register_column`): a per-core override to
  bias or ban cores from racing (e.g. thermally-throttled cores at 0).
  It exercises all three ownership mechanisms at once: an owned
  SimTables column (``race_w``), a traced ``SimParams.pol`` knob
  (``race_bound``, sweepable), and ``SimState.pol`` state
  (``race_ctr``).

Starvation is bounded exactly like shfl: after ``race_bound``
consecutive grants that bypassed the FIFO head, the head is forced
through — a slow waiter is deferred at most ``race_bound`` grants.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.columns import ColumnSpec, register_column
# Guarantees the ``dvfs`` column this policy reads is registered even
# when the simulator module has not been imported yet.
from repro.core import energy as _energy  # noqa: F401
from repro.core.policies import register
from repro.core.policies.base import (INF, LockPolicy, grant, policy_opts,
                                      queueless_acquire, waiting_mask)

register_column(ColumnSpec(
    name="race_w", dtype="f32", default=1.0, owner="dvfs_race",
    doc="per-core race-to-idle priority weight (0 bans a core from "
        "being shuffled forward; it still gets the forced-head grant)"))

DEFAULT_BOUND = 8


@register
class DvfsRacePolicy(LockPolicy):
    name = "dvfs_race"
    table_slots = ("big", "col.dvfs", "col.race_w")
    own_columns = ("race_w",)
    state_slots = ("race_ctr",)
    param_slots = ("pol.race_bound",)
    sweep_axes = {"race_bound": "race_bound"}

    def init_params(self, cfg):
        return {"race_bound": np.int32(
            policy_opts(cfg).get("race_bound", DEFAULT_BOUND))}

    def init_state(self, cfg, tb, pm):
        return {"race_ctr": jnp.zeros(cfg.n_locks, jnp.int32)}

    def on_acquire(self, st, cfg, tb, pm, c, t, cond):
        return queueless_acquire(st, cfg, tb, pm, c, t, cond)

    def pick_next(self, st, cfg, tb, pm, l, t, cond):
        waiting = waiting_mask(st, cfg, tb, l)
        speed = (tb.col["race_w"] * tb.col["dvfs"]
                 * (1.0 + tb.big.astype(jnp.float32)))
        # Masked score: non-waiters (and padded cores) at -1 can never
        # win, so batched/padded/sharded runs stay bit-identical.
        score = jnp.where(waiting, speed, -1.0)
        best = jnp.max(score)
        tie = jnp.logical_and(waiting, score == best)
        fast = jnp.argmin(jnp.where(tie, st.attempt_t,
                                    INF)).astype(jnp.int32)
        head = jnp.argmin(jnp.where(waiting, st.attempt_t,
                                    INF)).astype(jnp.int32)
        ctr = st.pol["race_ctr"][l]
        pick = jnp.where(ctr >= pm.pol["race_bound"], head, fast)
        bypassed = pick != head
        has = jnp.logical_and(jnp.any(waiting), cond)
        new_ctr = jnp.where(bypassed, ctr + 1, 0)
        st = st._replace(pol=dict(
            st.pol, race_ctr=st.pol["race_ctr"].at[l].set(
                jnp.where(has, new_ctr, ctr))))
        return grant(st, cfg, tb, pm, has, pick, t, wakeup=True)
