"""The lock-policy contract + the shared simulator vocabulary.

A :class:`LockPolicy` plugs into the policy-agnostic event loop of
:mod:`repro.core.simlock` through four hooks:

* ``on_acquire``        — a core's non-critical section ended; decide
  grab / queue / standby / spin (phase NONCRIT fires it).
* ``on_standby_expiry`` — a reorder window expired (phase STANDBY; only
  reachable when ``uses_standby`` is True, which also gates whether the
  handler exists in the compiled HLO at all).
* ``on_release``        — policy-private feedback at a critical-section
  release (e.g. LibASL's AIMD window update); the generic handler has
  already recorded the latencies.
* ``pick_next``         — the holder released; select & grant the next
  holder of lock ``l`` (the caller cleared ``holder[l]``; leaving the
  lock free is a legal outcome).

Every hook is *fully conditional*: it takes a ``cond`` and must commit
no state when it is false — combine ``cond`` only via ``logical_and`` /
``where`` (it may be the Python literal ``True`` on the single-run
``lax.switch`` path).  Hooks must also be **shape-independent**: a
padded (inactive) core must never perturb a decision — use
:func:`weighted_pick` for RNG choices and mask scans with INF/0 so the
batched, padded, sharded and single paths stay bit-identical.

State discipline: a policy *declares* the slots it owns —

* ``param_slots`` / ``table_slots`` name the :class:`SimParams` /
  :class:`SimTables` fields it reads (documentation + conformance);
* ``state_slots`` name entries of the ``SimState.pol`` dict (or core
  ``SimState`` fields) it owns; new per-run state goes into the ``pol``
  dict via :meth:`LockPolicy.init_state`, new traced knobs into the
  ``SimParams.pol`` dict via :meth:`LockPolicy.init_params` (fed from
  ``SimConfig.policy_kw``, canonicalized out of the jit key);
* ``sweep_axes`` maps sweep-axis names onto ``pol`` param slots, so a
  policy knob sweeps like any built-in axis (one executable);
* ``own_columns`` names the per-core ``SimTables.col`` columns the
  policy registered via :func:`repro.core.columns.register_column` —
  declared ``(name, dtype, default, sweepable)`` specs that batch as
  table sweep axes (docs/simulator.md §Policy-owned table columns).

Registration: decorate the class with ``@register`` (see
``repro.core.policies``); the registry order fixes the policy ids.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.faults import model as flt

# Phases == event types (one pending event per core; the phase of the
# core at the head of the event clock selects the handler).
NONCRIT, STANDBY, QUEUED, HOLDER, SPIN, ARRIVAL = 0, 1, 2, 3, 4, 5
INF = jnp.int32(1 << 30)

# 1 tick = 10 ns
US = 100  # ticks per microsecond


def ticks(us: float) -> int:
    return int(round(us * US))


def policy_opts(cfg) -> dict:
    """``SimConfig.policy_kw`` as a dict (policy-owned numeric knobs)."""
    return dict(cfg.policy_kw)


# --------------------------------------------------------------------------
# Queue helpers (ring buffers). All conditional: ops are no-ops when !cond.
# --------------------------------------------------------------------------

def enq(st, cond, l, b, c):
    n = st.q.shape[-1]
    pos = st.q_tail[l, b] % n
    val = jnp.where(cond, c, st.q[l, b, pos])
    q = st.q.at[l, b, pos].set(val)
    q_tail = st.q_tail.at[l, b].add(jnp.where(cond, 1, 0))
    return st._replace(q=q, q_tail=q_tail)


def deq(st, cond, l, b):
    """Returns (st, core) — core = -1 when !cond or empty."""
    n = st.q.shape[-1]
    nonempty = st.q_tail[l, b] > st.q_head[l, b]
    do = jnp.logical_and(cond, nonempty)
    pos = st.q_head[l, b] % n
    c = jnp.where(do, st.q[l, b, pos], -1)
    q_head = st.q_head.at[l, b].add(jnp.where(do, 1, 0))
    return st._replace(q_head=q_head), c


def qlen(st, l, b):
    return st.q_tail[l, b] - st.q_head[l, b]


def weighted_pick(key, weights):
    """Draw an index ~ weights with ONE scalar uniform (shape-independent:
    zero-weight padding entries never win and never perturb the draw, so a
    padded-core run is bit-identical to the unpadded one).  The total is
    cum[-1], NOT jnp.sum: a differently-ordered reduce could land one ulp
    above the cumsum, letting u fall past every threshold and "pick" a
    zero-weight index."""
    cum = jnp.cumsum(weights)
    total = cum[-1]
    u = jax.random.uniform(key) * total
    pick = jnp.argmax(cum > u).astype(jnp.int32)
    return pick, total > 0.0


def lock_of(st, cfg, tb, c):
    """The lock core ``c`` currently contends.  Key-sharded mode
    (``cfg.n_keys > 0`` — a static gate bit after canonicalization)
    reads the per-epoch Zipf-drawn lock (``SimState.cur_lock``, set by
    the engine's epoch-boundary key draws); otherwise the static
    per-segment program lock — the pre-multi-lock expression, so
    key-off runs compile identical HLO (bit-parity by construction)."""
    if cfg.n_keys > 0:
        return st.cur_lock[c]
    return tb.seg_lock[st.seg[c]]


def lock_vec(st, cfg, tb):
    """Per-core effective lock ids as a vector (``i32[N]``) — the
    vectorized :func:`lock_of`, used by waiter-mask scans."""
    if cfg.n_keys > 0:
        return st.cur_lock
    return tb.seg_lock[st.seg]


def grant(st, cfg, tb, pm, cond, c, t, wakeup=False):
    """Make core c (if cond) the holder of its lock; schedule its release.
    ``wakeup=True`` models a blocking lock's parked-waiter handoff latency
    (Bench-6): only queue-pop handoffs pay it, spinners/standbys do not."""
    c_safe = jnp.maximum(c, 0)
    l = lock_of(st, cfg, tb, c_safe)
    dur = tb.cs_dur[c_safe, st.seg[c_safe]]
    if cfg.wl:
        # Current-epoch service multiplier (drawn at the last epoch end);
        # floor at 1 tick so a heavy-tailed draw can't create a 0-length
        # critical section.
        dur = jnp.maximum((dur.astype(jnp.float32)
                           * st.svc_scale[c_safe]).astype(jnp.int32), 1)
    if cfg.straggle_rate > 0.0 or cfg.preempt_rate > 0.0:
        # Fault injection (repro.faults): the draw index is the core's
        # CS counter (counter-pure — batching/chunking/sharding cannot
        # perturb it), the rate is multiplied by the per-core
        # eligibility mask, and both terms are additive wheres, so a
        # zero rate is bit-identical to a fault-free run.
        gix = st.cs_cnt[c_safe]
        eligible = tb.col["ft_mask"][c_safe]
    if cfg.straggle_rate > 0.0:
        # Straggler spike: this CS runs straggle_scale x long (DVFS /
        # migration made the core slow) — applied before preemption so
        # the stall is independent of the spiked duration.
        dur = dur + flt.straggle_extra(pm.seed, c_safe, gix, dur,
                                       pm.straggle_rate * eligible,
                                       pm.straggle_scale)
    if cfg.preempt_rate > 0.0:
        # Lock-holder preemption: the holder is descheduled mid-CS for
        # an Exp(preempt_scale) stall; every waiter eats it.
        dur = dur + flt.preempt_extra(pm.seed, c_safe, gix,
                                      pm.preempt_rate * eligible,
                                      pm.preempt_scale)
    if wakeup and cfg.wakeup_us > 0.0:
        dur = dur + pm.wakeup
    holder = st.holder.at[l].set(jnp.where(cond, c_safe, st.holder[l]))
    phase = st.phase.at[c_safe].set(
        jnp.where(cond, HOLDER, st.phase[c_safe]))
    t_ready = st.t_ready.at[c_safe].set(
        jnp.where(cond, t + dur, st.t_ready[c_safe]))
    return st._replace(holder=holder, phase=phase, t_ready=t_ready)


def park(st, cond, c, new_phase):
    """Send core c (if cond) into a passive phase (QUEUED/SPIN) — it
    carries t_ready=INF and is woken by a releaser's pick_next."""
    return st._replace(
        phase=st.phase.at[c].set(jnp.where(cond, new_phase, st.phase[c])),
        t_ready=st.t_ready.at[c].set(jnp.where(cond, INF, st.t_ready[c])))


def waiting_mask(st, cfg, tb, l, phase=QUEUED):
    """Cores parked in ``phase`` currently contending lock l — the
    scan-based waiter set used by queue-less policies (edf/shfl/ks_*)."""
    return jnp.logical_and(st.phase == phase, lock_vec(st, cfg, tb) == l)


def queueless_acquire(st, cfg, tb, pm, c, t, cond):
    """The queue-less acquire step (edf/shfl): grab when the lock is free
    and nobody waits, else park in QUEUED — the releaser's pick_next
    scans the waiting mask instead of popping a ring buffer."""
    l = lock_of(st, cfg, tb, c)
    free = st.holder[l] == -1
    no_wait = jnp.logical_not(jnp.any(waiting_mask(st, cfg, tb, l)))
    can_grab = jnp.logical_and(free, no_wait)
    grab = jnp.logical_and(can_grab, cond)
    wait = jnp.logical_and(jnp.logical_not(can_grab), cond)
    st = grant(st, cfg, tb, pm, grab, c, t)
    return park(st, wait, c, QUEUED)


# --------------------------------------------------------------------------
# The policy contract
# --------------------------------------------------------------------------

class LockPolicy:
    """Base class: one instance per registered policy (stateless — all
    per-run state lives in SimState / SimState.pol)."""

    #: registry key; also the ``SimConfig.policy`` value.
    name: str = None
    #: True iff the policy parks cores in STANDBY (gates the standby
    #: handler's existence in the compiled step).
    uses_standby: bool = False
    #: True iff the policy reads the per-epoch read/write uniform
    #: (``SimState.cur_rw``, CREW-style policies).  Statically gates
    #: whether the engine's key-sharded epoch draws include the
    #: STREAM_RW uniform at all (key-off runs never draw it).
    uses_rw: bool = False
    #: SimParams fields this policy reads (declarative; conformance-checked).
    param_slots: tuple = ()
    #: SimTables slots this policy reads: core fields by name, registered
    #: per-core columns as ``"col.<name>"`` (e.g. ``"col.slo_scale"``).
    table_slots: tuple = ()
    #: Names of registered ``SimTables.col`` columns this policy *owns*
    #: (it called ``repro.core.columns.register_column`` for them at
    #: import time) — conformance asserts they exist and sweep.
    own_columns: tuple = ()
    #: SimState fields / SimState.pol entries this policy owns.
    state_slots: tuple = ()
    #: sweep-axis name -> SimParams.pol slot (policy knobs as batch axes).
    sweep_axes: dict = {}
    #: host-side admission-scheduler analogue (repro.core.asl_schedule
    #: key) and fleet-dispatch analogue (repro.serving.dispatch policy
    #: name); None when the policy has no host counterpart.
    host_scheduler: str = None
    host_dispatch: str = None

    # -- state-slot declaration -------------------------------------------
    def init_params(self, cfg) -> dict:
        """Policy-owned traced knobs -> ``SimParams.pol``, as numpy
        scalars (the engine places them; read ``policy_opts(cfg)`` for
        defaults; called with the REAL cfg)."""
        return {}

    def init_state(self, cfg, tb, pm) -> dict:
        """Policy-owned per-run state -> ``SimState.pol`` (called with
        the canonicalized cfg: read numeric knobs from ``pm``, not cfg)."""
        return {}

    # -- event hooks -------------------------------------------------------
    def on_acquire(self, st, cfg, tb, pm, c, t, cond):
        raise NotImplementedError

    def on_standby_expiry(self, st, cfg, tb, pm, c, t, cond):
        return st

    def on_release(self, st, cfg, tb, pm, c, t, ep_latency, last, cond):
        return st

    def pick_next(self, st, cfg, tb, pm, l, t, cond):
        raise NotImplementedError
