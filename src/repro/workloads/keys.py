"""Key-sharded Zipf traffic — counter-pure key streams and the bucketed
key→lock index (docs/workloads.md §Key-sharded traffic).

Production datastore traffic is millions of skewed keys hammering
thousands of bucket locks.  This module supplies the two pieces the
simulator needs to model that:

* **A counter-pure Zipf key generator.**  Every key draw is a pure
  function of ``(seed, core, epoch)`` through the ``STREAM_KEY`` stream
  — the same RNG discipline as every other workload draw
  (``repro.workloads.generators``): batching, sharding, chunking and
  event interleaving cannot perturb which key an epoch touches, and the
  host can reconstruct the full key table (:func:`key_table`).
  Sampling uses the Gray et al. / YCSB ``ZipfianGenerator``
  approximation — an O(1) branchless inverse-CDF built from three
  host-precomputed constants (:func:`zipf_consts`), so the device-side
  sampler (:func:`zipf_key`) is a handful of jnp ops with the key count
  and exponent riding *traced* (sweepable inside one executable).

* **A bucketed key→lock index.**  :func:`key_to_lock` maps key ``k`` to
  bucket ``k % n_locks`` — deliberately rank-preserving: key 0 (the
  hottest) lands on lock 0, so "the hot bucket" is well-defined and the
  key-affinity policies (``ks_erew``/``ks_crew``) can pin it to a big
  core.  ``n_locks`` rides traced too, so lock-count sweeps share the
  executable.

Keys are ranked by popularity: ``P(key = k) ∝ 1/(k+1)^theta``.
``theta = 0`` is uniform, ``theta ≈ 0.99`` is the YCSB default,
``theta > 1`` concentrates mass on a handful of keys (hot-key
collapse).  ``theta`` is nudged off the harmonic pole at 1.0 host-side
(:func:`zipf_consts` returns the nudged value; use it everywhere).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.workloads.generators import (STREAM_KEY, STREAM_RW,
                                        counter_uniform, stream_key)

#: Exponents within this distance of the theta=1 pole are nudged off it
#: (the Gray/YCSB constants divide by ``1 - theta``).
_POLE_EPS = 1e-4
#: The most keys a draw can reach: :func:`zipf_key` computes the rank in
#: float32, which holds every integer up to 2**24 and only even ones above.
MAX_KEYS = 1 << 24
TOO_MANY_KEYS = ("the Zipf rank is drawn in float32, whose integers above "
                 "2**24 are even only, so odd tail ranks could never be "
                 "drawn and rank % n_locks would skew the buckets")


def zipf_consts(n_keys: int, theta: float):
    """Host-precomputed sampler constants ``(theta', zeta, eta, alpha)``.

    ``theta'`` is the pole-nudged exponent actually used — store THAT in
    the traced params so host and device agree bit-for-bit.  ``zeta`` is
    the generalized harmonic number ``H_{n,theta}``; ``eta``/``alpha``
    are the Gray et al. rejection-free inverse-CDF constants.  All three
    ride traced (f32) so ``n_keys`` / ``zipf_theta`` sweep as batch
    axes — per-cell values are recomputed here by the sweep engine.
    """
    n_keys = int(n_keys)
    theta = float(theta)
    if n_keys < 1:
        raise ValueError(f"zipf_consts: n_keys must be >= 1, got {n_keys}")
    if n_keys > MAX_KEYS:
        raise ValueError(f"zipf_consts: n_keys {n_keys} exceeds 2**24: "
                         f"{TOO_MANY_KEYS}")
    if not np.isfinite(theta) or theta < 0.0:
        raise ValueError("zipf_consts: theta must be finite and >= 0, "
                         f"got {theta!r}")
    if abs(theta - 1.0) < _POLE_EPS:
        theta = 1.0 - _POLE_EPS if theta <= 1.0 else 1.0 + _POLE_EPS
    ranks = np.arange(1, n_keys + 1, dtype=np.float64)
    zeta = float(np.sum(ranks ** -theta))
    zeta2 = float(1.0 + 0.5 ** theta) if n_keys >= 2 else zeta
    alpha = 1.0 / (1.0 - theta)
    denom = 1.0 - zeta2 / zeta
    # n_keys 1..2 degenerate: the tail branch is never taken; keep eta
    # finite so the traced constant stays well-defined.
    eta = (1.0 - (2.0 / n_keys) ** (1.0 - theta)) / denom \
        if n_keys > 2 and abs(denom) > 1e-12 else 1.0
    return theta, float(zeta), float(eta), float(alpha)


def zipf_key(u, n_keys, theta, zeta, eta, alpha):
    """Branchless O(1) Zipf(n_keys, theta) rank from a uniform ``u``.

    The Gray et al. / YCSB inverse-CDF approximation: exact for ranks 0
    and 1, a smooth power-law inverse for the tail.  Every argument may
    be traced (``n_keys`` included), so sweeps over key count and
    exponent batch inside one executable.  Returns i32 in
    ``[0, n_keys)``."""
    n = jnp.asarray(n_keys, jnp.float32)
    u = jnp.asarray(u, jnp.float32)
    uz = u * zeta
    zeta2 = 1.0 + 0.5 ** jnp.asarray(theta, jnp.float32)
    tail = jnp.floor(n * (eta * u - eta + 1.0) ** alpha)
    k = jnp.where(uz < 1.0, 0.0, jnp.where(uz < zeta2, 1.0, tail))
    return jnp.clip(k, 0.0, n - 1.0).astype(jnp.int32)


def key_to_lock(key, n_locks):
    """Bucketed key→lock index: ``key % n_locks`` — rank-preserving, so
    key 0 (hottest) always lands on lock 0 (the hot bucket) and hotter
    keys map to lower lock ids.  ``n_locks`` may be traced (the active
    lock count of a lock-count sweep cell)."""
    return jnp.mod(jnp.asarray(key, jnp.int32),
                   jnp.maximum(jnp.asarray(n_locks, jnp.int32), 1))


# --------------------------------------------------------------------------
# Per-(core, epoch) streams — the device-side contract
# --------------------------------------------------------------------------

def epoch_key_u(seed, core, epoch):
    """The key-stream uniform for (core, epoch) — pure counter draw."""
    return counter_uniform(stream_key(seed, STREAM_KEY), core, epoch)


def epoch_rw_u(seed, core, epoch):
    """The read/write-stream uniform for (core, epoch) — CREW policies
    classify an epoch as a write when this falls below the traced write
    fraction."""
    return counter_uniform(stream_key(seed, STREAM_RW), core, epoch)


def epoch_lock(seed, core, epoch, n_keys, theta, zeta, eta, alpha,
               n_locks):
    """The lock a (core, epoch) contends: Zipf key → bucket, end to end
    counter-pure.  This is the one composition the engine calls."""
    u = epoch_key_u(seed, core, epoch)
    return key_to_lock(zipf_key(u, n_keys, theta, zeta, eta, alpha),
                       n_locks)


# --------------------------------------------------------------------------
# Host reconstruction (tests / analysis)
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(1, 2))
def _u_grid(key, n_cores: int, n_epochs: int):
    cs = jnp.arange(n_cores, dtype=jnp.int32)
    es = jnp.arange(n_epochs, dtype=jnp.int32)
    return jax.vmap(lambda c: jax.vmap(
        lambda e: counter_uniform(key, c, e))(es))(cs)


#: :func:`zipf_key` compiled whole, with its constants traced, as the
#: engine runs it.  Op by op, the base ``eta * u - eta + 1`` rounds
#: otherwise than XLA's fused form, and at 2**24 keys (exponent 100 at
#: theta 0.99) one ulp of the base moves a rank by about 100: 3% of the
#: ranks would differ from the device's.
_zipf_key_traced = jax.jit(zipf_key)


def key_table(seed, n_cores: int, n_epochs: int, n_keys: int,
              theta: float) -> np.ndarray:
    """Host reconstruction of the device key stream: ``[c, e]`` is the
    Zipf key core ``c`` draws for epoch ``e`` — element-wise counter-
    pure, so the table is prefix-invariant in BOTH dimensions (growing
    it never perturbs existing entries)."""
    th, zeta, eta, alpha = zipf_consts(n_keys, theta)
    u = _u_grid(stream_key(seed, STREAM_KEY), n_cores, n_epochs)
    return np.asarray(_zipf_key_traced(u, n_keys, th, zeta, eta, alpha))


def lock_table(seed, n_cores: int, n_epochs: int, n_keys: int,
               theta: float, n_locks: int) -> np.ndarray:
    """Host reconstruction of the per-(core, epoch) lock ids the engine
    consumes (``key_table`` pushed through the bucket index)."""
    return np.asarray(key_to_lock(
        key_table(seed, n_cores, n_epochs, n_keys, theta), n_locks))


def rw_table(seed, n_cores: int, n_epochs: int,
             write_frac: float) -> np.ndarray:
    """Host reconstruction of the CREW write bits (1 = write epoch)."""
    u = np.asarray(_u_grid(stream_key(seed, STREAM_RW),
                           n_cores, n_epochs))
    return (u < write_frac).astype(np.int32)


def zipf_pmf(n_keys: int, theta: float) -> np.ndarray:
    """The exact target pmf ``P(key = k) ∝ 1/(k+1)^theta`` (moments
    tests compare empirical frequencies against this)."""
    th, zeta, _, _ = zipf_consts(n_keys, theta)
    ranks = np.arange(1, n_keys + 1, dtype=np.float64)
    return ranks ** -th / zeta
