"""Pallas kernel for the simulator's fused masked step.

One :func:`fused_chunk` call retires a whole ``cfg.chunk`` of events
inside a single ``pl.pallas_call``: the traced pytrees (``SimTables``,
``SimParams``, ``SimState``) are *packed* — leaves grouped by
(dtype, shape) and stacked into a few i32/f32/u32 arrays — handed to
the kernel as whole-array VMEM refs, unpacked back into pytrees inside
the kernel, and the per-event step (argmin over the event clock +
masked scatter/gather handler updates) runs as an in-kernel
``lax.fori_loop``.  On a TPU the whole hot state would then stay
VMEM-resident for the duration of the chunk.

The step callable itself is the engine's ``simlock._step`` closure —
the kernel adds no semantics of its own, so results are bit-identical
to the plain jnp lowering (``tests/test_fused.py`` asserts exact
equality across every registered policy).

Interpret mode follows the platform the call is lowered for: the CPU
lowering interprets the kernel (correctness only), every other
platform compiles it with Mosaic and raises whatever Mosaic refuses.
On TPU v5e, Mosaic refuses the engine's step today: ``argmin`` over the
i32 event clock ("Only float32 is supported") and, past that, the
per-core ``dynamic_slice`` gathers (docs/simulator.md §Fused step
kernel).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _group(leaves) -> dict:
    """Leaf indices grouped by (dtype, shape) — the packing layout.
    Insertion-ordered, so pack/unpack agree across call and kernel."""
    groups: dict = {}
    for i, x in enumerate(leaves):
        key = (jnp.dtype(x.dtype).name, tuple(jnp.shape(x)))
        groups.setdefault(key, []).append(i)
    return groups


def _stack(leaves, idx):
    """One packed array: the group's leaves stacked on a leading axis,
    kept at least 2-D so a vmapped call's squeezed cell axis never lands
    in the last two block dimensions (Mosaic's (8, 128) tiling rule
    holds for a block dim equal to the array dim, not for a squeezed 1)."""
    x = jnp.stack([leaves[i] for i in idx])
    return x.reshape(len(idx), 1) if x.ndim == 1 else x


def _pack(leaves, groups):
    return [_stack(leaves, idx) for idx in groups.values()]


def _unpack(packed, groups, n_leaves):
    """Split packed refs or arrays back into per-leaf values (``ref[j]``
    is a load, so inside the kernel this yields values, not refs)."""
    out = [None] * n_leaves
    for r, ((_, shape), idx) in zip(packed, groups.items()):
        for j, i in enumerate(idx):
            out[i] = r[j].reshape(shape)
    return out


def fused_chunk(step, tb, pm, st, chunk: int):
    """Advance ``st`` by ``chunk`` events of ``step`` in one kernel.

    ``step(tb, pm, st) -> st`` must be shape-preserving and already
    horizon-guarded (the engine's live-guard retires past-horizon
    steps as no-ops, which is what makes a fixed-size chunk safe).
    """
    # Pallas kernels may not close over constant arrays (e.g. the
    # engine's horizon scalar — jax.closure_convert would leave such
    # integer consts baked in): trace the step to a jaxpr and hoist
    # ALL its consts into explicit inputs, packed with the read-only
    # tree.
    closed = jax.make_jaxpr(step)(tb, pm, st)
    consts = tuple(closed.consts)
    out_def = jax.tree_util.tree_structure(st)

    def step_c(tb_, pm_, st_, consts_):
        flat = jax.tree_util.tree_leaves((tb_, pm_, st_))
        out = jax.core.eval_jaxpr(closed.jaxpr, list(consts_), *flat)
        return jax.tree_util.tree_unflatten(out_def, out)

    ro_leaves, ro_def = jax.tree_util.tree_flatten((tb, pm, consts))
    st_leaves, st_def = jax.tree_util.tree_flatten(st)
    ro_groups = _group(ro_leaves)
    st_groups = _group(st_leaves)
    ro_packed = _pack(ro_leaves, ro_groups)
    st_packed = _pack(st_leaves, st_groups)
    n_ro, n_st = len(ro_packed), len(st_packed)

    def kernel(*refs):
        ro_refs = refs[:n_ro]
        st_refs = refs[n_ro:n_ro + n_st]
        out_refs = refs[n_ro + n_st:]
        tb_k, pm_k, consts_k = jax.tree_util.tree_unflatten(
            ro_def, _unpack(ro_refs, ro_groups, len(ro_leaves)))
        st_k = jax.tree_util.tree_unflatten(
            st_def, _unpack(st_refs, st_groups, len(st_leaves)))

        st_out = jax.lax.fori_loop(
            0, max(chunk, 1),
            lambda _, s: step_c(tb_k, pm_k, s, consts_k), st_k)
        out_leaves = jax.tree_util.tree_leaves(st_out)
        for r, idx in zip(out_refs, st_groups.values()):
            r[...] = _stack(out_leaves, idx)

    def call(*packed, interpret):
        return pl.pallas_call(
            kernel,
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in st_packed],
            interpret=interpret,
        )(*packed)

    # Interpret only where the call is lowered for the CPU.
    outs = jax.lax.platform_dependent(
        *ro_packed, *st_packed, cpu=partial(call, interpret=True),
        default=partial(call, interpret=False))
    return jax.tree_util.tree_unflatten(
        st_def, _unpack(outs, st_groups, len(st_leaves)))
