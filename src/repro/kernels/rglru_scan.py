"""RG-LRU linear recurrence as a Pallas TPU kernel.

``h_t = a_t * h_{t-1} + x_t`` over the sequence, per (batch, channel) lane.
TPU adaptation: instead of the GPU block-parallel-scan formulation, we tile
channels into VREG-aligned blocks, keep the carry ``h`` resident in VMEM,
and walk sequence chunks along the innermost sequential grid dim — each
(a, x) tile crosses HBM exactly once and the recurrence itself is pure VPU
elementwise work (there is no matmul to feed the MXU here; the op is
bandwidth-bound by construction, which is why fusing the neighbouring
projections matters more than the scan itself — see EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, h0_ref, o_ref, h_ref, *, block_s):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)    # [bs, bc]
    x = x_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + x[t]
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_s, step, h_ref[...])


def rglru_scan(a, x, h0=None, *, block_s=128, block_c=128, interpret=False):
    """a, x: [B,S,R]; h0: [B,R] -> h sequence [B,S,R]."""
    b, s, r = a.shape
    assert s % block_s == 0 and r % block_c == 0, (s, r)
    if h0 is None:
        h0 = jnp.zeros((b, r), jnp.float32)
    ns, nc = s // block_s, r // block_c

    kernel = functools.partial(_kernel, block_s=block_s)
    return pl.pallas_call(
        kernel,
        grid=(b * nc, ns),
        in_specs=[
            pl.BlockSpec((1, block_s, block_c),
                         lambda bc, si, nc=nc: (bc // nc, si, bc % nc)),
            pl.BlockSpec((1, block_s, block_c),
                         lambda bc, si, nc=nc: (bc // nc, si, bc % nc)),
            pl.BlockSpec((1, block_c),
                         lambda bc, si, nc=nc: (bc // nc, bc % nc)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_c),
                               lambda bc, si, nc=nc: (bc // nc, si, bc % nc)),
        out_shape=jax.ShapeDtypeStruct((b, s, r), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_c,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, x, h0)
