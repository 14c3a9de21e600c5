"""Split-K decode attention as a Pallas TPU kernel (flash-decoding style).

decode_32k is memory-bound: one query token reads the whole KV cache.  The
kernel streams the cache in ``block_k`` VMEM tiles along the innermost
sequential grid dim with an online-softmax accumulator, like flash
attention, but the query tile is the *GQA group*: the g q-heads that share
one kv head form the tile rows (padded to the 8-row VREG granule), so the
MXU runs [g, d] x [d, block_k] instead of degenerate [1, d] work.

Valid-length masking uses scalar-prefetched ``lengths`` (SMEM) — the block
grid is sized for the full cache but fully-invalid blocks are skipped, so
short sequences don't pay for the ring capacity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale, block_k, nk):
    bi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[bi]
    k_start = ki * block_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [g, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        jk = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(jk < length, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, block_k=256,
                     interpret=False):
    """q: [B,H,dh]; caches: [B,K,T,dh]; lengths: [B] -> [B,H,dh]."""
    b, h, dh = q.shape
    kh, t = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    assert t % block_k == 0, (t, block_k)
    nk = t // block_k
    scale = 1.0 / np.sqrt(dh)
    qg = q.reshape(b, kh, g, dh)

    kernel = functools.partial(_kernel, scale=scale, block_k=block_k, nk=nk)
    out = _call_with_prefetch(kernel, qg, k_cache, v_cache, lengths, b, kh,
                              g, dh, block_k, nk, interpret)
    return out.reshape(b, h, dh)


def _call_with_prefetch(kernel, qg, k_cache, v_cache, lengths, b, kh, g, dh,
                        block_k, nk, interpret):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda bi, hi, ki, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bi, hi, ki, lens: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bi, hi, ki, lens: (bi, hi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda bi, hi, ki, lens: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, dh), qg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qg, k_cache, v_cache)
