"""Flash attention (forward) as a Pallas TPU kernel.

Design (TPU-native, not a CUDA port):

* grid = (batch, q_heads, q_blocks, kv_blocks); the kv_blocks dim is the
  innermost *sequential* ("arbitrary") dimension, so the online-softmax
  running state (m, l, acc) lives in VMEM scratch that persists across kv
  steps for one (b, h, qi) output tile — the MXU sees [block_q, d] x
  [d, block_k] matmuls with fp32 accumulation.
* GQA without KV expansion: the K/V BlockSpec index_map folds the
  q-head -> kv-head mapping (``h // group``), so grouped heads stream the
  same KV tile from HBM (XLA would materialize the repeat).
* Causal + local-window masking at block granularity: fully-masked kv
  blocks are skipped with ``pl.when`` (halves the work for causal; for a
  2048-window at 32k the kernel touches only ~1/16 of the blocks).
* block_q x head_dim tiles are MXU/VREG aligned (multiples of (8, 128) for
  f32, (16, 128) bf16); callers pick block sizes via ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, block_q, block_k, nk, kv_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # Block-level skip: causal => skip blocks entirely above the diagonal;
    # local window => skip blocks entirely left of the window.
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
        if window:
            run = jnp.logical_and(
                run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)            # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        iq = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        jk = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jk < kv_len
        if causal:
            mask = jnp.logical_and(mask, jk <= iq)
            if window:
                mask = jnp.logical_and(mask, jk > iq - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=False):
    """q: [B,H,S,dh]; k,v: [B,K,T,dh] -> [B,H,S,dh]."""
    b, h, s, dh = q.shape
    kh, t = k.shape[1], k.shape[2]
    g = h // kh
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    nq, nk = s // block_q, t // block_k
    scale = 1.0 / np.sqrt(dh)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk, kv_len=t)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max
            pltpu.VMEM((block_q,), jnp.float32),      # running denom
            pltpu.VMEM((block_q, dh), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
