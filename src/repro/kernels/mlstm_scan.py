"""mLSTM matrix-memory recurrence as a Pallas TPU kernel.

Per (batch, head): C_t = f' C + i' v k^T with stabilized exponential gates
(see :mod:`repro.kernels.ref`).  TPU adaptation: the [dh, dh] matrix memory
and its normalizer stay **VMEM-resident** across the whole sequence — the
kernel streams q/k/v/gate tiles chunk-by-chunk along the sequential grid
dim, so HBM traffic is exactly one pass over qkv plus one [dh,dh] state
spill at the end, instead of the S outer-product round-trips a naive XLA
scan materializes.  Within a chunk the recurrence is a fori_loop of rank-1
MXU updates; the q readout ``C q`` reuses the resident state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, ig_ref, fg_ref, c0_ref, n0_ref, m0_ref,
            o_ref, cT_ref, nT_ref, mT_ref, C_ref, n_ref, m_ref, *, block_s,
            ns):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        C_ref[...] = c0_ref[0].astype(jnp.float32)
        n_ref[...] = n0_ref[0].astype(jnp.float32)
        m_ref[...] = m0_ref[0].astype(jnp.float32)

    q = q_ref[0].astype(jnp.float32)      # [bs, dh]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    ig = ig_ref[0].astype(jnp.float32)    # [bs]
    fg = fg_ref[0].astype(jnp.float32)

    def step(t, carry):
        C, n, m = carry
        log_f = -jax.nn.softplus(-fg[t])
        m_new = jnp.maximum(log_f + m, ig[t])
        i_p = jnp.exp(ig[t] - m_new)
        f_p = jnp.exp(log_f + m - m_new)
        C = f_p * C + i_p * (v[t][:, None] * k[t][None, :])
        n = f_p * n + i_p * k[t]
        num = C @ q[t]
        den = jnp.abs(jnp.dot(n, q[t]))
        o_ref[0, t, :] = (num / jnp.maximum(den, 1.0)).astype(o_ref.dtype)
        return C, n, m_new

    C, n, m = jax.lax.fori_loop(
        0, block_s, step, (C_ref[...], n_ref[...], m_ref[...]))
    C_ref[...] = C
    n_ref[...] = n
    m_ref[...] = m

    @pl.when(si == ns - 1)
    def _final():
        cT_ref[0] = C
        nT_ref[0] = n
        mT_ref[0] = m


def mlstm_scan(q, k, v, i_gate, f_gate, carry=None, *, block_s=128,
               interpret=False):
    """q,k,v: [B,H,S,dh]; gates: [B,H,S] -> (h [B,H,S,dh], (C,n,m))."""
    b, h, s, dh = q.shape
    assert s % block_s == 0
    ns = s // block_s
    if carry is None:
        c0 = jnp.zeros((b * h, dh, dh), jnp.float32)
        n0 = jnp.zeros((b * h, dh), jnp.float32)
        m0 = jnp.full((b * h, 1), -1e30, jnp.float32)
    else:
        C, n, m = carry
        c0 = C.reshape(b * h, dh, dh).astype(jnp.float32)
        n0 = n.reshape(b * h, dh).astype(jnp.float32)
        m0 = m.reshape(b * h, 1).astype(jnp.float32)

    flat = lambda t: t.reshape(b * h, s, -1)
    qf, kf, vf = flat(q), flat(k), flat(v)
    igf = i_gate.reshape(b * h, s)
    fgf = f_gate.reshape(b * h, s)

    kernel = functools.partial(_kernel, block_s=block_s, ns=ns)
    seq_spec = pl.BlockSpec((1, block_s, dh), lambda bh, si: (bh, si, 0))
    gate_spec = pl.BlockSpec((1, block_s), lambda bh, si: (bh, si))
    state_specs = [
        pl.BlockSpec((1, dh, dh), lambda bh, si: (bh, 0, 0)),
        pl.BlockSpec((1, dh), lambda bh, si: (bh, 0)),
        pl.BlockSpec((1, 1), lambda bh, si: (bh, 0)),
    ]
    out, cT, nT, mT = pl.pallas_call(
        kernel,
        grid=(b * h, ns),
        in_specs=[seq_spec, seq_spec, seq_spec, gate_spec, gate_spec,
                  *state_specs],
        out_specs=[seq_spec, *state_specs],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, dh), q.dtype),
            jax.ShapeDtypeStruct((b * h, dh, dh), jnp.float32),
            jax.ShapeDtypeStruct((b * h, dh), jnp.float32),
            jax.ShapeDtypeStruct((b * h, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((dh,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, igf, fgf, c0, n0, m0)
    return (out.reshape(b, h, s, dh),
            (cT.reshape(b, h, dh, dh), nT.reshape(b, h, dh),
             mT.reshape(b, h)))
