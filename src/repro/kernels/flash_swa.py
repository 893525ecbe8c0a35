"""Flash attention with optional sliding window — Pallas TPU kernel.

The long-context path for mixtral (SWA-4096) and gemma3 (1024-window local
layers). Grid (batch·heads, Q blocks, KV blocks); online softmax carried in
VMEM scratch (m, l, acc); KV blocks entirely outside the (causal ∩ window)
band are skipped via ``pl.when`` so a 4k window over a 512k context touches
only O(window) KV per query block, not O(S).

The jnp twin is models/attention.blockwise_attention (used by the lowering
paths); kernels/ref.flash_swa_ref is the materialised oracle for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int, nkv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk

    # is any (q, k) pair in this block-pair visible?
    #   causal: k_start <= q_end;  window: k_end > q_start - window
    q_end = q_start + bq - 1
    k_end = k_start + bk - 1
    relevant = True
    if causal:
        relevant = k_start <= q_end
    if window:
        relevant = relevant & (k_end > q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == nkv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_swa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, window: int = 0, bq: int = 256,
              bk: int = 256, interpret: bool = False) -> jnp.ndarray:
    """q, k, v: (BH, S, D) → (BH, S, D) attention output (q dtype)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = min(bq, sq), min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    nq, nkv = sq // bq, sk // bk
    scale = d ** -0.5

    grid = (bh, nq, nkv)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          bq=bq, bk=bk, nkv=nkv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
        name="flash_swa",
    )(q, k, v)
