"""Pallas TPU flash attention (prefill hot-spot).

Grid: (batch*q_heads, nq, nk) — the kv dimension is sequential ("arbitrary")
so the online-softmax running stats live in VMEM scratch across kv steps.
Block shapes are MXU-aligned (q/k tiles multiples of 128 where the problem
allows).  GQA is handled in the kv index_map (q head -> kv head // group).

Validated in interpret mode against ``repro.kernels.ref.attention_ref``
(tests/test_kernels.py sweeps shapes/dtypes).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  bq: int, bk: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # [bq, hd]
    k = k_ref[0].astype(jnp.float32)          # [bk, hd]
    v = v_ref[0].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [bq, bk]
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
    p = jnp.exp(logits - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: [B, H, S, hd]; k, v: [B, K, S, hd] with H = K*G.  -> [B, H, S, hd]
    """
    B, H, S, hd = q.shape
    K = k.shape[1]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk

    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * K, S, hd)
    vf = v.reshape(B * K, S, hd)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd)
