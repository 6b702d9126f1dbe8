"""Pallas TPU decode attention: ONE query token against a long KV cache.

This is the serving hot-spot at decode_32k / long_500k: memory-bound
streaming of the cache through VMEM.  Grid: (B, K, nS) with the kv/sequence
dimension sequential; online-softmax stats for the G query heads of each kv
head live in scratch.  Supports the ring-buffer cache layout (per-slot
positions, -1 = empty) used by the model zoo.
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


def _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale: float, window: int,
                   softcap: float, bs: int, ns: int):
    b = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # [G, hd]
    k = k_ref[0, 0].astype(jnp.float32)              # [bs, hd]
    v = v_ref[0, 0].astype(jnp.float32)
    kpos = kpos_ref[0]                               # [1, bs]
    qpos = qpos_ref[b]                               # scalar int32 (SMEM)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [G, bs]
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    valid = (kpos >= 0) & (kpos <= qpos)
    if window > 0:
        valid &= (qpos - kpos) < window
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
    p = jnp.exp(logits - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, k_positions, q_position, *,
                     window: int = 0, softcap: float = 0.0,
                     scale: Optional[float] = None, block_s: int = 512,
                     interpret: bool = False):
    """q: [B, H, hd]; k_cache/v_cache: [B, K, S, hd];
    k_positions: [B, S] int32 (−1 empty); q_position: [B] int32.
    Returns [B, H, hd]."""
    B, H, hd = q.shape
    K, S = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    bs = min(block_s, S)
    assert S % bs == 0
    ns = S // bs
    qg = q.reshape(B, K, G, hd)
    # positions shared by the K kv heads: [B, 1, S], so the block's last
    # two dims (1, bs) are (whole dim, lane-aligned) as Mosaic requires;
    # the query position is one scalar per row, prefetched into SMEM
    kpos = k_positions.astype(jnp.int32)[:, None, :]
    qpos = q_position.astype(jnp.int32)

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               softcap=softcap, bs=bs, ns=ns)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, ns),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, kh, si, _: (b, kh, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, kh, si, _: (b, kh, si, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, kh, si, _: (b, kh, si, 0)),
            pl.BlockSpec((1, 1, bs), lambda b, kh, si, _: (b, 0, si)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, kh, si, _: (b, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qpos, qg, k_cache, v_cache, kpos)
    return out.reshape(B, H, hd)
