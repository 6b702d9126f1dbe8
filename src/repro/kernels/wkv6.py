"""Pallas TPU WKV6 chunked scan (RWKV-6 recurrence hot-spot).

The recurrence S <- diag(w_t) S + k_t v_t^T; y_t = r_t (S + u k_t v_t^T)
is sequential in t, so the grid is (B, H, n_chunks) with the chunk dimension
"arbitrary" (sequential) and the [hd, hd] matrix state in VMEM scratch across
chunk steps.  Inside a chunk, a fori_loop walks the timesteps — HBM traffic
is chunked (r/k/v/w tiles), the state never leaves VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_ref, *,
                ct: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0, 0].astype(jnp.float32)                # [hd]

    def step(t, S):
        rt = r_ref[0, 0, t].astype(jnp.float32)        # [hd]
        kt = k_ref[0, 0, t].astype(jnp.float32)
        vt = v_ref[0, 0, t].astype(jnp.float32)
        wt = w_ref[0, 0, t].astype(jnp.float32)
        kv = kt[:, None] * vt[None, :]                 # [hd_k, hd_v]
        y = jnp.sum(rt[:, None] * (S + u[:, None] * kv), axis=0)
        y_ref[0, 0, t] = y.astype(y_ref.dtype)
        return wt[:, None] * S + kv

    s_ref[...] = jax.lax.fori_loop(0, ct, step, s_ref[...])


def wkv6(r, k, v, w, u, *, chunk: int = 64, interpret: bool = False):
    """r,k,v,w: [B, T, H, hd]; u: [H, hd].  Returns y [B, T, H, hd] (f32).

    w is the per-step decay in (0, 1); initial state is zero (fresh
    sequence), matching ``repro.models.rwkv6.wkv_scan``.
    """
    B, T, H, hd = r.shape
    ct = min(chunk, T)
    assert T % ct == 0
    nc = T // ct
    # layout [B, H, T, hd] so the chunk dim tiles cleanly; u as [H, 1, hd]
    # so its block's last two dims equal the array's.  Float32 in HBM: the
    # kernel reads one timestep row at a time, and Mosaic cannot load a
    # single row at a dynamic offset from packed (bf16) tiles
    perm = (0, 2, 1, 3)
    rt, kt, vt, wt = (x.astype(jnp.float32).transpose(perm)
                      for x in (r, k, v, w))

    kernel = functools.partial(_wkv_kernel, ct=ct, nc=nc)
    y = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, h, c: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, ct, hd), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rt, kt, vt, wt, u[:, None, :])
    return y.transpose(perm)
