"""Pallas TPU RG-LRU linear-recurrence scan (RecurrentGemma hot-spot).

h_t = a_t * h_{t-1} + x_t, elementwise over the recurrent width R.
Grid: (B, nR, n_chunks); chunks sequential with the [Rb] hidden state in
VMEM scratch; within a chunk a fori_loop applies the diagonal recurrence.
(The training path uses ``lax.associative_scan``; this kernel is the
streaming form used for long sequences / decode-prefill.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, x_ref, h0_ref, y_ref, h_ref, *, ct: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    def step(t, h):
        a = a_ref[0, t].astype(jnp.float32)        # [Rb]
        x = x_ref[0, t].astype(jnp.float32)
        h = a * h + x
        y_ref[0, t] = h.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, ct, step, h_ref[...])


def rglru_scan(a, x, h0=None, *, chunk: int = 128, block_r: int = 512,
               interpret: bool = False):
    """a, x: [B, T, R] (decay in (0,1), gated input); h0: [B, R] or None.
    Returns h trajectory [B, T, R] (f32)."""
    B, T, R = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, R), jnp.float32)
    ct = min(chunk, T)
    br = min(block_r, R)
    assert T % ct == 0 and R % br == 0
    nc, nr = T // ct, R // br

    # float32 in HBM: the kernel reads one timestep row at a time, and
    # Mosaic cannot load a single row at a dynamic offset from packed
    # (bf16) tiles
    a, x = a.astype(jnp.float32), x.astype(jnp.float32)
    kernel = functools.partial(_rglru_kernel, ct=ct)
    y = pl.pallas_call(
        kernel,
        grid=(B, nr, nc),
        in_specs=[
            pl.BlockSpec((1, ct, br), lambda b, r, c: (b, c, r)),
            pl.BlockSpec((1, ct, br), lambda b, r, c: (b, c, r)),
            pl.BlockSpec((1, 1, br), lambda b, r, c: (b, 0, r)),
        ],
        out_specs=pl.BlockSpec((1, ct, br), lambda b, r, c: (b, c, r)),
        out_shape=jax.ShapeDtypeStruct((B, T, R), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, x, h0[:, None, :])
    return y
