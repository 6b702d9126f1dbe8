"""RWKV-6 "Finch" — attention-free time-mix with data-dependent decay.
[arXiv:2404.05892]

State per layer: wkv matrix state S [B, H, hd, hd] + token-shift states.
The WKV recurrence takes one of two forms, chosen from the number of
timesteps: a prompt (prefill, logits, training) runs ``wkv_chunked``, which
reads and writes the state once per chunk of ``CHUNK`` timesteps and forms
the pairs inside a chunk all at once; a decode step (one timestep) runs
``wkv_scan``.  Heads are sharded over the ``model`` axis; the recurrence is
elementwise in the sharded dims, so neither form has collectives.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.partition import AxisInfo, shard, dp_axes, mp_axis
from repro.obs import keys as okeys
from repro.obs.metrics import EVENTS

TM_LORA = 32
DECAY_LORA = 64
MIX = ("w", "k", "v", "r", "g")
CHUNK = 16      # timesteps between two reads of the WKV state on a prompt


def init_params(key, cfg: ModelConfig, ax: Optional[AxisInfo], **_unused):
    D, F, L = cfg.d_model, cfg.d_ff, cfg.num_layers
    H, hd = cfg.num_rwkv_heads, cfg.rwkv_head_dim
    dtype = jnp.dtype(cfg.dtype)
    k = jax.random.split(key, 16)
    uniform = lambda kk, shape, s=0.1: (jax.random.uniform(
        kk, shape, jnp.float32, -s, s)).astype(jnp.float32)

    def stack(fn, kk):
        return fn(kk)  # already created with leading L dim

    blocks = {
        "ln1": _norms(k[0], L, D, dtype),
        "ln2": _norms(k[1], L, D, dtype),
        "mu_x": uniform(k[2], (L, D)),
        "mu_mix": uniform(k[3], (L, 5, D)),
        "tm_w1": layers.dense_init(k[4], (L, D, 5 * TM_LORA), dtype, fan_in=D),
        "tm_w2": layers.dense_init(k[5], (L, 5, TM_LORA, D), dtype,
                                   fan_in=TM_LORA),
        "w0": jnp.zeros((L, D), jnp.float32) - 0.5,
        "dw1": layers.dense_init(k[6], (L, D, DECAY_LORA), dtype, fan_in=D),
        "dw2": layers.dense_init(k[7], (L, DECAY_LORA, D), dtype,
                                 fan_in=DECAY_LORA),
        "u": uniform(k[8], (L, H, hd), 0.5),
        "wr": layers.dense_init(k[9], (L, D, D), dtype, fan_in=D),
        "wk": layers.dense_init(k[10], (L, D, D), dtype, fan_in=D),
        "wv": layers.dense_init(k[11], (L, D, D), dtype, fan_in=D),
        "wg": layers.dense_init(k[12], (L, D, D), dtype, fan_in=D),
        "wo": layers.dense_init(k[13], (L, D, D), dtype, fan_in=D),
        "gn_scale": jnp.ones((L, D), jnp.float32),
        "gn_bias": jnp.zeros((L, D), jnp.float32),
        # channel mix
        "cm_mu_k": uniform(k[14], (L, D)),
        "cm_mu_r": uniform(k[14], (L, D)),
        "cm_wk": layers.dense_init(k[15], (L, D, F), dtype, fan_in=D),
        "cm_wv": layers.dense_init(k[15], (L, F, D), dtype, fan_in=F),
        "cm_wr": layers.dense_init(k[15], (L, D, D), dtype, fan_in=D),
    }
    ke = jax.random.split(key, 2)
    return {
        "embed": layers.embed_init(ke[0], cfg.padded_vocab, D, dtype),
        "final_norm": layers.init_norm(ke[1], D, cfg.norm, dtype),
        "blocks": blocks,
    }


def _norms(key, L, D, dtype):
    return {"scale": jnp.ones((L, D), dtype), "bias": jnp.zeros((L, D), dtype)}


def _ddlerp(x, xprev, lp):
    """Data-dependent lerp producing the 5 mixed inputs (w,k,v,r,g)."""
    dx = xprev - x
    xxx = x + dx * lp["mu_x"]
    B, T, D = x.shape
    low = jnp.tanh(xxx @ lp["tm_w1"]).reshape(B, T, 5, TM_LORA)
    mixes = jnp.einsum("btjl,jld->btjd", low, lp["tm_w2"])
    outs = []
    for j in range(5):
        outs.append(x + dx * (lp["mu_mix"][j] + mixes[:, :, j]))
    return outs  # [x_w, x_k, x_v, x_r, x_g]


def wkv_scan(r, k, v, w, u, state):
    """Reference WKV6 recurrence.

    r,k,v: [B, T, H, hd]; w: [B, T, H, hd] decay in (0,1); u: [H, hd];
    state: [B, H, hd, hd].  Returns (y [B,T,H,hd], new_state).
    """
    def step(S, inp):
        rt, kt, vt, wt = inp                     # [B, H, hd]
        kv = jnp.einsum("bhi,bhj->bhij", kt, vt)   # outer over (key, value)
        y = jnp.einsum("bhi,bhij->bhj", rt, S + u[None, :, :, None] * kv)
        S = wt[..., None] * S + kv
        return S, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    state, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1), state


def wkv_chunked(r, k, v, logw, u, state, *, chunk: int = CHUNK):
    """The recurrence of ``wkv_scan`` over chunks of ``chunk`` timesteps.

    r,k,v: [B, T, H, hd]; logw: [B, T, H, hd] log of the decay (<= 0, so
    a decay that underflows to 0 stays exact); u: [H, hd]; state:
    [B, H, hd, hd].  Returns (y [B,T,H,hd], new_state).

    Inside a chunk that enters with state S_0, with c_t the sum of logw up
    to and including t and b_t the sum before t:
    y_t = (r_t * e^b_t) S_0 + sum_{s<t} A_ts v_s + (r_t . u*k_t) v_t, with
    A_ts = sum_i r_ti k_si e^(b_ti - c_si), and the chunk leaves the state
    e^c_last * S_0 + sum_s (k_s * e^(c_last - c_s)) v_s.  Every exponent
    is a sum of logw over a run of timesteps, so none is positive; pairs
    s >= t are masked before the exp.  A tail that fills no whole chunk is
    padded with k = v = r = 0 and logw = 0, which leaves y and the state
    as they were.  Only the state crosses chunks: a ``lax.scan`` of T/chunk
    steps carries it.
    """
    B, T, H, hd = r.shape
    C = min(chunk, T)
    N = -(-T // C)
    hi = jax.lax.Precision.HIGHEST

    def chunks(x):                          # -> [N, B, H, C, hd]
        x = jnp.pad(x, ((0, 0), (0, N * C - T), (0, 0), (0, 0)))
        return x.reshape(B, N, C, H, hd).transpose(1, 0, 3, 2, 4)

    def before(x):                          # x_{t-1} along the chunk, 0 first
        return jnp.pad(x[..., :-1, :], ((0, 0),) * 3 + ((1, 0), (0, 0)))

    r, k, v, logw = map(chunks, (r, k, v, logw))
    c = jnp.cumsum(logw, axis=3)            # through t
    b = before(c)                           # before t
    after = jnp.flip(before(jnp.cumsum(jnp.flip(logw, 3), 3)), 3)  # after t
    tri = jnp.tril(jnp.ones((C, C), bool), -1)[:, :, None]         # s < t
    a = jnp.sum(r[..., :, None, :] * k[..., None, :, :] * jnp.exp(
        jnp.where(tri, b[..., :, None, :] - c[..., None, :, :], -jnp.inf)),
        axis=-1)
    bonus = jnp.sum(r * u[:, None, :] * k, axis=-1, keepdims=True)
    y_in = jnp.einsum("nbhts,nbhsj->nbhtj", a, v, precision=hi) + bonus * v

    def step(S, inp):
        r_in, k_out, v_n, decay = inp
        y = jnp.einsum("bhti,bhij->bhtj", r_in, S, precision=hi)
        S = decay[..., None] * S + jnp.einsum(
            "bhsi,bhsj->bhij", k_out, v_n, precision=hi)
        return S, y

    S, y_state = jax.lax.scan(step, state, (
        r * jnp.exp(b), k * jnp.exp(after), v, jnp.exp(c[..., -1, :])))
    y = (y_state + y_in).transpose(1, 0, 3, 2, 4).reshape(B, N * C, H, hd)
    return y[:, :T], S


def _time_mix(x, xprev, S, lp, cfg: ModelConfig, ax):
    B, T, D = x.shape
    H, hd = cfg.num_rwkv_heads, cfg.rwkv_head_dim
    xw, xk, xv, xr, xg = _ddlerp(x, xprev, lp)
    f32 = jnp.float32
    r = (xr @ lp["wr"]).astype(f32)
    k = (xk @ lp["wk"]).astype(f32)
    v = (xv @ lp["wv"]).astype(f32)
    g = jax.nn.silu((xg @ lp["wg"]).astype(f32))
    dm = dp_axes(ax)
    r = shard(ax, r, dm, None, mp_axis(ax))
    k = shard(ax, k, dm, None, mp_axis(ax))
    v = shard(ax, v, dm, None, mp_axis(ax))
    decay_low = jnp.tanh(xw @ lp["dw1"]) @ lp["dw2"]
    logw = -jnp.exp((lp["w0"] + decay_low).astype(f32))  # [B,T,D], log w
    logw = shard(ax, logw, dm, None, mp_axis(ax))
    r, k, v, logw = (t.reshape(B, T, H, hd) for t in (r, k, v, logw))
    u = lp["u"].astype(f32)
    if T > 1:
        EVENTS.inc(okeys.WKV_CHUNKED)
        y, S = wkv_chunked(r, k, v, logw, u, S)
    else:
        EVENTS.inc(okeys.WKV_STEP)
        y, S = wkv_scan(r, k, v, jnp.exp(logw), u, S)
    y = layers.groupnorm_heads(y.reshape(B, T, D), lp["gn_scale"],
                               lp["gn_bias"], H)
    out = ((y * g).astype(x.dtype)) @ lp["wo"]
    return out, S


def _channel_mix(x, xprev, lp):
    dx = xprev - x
    xk = x + dx * lp["cm_mu_k"]
    xr = x + dx * lp["cm_mu_r"]
    kk = jnp.square(jax.nn.relu(xk @ lp["cm_wk"]))
    return jax.nn.sigmoid(xr @ lp["cm_wr"]) * (kk @ lp["cm_wv"])


def _shift(x, prev):
    """prev: [B, D] last token of previous chunk (zeros at t=0)."""
    return jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)


def forward(params, tokens, cfg: ModelConfig, ax: Optional[AxisInfo], *,
            build_cache: bool = False, cache_len=None, remat: bool = True,
            **_unused):
    B, T = tokens.shape
    H, hd = cfg.num_rwkv_heads, cfg.rwkv_head_dim
    x = layers.embed_lookup(params["embed"], tokens)
    x = shard(ax, x, dp_axes(ax), mp_axis(ax), None)

    def block_fn(x, lp):
        x = shard(ax, x, dp_axes(ax), mp_axis(ax), None)
        zeros_shift = jnp.zeros((B, x.shape[-1]), x.dtype)
        S0 = jnp.zeros((B, H, hd, hd), jnp.float32)
        h1 = layers.layernorm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
        tm_out, S = _time_mix(h1, _shift(h1, zeros_shift), S0, lp, cfg, ax)
        x = x + tm_out
        h2 = layers.layernorm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
        x = (x + _channel_mix(h2, _shift(h2, zeros_shift), lp)).astype(
            jnp.dtype(cfg.dtype))
        cache_out = {}
        if build_cache:
            cache_out = {"S": S, "tm_shift": h1[:, -1], "cm_shift": h2[:, -1]}
        return x, cache_out

    body = jax.checkpoint(block_fn) if remat else block_fn
    x, caches = jax.lax.scan(lambda c, lp: body(c, lp), x, params["blocks"])
    x = layers.apply_norm(x, params["final_norm"], cfg.norm)
    logits = layers.unembed(x, params["embed"])
    logits = shard(ax, logits, dp_axes(ax), mp_axis(ax), None)
    aux = jnp.zeros((), jnp.float32)
    if build_cache:
        return logits, caches, aux
    return logits, aux


def init_cache(cfg: ModelConfig, ax, batch: int, cache_len: int, **_unused):
    L = cfg.num_layers
    D, H, hd = cfg.d_model, cfg.num_rwkv_heads, cfg.rwkv_head_dim
    return {
        "S": jnp.zeros((L, batch, H, hd, hd), jnp.float32),
        "tm_shift": jnp.zeros((L, batch, D), jnp.dtype(cfg.dtype)),
        "cm_shift": jnp.zeros((L, batch, D), jnp.dtype(cfg.dtype)),
    }


def cache_pspecs(cfg: ModelConfig, ax: AxisInfo, **_unused):
    from jax.sharding import PartitionSpec as P
    dp, mp = ax.batch, ax.model
    return {"S": P(None, dp, mp, None, None),
            "tm_shift": P(None, dp, None),
            "cm_shift": P(None, dp, None)}


def decode_step(params, tokens, pos, cache, cfg: ModelConfig,
                ax: Optional[AxisInfo], **_unused):
    """tokens: [B,1].  Cache: {S, tm_shift, cm_shift} stacked over layers."""
    B = tokens.shape[0]
    x = layers.embed_lookup(params["embed"], tokens)
    x = shard(ax, x, dp_axes(ax), None, None)

    def block_fn(carry, lp):
        x, cache, bi = carry
        c = jax.tree.map(
            lambda t: jax.lax.dynamic_index_in_dim(t, bi, axis=0,
                                                   keepdims=False), cache)
        h = layers.layernorm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
        tm_out, S = _time_mix(h, c["tm_shift"][:, None], c["S"], lp, cfg, ax)
        x = x + tm_out
        h2 = layers.layernorm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
        x = (x + _channel_mix(h2, c["cm_shift"][:, None], lp)).astype(
            jnp.dtype(cfg.dtype))
        new_c = {"S": S, "tm_shift": h[:, -1].astype(c["tm_shift"].dtype),
                 "cm_shift": h2[:, -1].astype(c["cm_shift"].dtype)}
        cache = jax.tree.map(
            lambda t, nc: jax.lax.dynamic_update_index_in_dim(
                t, nc.astype(t.dtype), bi, axis=0), cache, new_c)
        return (x, cache, bi + 1), None

    (x, new_cache, _), _ = jax.lax.scan(
        block_fn, (x, cache, jnp.zeros((), jnp.int32)), params["blocks"])
    x = layers.apply_norm(x, params["final_norm"], cfg.norm)
    logits = layers.unembed(x, params["embed"])
    return logits, new_cache
