"""Mixture-of-Experts FFN with TPU expert parallelism.

Two execution paths:

* **reference** (``ax is None``): loop over experts with masked combine —
  exact, used by smoke tests and as the oracle for the EP path.
* **expert-parallel** (mesh present): ``shard_map`` over the mesh.  Tokens are
  dispatched into per-expert capacity buckets via a sort-based ranking (no
  O(T*E*C) one-hot einsum — that would dwarf the expert FLOPs), exchanged with
  ``all_to_all`` over the ``model`` axis (experts are sharded E/mp per chip),
  computed with dense per-expert matmuls, and combined on the way back.

This is the TPU-native adaptation of the paper's "operator placement"
optimization applied to the MoE hot-spot (DESIGN.md §2/§6).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.partition import AxisInfo


def moe_init(key, cfg: ModelConfig, dtype, n_layers: int):
    """Stacked MoE params for ``n_layers`` MoE layers."""
    D, F, E = cfg.d_model, cfg.expert_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": layers.dense_init(ks[0], (n_layers, D, E), dtype=jnp.float32,
                                    fan_in=D),
        "w_up": layers.dense_init(ks[1], (n_layers, E, D, F), dtype, fan_in=D),
        "w_down": layers.dense_init(ks[2], (n_layers, E, F, D), dtype,
                                    fan_in=F),
    }
    if cfg.gated_mlp:
        p["w_gate"] = layers.dense_init(ks[3], (n_layers, E, D, F), dtype,
                                        fan_in=D)
    return p


def quantize_expert_weights(moe_params):
    """int8-quantize stacked expert weights (serving; §Perf A decode lever).

    Each [n, E, D, F]-like tensor becomes {"q": int8, "s": f32 [n, E, F]}
    (per-(expert, out-feature) scale over the reduction dim).  The FSDP
    all-gather then moves half the bytes; dequant happens post-gather inside
    the shard_map, right before the expert matmul.
    """
    out = dict(moe_params)
    for name in ("w_gate", "w_up", "w_down"):
        if name not in moe_params:
            continue
        w = moe_params[name].astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2), 1e-8) / 127.0
        q = jnp.clip(jnp.round(w / scale[..., None, :]), -127, 127
                     ).astype(jnp.int8)
        out[name] = {"q": q, "s": scale}
    return out


def _maybe_dequant(w, dtype=jnp.bfloat16):
    if isinstance(w, dict) and "q" in w:
        return (w["q"].astype(jnp.float32) * w["s"][..., None, :]
                ).astype(dtype)
    return w


def _expert_ffn(x, w_gate, w_up, w_down, act: str, gated: bool):
    """x: [..., E, C, D]; weights: [E, D, F] / [E, F, D] (or int8 dicts)."""
    w_gate = _maybe_dequant(w_gate)
    w_up = _maybe_dequant(w_up)
    w_down = _maybe_dequant(w_down)
    up = jnp.einsum("...ecd,edf->...ecf", x, w_up)
    if gated:
        g = jnp.einsum("...ecd,edf->...ecf", x, w_gate)
        h = layers._act(g, act) * up
    else:
        h = layers._act(up, act)
    return jnp.einsum("...ecf,efd->...ecd", h, w_down)


def _router(xf, router_w, k: int):
    """xf: [T, D] -> (weights [T,k], idx [T,k], aux_loss scalar)."""
    logits = (xf.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # [T, E]
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss
    E = logits.shape[-1]
    me = jnp.mean(probs, axis=0)                                 # router frac
    ce = jnp.mean(
        jax.nn.one_hot(top_i[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return top_w, top_i, aux


# ---------------------------------------------------------------------------
# Reference path (single device)
# ---------------------------------------------------------------------------
def moe_apply_reference(x, params, cfg: ModelConfig):
    """x: [B, S, D].  Exact masked-combine over all experts."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    xf = x.reshape(-1, D)
    top_w, top_i, aux = _router(xf, params["router"], k)
    out = jnp.zeros_like(xf, dtype=jnp.float32)
    sl = lambda w, e: jax.tree.map(lambda t: t[e:e + 1], w)
    for e in range(E):
        w_g = params.get("w_gate")
        h = _expert_ffn(xf[None],
                        sl(params["w_gate"], e) if w_g is not None else None,
                        sl(params["w_up"], e), sl(params["w_down"], e),
                        cfg.act, cfg.gated_mlp)[0]
        gate = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        out = out + gate[:, None] * h.astype(jnp.float32)
    return out.reshape(B, S, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path
# ---------------------------------------------------------------------------
def _capacity(tokens: int, k: int, E: int, factor: float) -> int:
    return max(1, int(math.ceil(tokens * k * factor / E)))


def _dispatch_combine_local(xf, router_w, w_gate, w_up, w_down, *,
                            cfg: ModelConfig, mp: int, mp_axis: str,
                            dispatch: str = "all_to_all"):
    """Runs on one chip inside shard_map.  xf: [T, D] local tokens;
    expert weights are the local shard [E_loc, D, F]."""
    T, D = xf.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    E_loc = E // mp
    C = _capacity(T, k, E, cfg.capacity_factor)

    top_w, top_i, aux = _router(xf, router_w, k)
    flat_e = top_i.reshape(-1)                                   # [T*k]
    flat_w = top_w.reshape(-1)
    token_idx = jnp.arange(T * k) // k

    # rank of each (token, expert) slot within its expert, via stable sort
    order = jnp.argsort(flat_e)
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    ranks_sorted = jnp.arange(T * k) - starts[flat_e[order]]
    ranks = jnp.zeros((T * k,), jnp.int32).at[order].set(
        ranks_sorted.astype(jnp.int32))
    keep = ranks < C
    safe_rank = jnp.where(keep, ranks, C - 1)

    # dispatch buffer [E, C, D]
    contrib = jnp.where(keep[:, None], xf[token_idx], 0.0)
    buf = jnp.zeros((E, C, D), xf.dtype).at[flat_e, safe_rank].add(contrib)

    if dispatch == "all_to_all" and mp > 1:
        send = buf.reshape(mp, E_loc, C, D)
        recv = jax.lax.all_to_all(send, mp_axis, split_axis=0,
                                  concat_axis=0, tiled=True)     # [mp,Eloc,C,D]
        h = _expert_ffn(recv, w_gate, w_up, w_down, cfg.act, cfg.gated_mlp)
        back = jax.lax.all_to_all(h, mp_axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        out_buf = back.reshape(E, C, D)
    elif mp > 1:
        # baseline "allgather" dispatch: gather full expert weights per chip
        wg = (jax.lax.all_gather(w_gate, mp_axis, axis=0, tiled=True)
              if w_gate is not None else None)
        wu = jax.lax.all_gather(w_up, mp_axis, axis=0, tiled=True)
        wd = jax.lax.all_gather(w_down, mp_axis, axis=0, tiled=True)
        out_buf = _expert_ffn(buf, wg, wu, wd, cfg.act, cfg.gated_mlp)
    else:
        out_buf = _expert_ffn(buf, w_gate, w_up, w_down, cfg.act,
                              cfg.gated_mlp)

    gathered = out_buf[flat_e, safe_rank] * keep[:, None]
    y = (flat_w[:, None] * gathered.astype(jnp.float32)).reshape(T, k, D)
    return y.sum(axis=1).astype(xf.dtype), aux


def moe_apply_ep(x, params, cfg: ModelConfig, ax: AxisInfo, *,
                 seq_sharded: bool, dispatch: str = "all_to_all"):
    """Expert-parallel MoE.  x: [B, S, D].

    ``seq_sharded``: the residual stream is sharded [B->data, S->model, D]
    (train/prefill).  Otherwise (decode) tokens are [B->data, 1, D] and each
    model-row chip takes a sub-slice of the local batch.
    """
    mp, mp_ax = ax.mp_size, ax.model
    dp = ax.batch
    E = cfg.num_experts
    assert E % mp == 0, (E, mp)

    def fn(x_loc, router_w, w_g, w_u, w_d):
        B_loc, S_loc, D = x_loc.shape
        if seq_sharded:
            xf = x_loc.reshape(-1, D)
            y, aux = _dispatch_combine_local(
                xf, router_w, w_g, w_u, w_d, cfg=cfg, mp=mp, mp_axis=mp_ax,
                dispatch=dispatch)
            out = y.reshape(B_loc, S_loc, D)
        else:
            # split local tokens across the model axis, then all_gather
            T = B_loc * S_loc
            pad = (-T) % mp
            xf = jnp.pad(x_loc.reshape(T, D), ((0, pad), (0, 0)))
            per = (T + pad) // mp
            i = jax.lax.axis_index(mp_ax)
            xs = jax.lax.dynamic_slice_in_dim(xf, i * per, per, axis=0)
            y, aux = _dispatch_combine_local(
                xs, router_w, w_g, w_u, w_d, cfg=cfg, mp=mp, mp_axis=mp_ax,
                dispatch=dispatch)
            yf = jax.lax.all_gather(y, mp_ax, axis=0, tiled=True)
            out = yf[:T].reshape(B_loc, S_loc, D)
        aux = jax.lax.pmean(aux, mp_ax)
        for a in dp:
            aux = jax.lax.pmean(aux, a)
        return out, aux

    seq_spec = mp_ax if seq_sharded else None

    def w_spec(w):
        if isinstance(w, dict):   # int8-quantized {"q": [E,D,F], "s": [E,F]}
            return {"q": P(mp_ax, None, None), "s": P(mp_ax, None)}
        return P(mp_ax, None, None)

    in_specs = (P(dp, seq_spec, None), P(None, None),
                w_spec(params.get("w_gate", params["w_up"])),
                w_spec(params["w_up"]), w_spec(params["w_down"]))
    out_specs = (P(dp, seq_spec, None), P())
    fn_s = shard_map(fn, mesh=ax.mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)
    w_gate = params.get("w_gate")
    if w_gate is None:
        w_gate = params["w_up"]  # placeholder, unused when not gated
    return fn_s(x, params["router"], w_gate, params["w_up"],
                params["w_down"])


def moe_apply(x, params, cfg: ModelConfig, ax: Optional[AxisInfo], *,
              seq_sharded: bool = True,
              dispatch: str = "all_to_all") -> Tuple[jax.Array, jax.Array]:
    """Dispatch to reference or expert-parallel path.  Returns (y, aux)."""
    if ax is None or ax.mp_size == 1:
        return moe_apply_reference(x, params, cfg)
    return moe_apply_ep(x, params, cfg, ax, seq_sharded=seq_sharded,
                        dispatch=dispatch)
