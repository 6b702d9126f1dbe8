"""Plain reference of a llama-style dense decoder (Yi): its seeded weights
and its full-sequence forward pass in straightforward ``jax.numpy``.

Per layer: x += Wo . attn(rope(Wq h), rope(Wk h), Wv h) with h =
rmsnorm(x), grouped-query causal softmax attention; then x +=
Wd (silu(Wg h2) * Wu h2) with h2 = rmsnorm(x).  The head is tied to the
embedding.  RoPE rotates the two halves of each head (the "rotate half"
form).  The weights are laid out as the served program takes them: layers
stacked on a leading axis under ``blocks/0``, and each norm's gain stored
as its offset from 1.
"""
import math

import jax
import jax.numpy as jnp

from . import common as C

F32 = jnp.float32


def padded_vocab(cfg):
    return -(-cfg["vocab_size"] // 256) * 256


def make_weights(cfg, key):
    """Seeded weights in the served type; call under ``jax.jit``."""
    D, L, F = cfg["d_model"], cfg["num_layers"], cfg["d_ff"]
    Hd = cfg["num_heads"] * cfg["head_dim"]
    Kd = cfg["num_kv_heads"] * cfg["head_dim"]
    dt = jnp.dtype(cfg["dtype"])
    k = iter(jax.random.split(key, 16))

    def gain(shape):
        return C.normal(next(k), shape, dt, 0.1)

    return {
        "embed": C.normal(next(k), (padded_vocab(cfg), D), dt, D ** -0.5),
        "final_norm": {"scale": gain((D,))},
        "blocks": {"0": {
            "ln1": {"scale": gain((L, D))},
            "attn": {"wq": C.normal(next(k), (L, D, Hd), dt, D ** -0.5),
                     "wk": C.normal(next(k), (L, D, Kd), dt, D ** -0.5),
                     "wv": C.normal(next(k), (L, D, Kd), dt, D ** -0.5),
                     "wo": C.normal(next(k), (L, Hd, D), dt, Hd ** -0.5)},
            "ln2": {"scale": gain((L, D))},
            "mlp": {"w_gate": C.normal(next(k), (L, D, F), dt, D ** -0.5),
                    "w_up": C.normal(next(k), (L, D, F), dt, D ** -0.5),
                    "w_down": C.normal(next(k), (L, F, D), dt, F ** -0.5)},
        }},
    }


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def logits(w, tokens, first, *, cfg, mm):
    """float32 logits [B, T - first, V] at positions first..T-1 of
    ``tokens`` [B, T].  ``mm`` is a matmul of ``refs.common.MATMULS``.
    Layer weights are promoted to float32 one layer at a time."""
    B, T = tokens.shape
    H, K, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps = cfg["norm_eps"]
    pos = jnp.arange(T, dtype=F32)
    inv = 1.0 / cfg["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def rope(t):
        a, b = t[..., :hd // 2], t[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(F32), lw)
        h = _rmsnorm(x, lw["ln1"]["scale"], eps)
        q = rope(mm("btd,de->bte", h, lw["attn"]["wq"]).reshape(B, T, H, hd))
        k = rope(mm("btd,de->bte", h, lw["attn"]["wk"]).reshape(B, T, K, hd))
        v = mm("btd,de->bte", h, lw["attn"]["wv"]).reshape(B, T, K, hd)
        q = q.reshape(B, T, K, H // K, hd)
        s = mm("btkgd,bskd->bkgts", q, k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm("bkgts,bskd->btkgd", p, v).reshape(B, T, H * hd)
        x = x + mm("bte,ed->btd", o, lw["attn"]["wo"])
        h = _rmsnorm(x, lw["ln2"]["scale"], eps)
        u = jax.nn.silu(mm("btd,df->btf", h, lw["mlp"]["w_gate"])) \
            * mm("btd,df->btf", h, lw["mlp"]["w_up"])
        return x + mm("btf,fd->btd", u, lw["mlp"]["w_down"]), None

    x = w["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(layer, x, w["blocks"]["0"])
    x = _rmsnorm(x[:, first:], w["final_norm"]["scale"].astype(F32), eps)
    out = mm("btd,vd->btv", x, w["embed"])
    return out[..., :cfg["vocab_size"]]
