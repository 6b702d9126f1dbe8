"""Plain reference of RWKV-6 "Finch": its seeded weights and its
full-sequence forward pass, one timestep at a time.

Per layer, with h = layernorm(x) and h' the previous token's h (zeros
before the first):
  time mix   d = h' - h;  l_j = tanh((h + d mu_x) A) B_j;
             x_j = h + d (mu_j + l_j) for j in (w, k, v, r, g);
             r, k, v = x_r Wr, x_k Wk, x_v Wv;  g = silu(x_g Wg);
             w = exp(-exp(w0 + tanh(x_w D1) D2))   (decay per key channel)
             per head:  y_t = r_t (S + u k_t^T v_t);  S = w_t S + k_t^T v_t
             x += (groupnorm_heads(y) * g) Wo
  channel mix  with h2 = layernorm(x):  x += sigmoid(x_r Wr') *
             (relu(x_k Wk')^2 Wv'),  x_k, x_r token-shifted as above.
The head is tied to the embedding and there is no extra norm after the
embedding.  The weights are laid out as the served program takes them:
layers stacked on a leading axis under ``blocks``.
"""
import jax
import jax.numpy as jnp

from . import common as C

F32 = jnp.float32
TM_LORA = 32
DECAY_LORA = 64
LN_EPS = 1e-5
GN_EPS = 64e-5
#: the data-dependent low-rank mixes and decay are drawn at a tenth of
#: unit scale (RWKV's own initialisation starts them near zero); at unit
#: scale the 24 layers amplify a perturbation of the input some
#: thousandfold, and bf16 serving lands far from any float32 answer
LORA_GAIN = 0.1


def padded_vocab(cfg):
    return -(-cfg["vocab_size"] // 256) * 256


def make_weights(cfg, key):
    """Seeded weights in the served type; call under ``jax.jit``."""
    D, L, F = cfg["d_model"], cfg["num_layers"], cfg["d_ff"]
    hd = cfg["rwkv_head_dim"]
    H = D // hd
    dt = jnp.dtype(cfg["dtype"])
    k = iter(jax.random.split(key, 32))

    def mat(shape, fan_in, gain=1.0):
        return C.normal(next(k), shape, dt, gain * fan_in ** -0.5)


    def norm(shape, dtype):
        return {"scale": 1.0 + C.normal(next(k), shape, dtype, 0.1),
                "bias": C.normal(next(k), shape, dtype, 0.1)}

    ln1, ln2 = norm((L, D), dt), norm((L, D), dt)
    gn = norm((L, D), F32)
    return {
        "embed": C.normal(next(k), (padded_vocab(cfg), D), dt, D ** -0.5),
        "final_norm": norm((D,), dt),
        "blocks": {
            "ln1": ln1, "ln2": ln2,
            "mu_x": C.uniform(next(k), (L, D), F32, 0.0, 1.0),
            "mu_mix": C.uniform(next(k), (L, 5, D), F32, 0.0, 1.0),
            "tm_w1": mat((L, D, 5 * TM_LORA), D),
            "tm_w2": mat((L, 5, TM_LORA, D), TM_LORA, LORA_GAIN),
            "w0": C.uniform(next(k), (L, D), F32, -2.0, 0.0),
            "dw1": mat((L, D, DECAY_LORA), D),
            "dw2": mat((L, DECAY_LORA, D), DECAY_LORA, LORA_GAIN),
            "u": C.uniform(next(k), (L, H, hd), F32, -0.5, 0.5),
            "wr": mat((L, D, D), D), "wk": mat((L, D, D), D),
            "wv": mat((L, D, D), D), "wg": mat((L, D, D), D),
            "wo": mat((L, D, D), D),
            "gn_scale": gn["scale"], "gn_bias": gn["bias"],
            "cm_mu_k": C.uniform(next(k), (L, D), F32, 0.0, 1.0),
            "cm_mu_r": C.uniform(next(k), (L, D), F32, 0.0, 1.0),
            "cm_wk": mat((L, D, F), D), "cm_wv": mat((L, F, D), F),
            "cm_wr": mat((L, D, D), D),
        },
    }


def _layernorm(x, p, eps=LN_EPS):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _shift(h):
    return jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)


def logits(w, tokens, first, *, cfg, mm):
    """float32 logits [B, T - first, V] at positions first..T-1 of
    ``tokens`` [B, T].  ``mm`` is a matmul of ``refs.common.MATMULS``.
    Layer weights are promoted to float32 one layer at a time."""
    B, T = tokens.shape
    D, hd = cfg["d_model"], cfg["rwkv_head_dim"]
    H = D // hd

    def wkv(r, k, v, wd, u):
        def step(S, inp):                       # S [B, H, key, value]
            rt, kt, vt, wt = inp
            kv = kt[..., :, None] * vt[..., None, :]
            y = jnp.sum(rt[..., :, None] * (S + u[None, :, :, None] * kv),
                        axis=-2)
            return wt[..., :, None] * S + kv, y

        heads = [t.reshape(B, T, H, hd).transpose(1, 0, 2, 3)
                 for t in (r, k, v, wd)]
        _, ys = jax.lax.scan(step, jnp.zeros((B, H, hd, hd), F32),
                             tuple(heads))
        return ys.transpose(1, 0, 2, 3)         # [B, T, H, hd]

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(F32), lw)
        h = _layernorm(x, lw["ln1"])
        d = _shift(h) - h
        low = jnp.tanh(mm("btd,de->bte", h + d * lw["mu_x"], lw["tm_w1"]))
        low = low.reshape(B, T, 5, TM_LORA)
        mix = mm("btjl,jld->btjd", low, lw["tm_w2"])
        xw, xk, xv, xr, xg = (h + d * (lw["mu_mix"][j] + mix[:, :, j])
                              for j in range(5))
        r = mm("btd,de->bte", xr, lw["wr"])
        k = mm("btd,de->bte", xk, lw["wk"])
        v = mm("btd,de->bte", xv, lw["wv"])
        g = jax.nn.silu(mm("btd,de->bte", xg, lw["wg"]))
        decay = mm("btr,rd->btd",
                   jnp.tanh(mm("btd,dr->btr", xw, lw["dw1"])), lw["dw2"])
        wd = jnp.exp(-jnp.exp(lw["w0"] + decay))
        y = wkv(r, k, v, wd, lw["u"])
        mu = jnp.mean(y, -1, keepdims=True)
        var = jnp.mean(jnp.square(y - mu), -1, keepdims=True)
        y = ((y - mu) * jax.lax.rsqrt(var + GN_EPS)).reshape(B, T, D)
        y = y * lw["gn_scale"] + lw["gn_bias"]
        x = x + mm("btd,de->bte", y * g, lw["wo"])
        h = _layernorm(x, lw["ln2"])
        d = _shift(h) - h
        kk = jnp.square(jax.nn.relu(
            mm("btd,df->btf", h + d * lw["cm_mu_k"], lw["cm_wk"])))
        rr = jax.nn.sigmoid(
            mm("btd,de->bte", h + d * lw["cm_mu_r"], lw["cm_wr"]))
        return x + rr * mm("btf,fd->btd", kk, lw["cm_wv"]), None

    x = w["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(layer, x, w["blocks"])
    fn = jax.tree.map(lambda a: a.astype(F32), w["final_norm"])
    x = _layernorm(x[:, first:], fn)
    out = mm("btd,vd->btv", x, w["embed"])
    return out[..., :cfg["vocab_size"]]
