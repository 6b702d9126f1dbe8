"""Operations and bytes of RWKV-6's serving stages, from shapes alone:
the least work the algorithm needs.

* Matmuls: 2 operations per multiply-add of every weight, per token
  (receptance, key, value, gate and output; the two low-rank mixes; the
  channel mix).
* WKV scan: per head and token, y = r S + (r.(u*k)) v and S = w*S + k^T v
  take 5 hd^2 operations (the O(hd) terms are left out).
* Head: prefill needs the logits of its last position only.
* Bytes: every weight read once per stage (float32 vectors at 4 bytes);
  the head reads the whole embedding table; the recurrent state (float32
  WKV matrices and two bfloat16 token shifts a layer) is written by the
  prefill and read and written by each decode step.
"""

TM_LORA = 32
DECAY_LORA = 64
#: float32 vectors of d_model per layer: mu_x, 5 mu_mix, w0, u,
#: gn scale and bias, cm_mu_k, cm_mu_r
F32_VECTORS = 12
#: bfloat16 vectors of d_model per layer: ln1 and ln2 scale and bias
BF16_VECTORS = 4


def layer_matmul_params(cfg):
    D, F = cfg["d_model"], cfg["d_ff"]
    return (6 * D * D + 2 * D * F + 2 * 5 * TM_LORA * D
            + 2 * DECAY_LORA * D)


def param_count(cfg):
    D, L, V = cfg["d_model"], cfg["num_layers"], cfg["vocab_size"]
    per_layer = layer_matmul_params(cfg) + (F32_VECTORS + BF16_VECTORS) * D
    return V * D + L * per_layer + 2 * D


def param_bytes(cfg):
    D, L, V = cfg["d_model"], cfg["num_layers"], cfg["vocab_size"]
    per_layer = (2 * layer_matmul_params(cfg) + 4 * F32_VECTORS * D
                 + 2 * BF16_VECTORS * D)
    return 2 * V * D + L * per_layer + 2 * 2 * D


def state_bytes(cfg):
    """Recurrent state of one sequence."""
    D, L, hd = cfg["d_model"], cfg["num_layers"], cfg["rwkv_head_dim"]
    return L * (4 * D * hd + 2 * 2 * D)


def stages(cfg, rows, prompt_len, steps):
    """[(operations, bytes)] of one dispatch of ``rows`` rows: the prefill
    of ``prompt_len`` tokens, then ``steps`` decode steps."""
    D, L, V = cfg["d_model"], cfg["num_layers"], cfg["vocab_size"]
    hd = cfg["rwkv_head_dim"]
    b, T = rows, prompt_len
    w_bytes = param_bytes(cfg) - 2 * V * D
    per_token = 2 * L * layer_matmul_params(cfg) + 5 * L * D * hd
    head_ops, head_bytes = 2 * D * V, 2 * V * D
    out = [(b * T * per_token + b * head_ops,
            w_bytes + head_bytes + 2 * b * T * D + 4 * b * T
            + b * state_bytes(cfg))]
    for _ in range(steps):
        out.append((b * per_token + b * head_ops,
                    w_bytes + head_bytes + 2 * b * D
                    + 2 * b * state_bytes(cfg)))
    return out
