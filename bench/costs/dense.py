"""Operations and bytes of a llama-style dense decoder's serving stages,
from shapes alone: the least work the algorithm needs, not what a given
program happens to compute.

* Matmuls: 2 operations per multiply-add of every weight, per token.
* Attention: causal, so prefill counts the S(S+1)/2 (query, key) pairs
  that are not masked, and a decode step at position p counts its p + 1
  keys; two products each (scores and values).  Work on masked or empty
  cache slots is not counted.
* Head: prefill needs the logits of its last position only.
* Bytes: every weight read once per stage; the head reads the whole
  embedding table, the input embedding only the rows it looks up; the
  prefill writes the cache it builds, a decode step reads the keys and
  values it attends to.
"""


def _dims(cfg):
    D, F, L, V = (cfg["d_model"], cfg["d_ff"], cfg["num_layers"],
                  cfg["vocab_size"])
    Hd = cfg["num_heads"] * cfg["head_dim"]
    Kd = cfg["num_kv_heads"] * cfg["head_dim"]
    return D, F, L, V, Hd, Kd


def layer_matmul_params(cfg):
    D, F, _, _, Hd, Kd = _dims(cfg)
    return D * Hd + 2 * D * Kd + Hd * D + 3 * D * F


def param_count(cfg):
    """Every parameter the served model holds (tied head counted once)."""
    D, _, L, V, _, _ = _dims(cfg)
    return V * D + L * (layer_matmul_params(cfg) + 2 * D) + D


def param_bytes(cfg):
    return 2 * param_count(cfg)             # all bfloat16


def stages(cfg, rows, prompt_len, steps):
    """[(operations, bytes)] of one dispatch of ``rows`` rows: the prefill
    of ``prompt_len`` tokens, then ``steps`` decode steps."""
    D, _, L, V, Hd, Kd = _dims(cfg)
    b, S = rows, prompt_len
    w_bytes = 2 * L * (layer_matmul_params(cfg) + 2 * D) + 2 * D
    mm = 2 * L * layer_matmul_params(cfg)
    head_ops, head_bytes = 2 * D * V, 2 * V * D
    out = [(b * S * mm + 2 * b * L * Hd * S * (S + 1) + b * head_ops,
            w_bytes + head_bytes + 2 * b * S * D + 4 * b * S
            + 2 * b * L * 2 * S * Kd)]
    for i in range(steps):
        keys = S + i + 1
        out.append((b * mm + 4 * b * L * Hd * keys + b * head_ops,
                    w_bytes + head_bytes + 2 * b * D
                    + 2 * b * L * 2 * keys * Kd))
    return out
