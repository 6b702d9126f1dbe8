"""use_pallas=True must match the pure-jnp model paths (interpret mode)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny_config
from repro.models import build_model

B, S = 2, 32


def _compare(arch, tol=0.05):
    cfg = get_tiny_config(arch)
    cfg_p = dataclasses.replace(cfg, use_pallas=True)
    key = jax.random.PRNGKey(0)
    m, mp = build_model(cfg), build_model(cfg_p)
    params = m.init(key)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    a, _ = m.logits(params, {"tokens": toks}, remat=False)
    b, _ = mp.logits(params, {"tokens": toks}, remat=False)
    a = np.asarray(a.astype(jnp.float32))
    b = np.asarray(b.astype(jnp.float32))
    rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
    assert rel < tol, (arch, rel)


@pytest.mark.parametrize("arch", ["yi-9b", "gemma2-9b"])
def test_pallas_forward_matches_jnp(arch):
    _compare(arch)


def test_pallas_decode_matches_jnp():
    cfg = get_tiny_config("yi-9b")
    cfg_p = dataclasses.replace(cfg, use_pallas=True)
    key = jax.random.PRNGKey(0)
    m, mpal = build_model(cfg), build_model(cfg_p)
    params = m.init(key)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    _, cache = m.prefill(params, {"tokens": toks}, cache_len=S + 4)
    pos = jnp.full((B,), S, jnp.int32)
    nxt = toks[:, :1]
    a, _ = m.decode_step(params, nxt, pos, cache)
    b, _ = mpal.decode_step(params, nxt, pos, cache)
    rel = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9))
    assert rel < 0.05, rel


def test_causal_skip_matches_full_attention():
    """Triangle-pair chunked attention == full chunked attention (exact)."""
    import dataclasses as dc
    from repro.models import layers as L
    key = jax.random.PRNGKey(3)
    Bq, Sq, H, K, hd = 2, 128, 4, 2, 32
    q = jax.random.normal(key, (Bq, Sq, H, hd)) * 0.5
    k = jax.random.normal(jax.random.PRNGKey(4), (Bq, Sq, K, hd)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(5), (Bq, Sq, K, hd)) * 0.5
    pos = jnp.arange(Sq, dtype=jnp.int32)
    a = L.chunked_attention(q, k, v, q_positions=pos, k_positions=pos,
                            causal=True, chunk_q=32, chunk_k=32)
    b = L.chunked_attention_causal_skip(q, k, v, q_positions=pos,
                                        k_positions=pos, chunk=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_causal_skip_model_logits_match():
    cfg = get_tiny_config("yi-9b")
    cfg_cs = dataclasses.replace(cfg, causal_skip=True)
    key = jax.random.PRNGKey(0)
    m, mcs = build_model(cfg), build_model(cfg_cs)
    params = m.init(key)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                              cfg.vocab_size)
    a, _ = m.logits(params, {"tokens": toks}, remat=False)
    b, _ = mcs.logits(params, {"tokens": toks}, remat=False)
    rel = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32)))
                / (jnp.max(jnp.abs(a.astype(jnp.float32))) + 1e-9))
    assert rel < 1e-2, rel
