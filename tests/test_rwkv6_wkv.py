"""The RWKV-6 WKV recurrence: the chunked form a prompt takes against the
one-timestep scan a decode step takes, and which form each serving stage
traces."""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.rwkv6_1b6 import CONFIG as RWKV6
from repro.models import build_model, rwkv6
from repro.models.registry import model_stage_op
from repro.obs import EVENTS, keys

H, HD = 2, 16
# ranges of log w: "underflow" makes w itself 0 in float32
DECAYS = {"weak": (-1e-3, -1e-3), "mixed": (-1e-3, -30.0),
          "strong": (-30.0, -30.0), "underflow": (-120.0, -2000.0)}


def _inputs(B, T, decay, state, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, HD)) for i in range(3))
    u = jax.random.normal(ks[3], (H, HD)) * 0.5
    lo, hi = DECAYS[decay]
    logw = -jnp.exp(jax.random.uniform(ks[4], (B, T, H, HD),
                                       minval=np.log(-lo),
                                       maxval=np.log(-hi)))
    S0 = (jax.random.normal(ks[5], (B, H, HD, HD)) if state == "random"
          else jnp.zeros((B, H, HD, HD)))
    return r, k, v, logw, u, S0


_scan = jax.jit(lambda r, k, v, logw, u, S: rwkv6.wkv_scan(
    r, k, v, jnp.exp(logw), u, S))
_chunked = jax.jit(rwkv6.wkv_chunked)


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * scale)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("state", ["zero", "random"])
@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("T", [16, 37, 256])
def test_wkv_chunked_matches_scan(T, B, state, decay):
    """y and the final state of the chunked form equal the step scan's in
    float32; T = 37 pads the last chunk."""
    args = _inputs(B, T, decay, state, seed=T * 10 + B)
    if decay == "underflow":
        assert bool(jnp.all(jnp.exp(args[3]) == 0))
    y, S = _chunked(*args)
    y_ref, S_ref = _scan(*args)
    assert y.shape == y_ref.shape and S.shape == S_ref.shape
    assert bool(jnp.all(jnp.isfinite(y))) and bool(jnp.all(jnp.isfinite(S)))
    _close(y, y_ref, 2e-5)
    _close(S, S_ref, 2e-5)


def _rehearsal():
    """rwkv6-1.6b at the benchmark's rehearsal widths."""
    cfg = dataclasses.replace(RWKV6, num_layers=2, d_model=128, d_ff=256,
                              vocab_size=512, rwkv_head_dim=32)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _step_path(r, k, v, logw, u, state, **_):
    return rwkv6.wkv_scan(r, k, v, jnp.exp(logw), u, state)


@pytest.mark.parametrize("prompt", [16, 100])
def test_prefill_stage_matches_step_scan(prompt, monkeypatch):
    """The served prefill stage (rows batched natively, as under the
    batched chain) gives the same logits, next token and cache leaves as
    the same model whose prompt WKV runs the step scan."""
    cfg, model, params = _rehearsal()
    toks = jax.random.randint(jax.random.PRNGKey(prompt), (3, prompt), 0,
                              cfg.vocab_size)

    def run():
        op = model_stage_op(model, params, "prefill", seq_len=prompt,
                            cache_len=prompt + 4, measure=False)
        logits, _ = jax.jit(lambda p, t: model.prefill(
            p, {"tokens": t}, prompt + 4))(params, toks)
        return logits, jax.vmap(op.fn)(toks)

    logits, outs = run()
    monkeypatch.setattr(rwkv6, "wkv_chunked", _step_path)
    logits_ref, outs_ref = run()
    # float32 to its rounding; bfloat16 to one step of its 8-bit mantissa
    tol = lambda x: 1e-5 if x.dtype == jnp.float32 else 2.0 ** -7
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    _close(f32(logits), f32(logits_ref), tol(logits))
    assert len(outs) == len(outs_ref) == 5       # tok, pos, S, 2 shifts
    np.testing.assert_array_equal(outs[0], outs_ref[0])
    np.testing.assert_array_equal(outs[1], outs_ref[1])
    for got, want in zip(outs[2:], outs_ref[2:]):
        assert got.shape == want.shape and got.dtype == want.dtype
        _close(f32(got), f32(want), tol(want))


def test_wkv_path_counted_by_stage():
    """Tracing the prefill stage counts the chunked form, a decode step
    the step scan; neither counts the other."""
    _, model, params = _rehearsal()
    key = {"chunked": keys.WKV_CHUNKED, "step": keys.WKV_STEP}

    def counts():
        snap = EVENTS.snapshot("model/wkv/")
        return {p: snap.get(k, 0) for p, k in key.items()}

    def stage(name):
        return jax.vmap(model_stage_op(model, params, name, seq_len=16,
                                       cache_len=20, measure=False).fn)

    before = counts()
    state = stage("prefill")(jnp.zeros((2, 16), jnp.int32))
    mid = counts()
    assert mid["chunked"] > before["chunked"]
    assert mid["step"] == before["step"]
    stage("decode")(*state)
    after = counts()
    assert after["step"] > mid["step"]
    assert after["chunked"] == mid["chunked"]


def _load_wkv_check():
    path = Path(__file__).resolve().parents[1] / "scripts" / "wkv_check.py"
    spec = importlib.util.spec_from_file_location("wkv_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("chunk", [rwkv6.CHUNK, 32, 64])
def test_wkv_check_reads_each_chunk_right(chunk):
    """The device check (``scripts/wkv_check.py``) at small widths on the
    CPU: one gap per shape, each within its tolerance, with a padded tail."""
    wkv_check = _load_wkv_check()
    gaps = wkv_check.check((chunk,), lengths=(40,), rows=(1, 4), heads=2,
                           head_dim=8)
    assert sorted(gaps) == [f"T40_B{b}_C{chunk}" for b in (1, 4)]
    assert max(gaps.values()) <= wkv_check.TOL, gaps
