"""ModelOp: registry model serving stages as first-class plan operators.

Stage functions must agree with the underlying model (per-row AND
native-batched under vmap), lower into jitted chains, expose per-bucket
cost hooks that seed the estimator's curves, and drive the SLO
controller's propose -> hot-apply tick.  Also covers the
distribution-aware warm walk (observed buckets first).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny_config
from repro.core import operators as ops
from repro.core.compiler import compile_flow
from repro.core.dataflow import Dataflow
from repro.core.lowering import map_is_jax_lowerable
from repro.core.table import Table
from repro.models import build_model
from repro.models.registry import model_stage_op
from repro.profiling.controller import SLOController
from repro.profiling.profiler import seed_from_model_ops
from repro.profiling.replan import warm_deployment
from repro.runtime import NetModel, Runtime

ARCH = "yi-9b"
SEQ, CACHE = 8, 16


@pytest.fixture(scope="module")
def stages():
    cfg = get_tiny_config(ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    kw = dict(model_name=ARCH, seq_len=SEQ, cache_len=CACHE,
              measure=False)
    return (cfg, model, params,
            model_stage_op(model, params, "logits", **kw),
            model_stage_op(model, params, "prefill", **kw),
            model_stage_op(model, params, "decode", **kw))


@pytest.fixture(scope="module")
def rt():
    r = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0))
    yield r
    r.stop()


def _toks(cfg, n):
    return jax.random.randint(jax.random.PRNGKey(1), (n, SEQ), 0,
                              cfg.vocab_size)


def test_stage_ops_are_lowerable_model_ops(stages):
    _, _, _, lg, pre, dec = stages
    for op in (lg, pre, dec):
        assert isinstance(op, ops.ModelOp)
        assert map_is_jax_lowerable(op), op.name
        assert op.cost_hook is None            # measure=False
    assert lg.name == f"model[{ARCH}:logits]"
    assert pre.stage == "prefill" and dec.stage == "decode"


def test_logits_stage_matches_model(stages):
    cfg, model, params, lg, _, _ = stages
    toks = _toks(cfg, 2)
    want, _ = model.logits(params, {"tokens": toks}, remat=False)
    want = want[:, -1]
    got_row = lg.fn(toks[0])                   # per-row path
    got_vmap = jax.vmap(lg.fn)(toks)           # batched-lowered path
    np.testing.assert_allclose(np.asarray(got_row),
                               np.asarray(want[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_vmap),
                               np.asarray(want), atol=1e-5)


def test_prefill_decode_stages_match_model_loop(stages):
    cfg, model, params, _, pre, dec = stages
    toks = _toks(cfg, 2)
    steps = 2

    # the op path, natively batched under vmap (what lowered chains do)
    state = jax.vmap(pre.fn)(toks)
    for _ in range(steps):
        state = jax.vmap(dec.fn)(*state)
    got = [int(t) for t in state[0]]

    # the op path per row (the runtime's singleton route)
    row = pre.fn(toks[0])
    for _ in range(steps):
        row = dec.fn(*row)
    got_row = int(row[0])

    # the plain model loop (ground truth)
    logits, cache = model.prefill(params, {"tokens": toks}, CACHE)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    pos = jnp.full((2,), SEQ, jnp.int32)
    for _ in range(steps):
        lg_, cache = model.decode_step(params, tok[:, None], pos, cache)
        tok = jnp.argmax(lg_[:, -1], -1).astype(jnp.int32)
        pos = pos + 1
    want = [int(t) for t in tok]

    assert got == want
    assert got_row == want[0]


def _stage_of(op_name):
    """The serving stage an op's ``op_name`` metadata puts it under:
    a path segment ``prefill`` / ``decode`` / ``logits``, bare or
    wrapped by a transform (``vmap(decode)``)."""
    for seg in op_name.split("/"):
        m = re.fullmatch(r"(?:\w+\()*(prefill|decode|logits)\)*", seg)
        if m:
            return m.group(1)
    return None


def test_stages_name_their_device_ops(stages):
    """Each stage's body runs under ``jax.named_scope(stage)``, so the
    compiled program's op metadata (what the profiler's op events carry)
    names the stage, per row and under vmap."""
    cfg, _, _, lg, pre, dec = stages
    toks = _toks(cfg, 2)

    def chain(t):
        return dec.fn(*pre.fn(t))

    for fn, arg, want in ((chain, toks[0], {"prefill", "decode"}),
                          (jax.vmap(chain), toks, {"prefill", "decode"}),
                          (lg.fn, toks[0], {"logits"})):
        hlo = jax.jit(fn).lower(arg).compile().as_text()
        got = {_stage_of(n) for n in re.findall(r'op_name="([^"]*)"', hlo)}
        assert want <= got, got


def test_weights_reach_executables_as_arguments(stages, rt):
    """A served stage's weights are an argument of the chain executable,
    never constants of the compiled program: lowering a full-width model
    with its weights captured as constants exhausts the host's memory.
    JAX warns once captured constants pass the threshold set here, far
    below the tiny model's weights."""
    import warnings
    cfg, _model, params, lg, _, _ = stages
    assert lg.fn.__consts__ is params
    fl = Dataflow([("tokens", jax.Array)])
    fl.output = fl.map(_clip, names=["tokens"], gpu=True).apply_op(
        lg, gpu=True)
    dep = compile_flow(fl, rt, fusion=True, name="weights_as_args")
    tab = Table([("tokens", jax.Array)],
                [(t,) for t in _toks(cfg, 3)])
    prev = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 10_000)
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            out = dep.execute(tab).result(120)            # batched
            dep.execute(Table(tab.schema, tab.rows[:1])).result(120)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", prev)
    assert len(out.rows) == 3
    assert not [w for w in caught if "constants were captured"
                in str(w.message)]


def _clip(tokens: jax.Array) -> jax.Array:
    return jnp.clip(tokens, 0, 10**6)


def test_cost_hook_contract(stages):
    cfg, model, params, _, _, _ = stages
    op = model_stage_op(model, params, "logits", model_name=ARCH,
                        seq_len=SEQ, cache_len=CACHE, runs=1)
    d = op.cost_hook(2)
    assert {"mean_s", "p99_s", "cv", "runs", "out_bytes"} <= set(d)
    assert d["mean_s"] > 0 and d["p99_s"] >= d["mean_s"]
    assert d["runs"] == 1 and d["out_bytes"] > 0


def _gate(tokens: "jax.Array") -> "jax.Array":
    return jnp.abs(tokens)


def test_seed_from_model_ops_feeds_controller(stages, rt):
    """A measured ModelOp's cost hooks become estimator curves keyed by
    the (fused) physical op, and the controller completes a
    propose -> hot-apply tick against them."""
    cfg, model, params, _, _, _ = stages
    det = model_stage_op(model, params, "logits", model_name=ARCH,
                         seq_len=SEQ, cache_len=CACHE, runs=1)
    fl = Dataflow([("tokens", jax.Array)])
    fl.output = fl.map(_gate, names=["tokens"], gpu=True) \
        .apply_op(det, gpu=True)
    dep = compile_flow(fl, rt, fusion=True, name="modelop_seed")

    profile = seed_from_model_ops(dep.plan, batch_sizes=(1, 2))
    assert len(profile.curves) == 1
    (op_id, curve), = profile.curves.items()
    assert any(isinstance(s, ops.ModelOp)
               for s in getattr(dep.plan.op(op_id).op, "ops",
                                [dep.plan.op(op_id).op]))
    assert set(curve.buckets) == {1, 2}
    assert all(b.mean_s > 0 and b.out_bytes > 0
               for b in curve.buckets.values())

    tab = Table([("tokens", jax.Array)], [(_toks(cfg, 1)[0],)])
    for _ in range(3):
        dep.execute(tab).result(120)
    ev = SLOController(rt, dep, slo_p99_s=0.5, profile=profile,
                       replan_cooldown_s=1e9).tick()
    assert ev.kind in ("apply", "steady"), (ev.kind, ev.detail)


def _m1(x: "jax.Array") -> "jax.Array":
    return x * 2.0


def _m2(x: "jax.Array") -> "jax.Array":
    return x + 1.0


def test_warm_deployment_prefers_observed_buckets(rt):
    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(_m1, names=["x"], gpu=True) \
        .map(_m2, names=["y"], gpu=True)
    dep = compile_flow(fl, rt, fusion=True, name="warm_obs")
    tab = Table([("x", jax.Array)], [(jnp.ones((4,)),)])

    rep = warm_deployment(rt, dep, tab)
    assert rep["observed"] == []               # no traffic yet

    # live histogram: mostly 4-row merges, some 2s, one odd 3 (pads to 4)
    for v in (4, 4, 4, 2, 3):
        rt.record_metric("batch/warm_obs/warm_obs/n:any/size", v)
    rt.record_metric("batch/warm_obs/warm_obs/n:any/latency_s", 1.0)
    rep = warm_deployment(rt, dep, tab)
    assert rep["observed"] == [4, 2]
    assert rep["buckets"][:2] == [4, 2]        # observed first...
    assert set(rep["buckets"]) >= {1, 2, 4}    # ...full coverage kept
    assert rep["errors"] == []
