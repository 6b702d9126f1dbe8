"""Transformer prefill -> decode cascade on the compiled serving path.

A registry transformer's serving stages become first-class plan operators
(``model_stage_op``): ``prefill`` turns a prompt row into greedy-decode
state (next token, position, per-row KV cache columns) and each ``decode``
step advances it.  The compiler fuses the whole cascade into ONE
device-resident batched chain — the KV cache never leaves the device
between steps, and a whole batch of prompts runs each fused step as a
single XLA dispatch (the ModelOp's ``custom_vmap`` rule maps the row axis
onto the model's native batch dimension).

  PYTHONPATH=src python examples/decode_cascade.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_tiny_config
from repro.core.compiler import compile_flow
from repro.core.dataflow import Dataflow
from repro.core.table import Table
from repro.models import build_model
from repro.models.registry import model_stage_op
from repro.runtime import NetModel, Runtime

ARCH = "yi-9b"
SEQ = 16
CACHE = 32
STEPS = 4


def build_ops(cfg=None, *, seq_len=SEQ, cache_len=CACHE, measure=True,
              seed=0):
    """(model, params, prefill op, decode op) for ``cfg`` (default: the
    tiny ``ARCH``) with random weights from ``seed``.  Weights are made in
    one jitted call, so a full-width model is built on the device without
    float32 temporaries of every tensor.  The decode op is ONE instance
    reused at every cascade position, so recompiles share step function
    identity (stable chain signatures -> zero retraces)."""
    cfg = cfg or get_tiny_config(ARCH)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    pre = model_stage_op(model, params, "prefill", model_name=cfg.name,
                         seq_len=seq_len, cache_len=cache_len,
                         measure=measure)
    dec = model_stage_op(model, params, "decode", model_name=cfg.name,
                         seq_len=seq_len, cache_len=cache_len,
                         measure=measure)
    return model, params, pre, dec


def build_flow(pre, dec, *, steps=STEPS):
    fl = Dataflow([("tokens", jax.Array)])
    node = fl.apply_op(pre, gpu=True)
    for _ in range(steps):
        node = node.apply_op(dec, gpu=True)
    fl.output = node
    return fl


def build(rt, pre, dec, *, steps=STEPS, name="decode-cascade"):
    """Compile the cascade onto ``rt``; returns the deployed flow."""
    return compile_flow(build_flow(pre, dec, steps=steps), rt,
                        fusion=True, name=name)


def check_flows():
    """Static-verifier hook (``python -m repro.check``)."""
    from repro.models.registry import stage_input_specs
    model, _params, pre, dec = build_ops(measure=False)
    return [{"name": "decode-cascade", "flow": build_flow(pre, dec),
             "compile": {"fusion": True},
             "input_specs": stage_input_specs(model, "prefill",
                                              seq_len=SEQ,
                                              cache_len=CACHE)}]


def reference_decode(model, params, toks, *, steps=STEPS, cache_len=CACHE):
    """Plain model loop (the unfused oracle): greedy tokens after
    prefill + ``steps`` decode steps."""
    logits, cache = model.prefill(params, {"tokens": toks}, cache_len)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    pos = jnp.full(toks.shape[:1], toks.shape[1], jnp.int32)
    for _ in range(steps):
        lg, cache = model.decode_step(params, tok[:, None], pos, cache)
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
        pos = pos + 1
    return [int(x) for x in tok]


def run(prompts: int = 3, *, steps: int = STEPS, verbose: bool = False):
    """Headless run; returns a metrics dict (used by the smoke test)."""
    rt = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0))
    try:
        model, params, pre, dec = build_ops(measure=False)
        dep = build(rt, pre, dec, steps=steps)
        cfg = model.cfg
        toks = jax.random.randint(jax.random.PRNGKey(1), (prompts, SEQ),
                                  0, cfg.vocab_size)
        table = Table([("tokens", jax.Array)],
                      [(toks[i],) for i in range(prompts)])
        lats, out = [], None
        for _ in range(3):
            t0 = time.perf_counter()
            out = dep.execute(table).result(120)
            lats.append(time.perf_counter() - t0)
        got = [int(r.values[0]) for r in out.rows]
        want = reference_decode(model, params, toks, steps=steps)
        if verbose:
            print(f"fused cascade tokens:  {got}")
            print(f"reference loop tokens: {want}")
            print(f"latency: first {lats[0] * 1e3:.1f} ms, "
                  f"steady {min(lats) * 1e3:.1f} ms")
        return {"prompts": prompts, "steps": steps,
                "tokens_match": got == want,
                "first_ms": lats[0] * 1e3, "steady_ms": min(lats) * 1e3}
    finally:
        rt.stop()


def main():
    r = run(verbose=True)
    print("PARITY OK" if r["tokens_match"] else "PARITY FAILED")


if __name__ == "__main__":
    main()
