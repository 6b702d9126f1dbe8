#!/usr/bin/env python3
"""Drive the compiled serving path once on one TPU chip and check it.

    python3 chip_smoke.py

Everything runs in this one process, which holds the chip, and stops at
the first failure with a non-zero exit code:

1. device  — JAX must find a TPU (never a CPU fallback).
2. kernels — each ``KERNEL_REGISTRY`` kernel served as a ``kernel_step``
   map in a ``compile_flow`` deployment, where ``PlaceKernelsPass`` swaps
   in the Pallas kernel, at real widths (yi-9b attention, rwkv6-1.6b
   WKV, RG-LRU width 2560).  Each must run as a Mosaic kernel (interpret
   resolved to False, ``tpu_custom_call`` compiled, batched dispatches
   on the lowered chain) and match its ``kernels/ref.py`` oracle.
3. model   — yi-9b at its published widths, depth cut to 16 of 48 layers,
   seeded random bf16 weights, served through ``Runtime``:
   (a) a ``logits`` stage against the model's float32 forward;
   (b) a prefill -> decode cascade (positions, token range, and a
       repeated request that must trace nothing);
   (c) the model's own ``prefill`` then ``decode_step`` logits against
       the float32 forward at the same positions.

No lowering fallback may latch anywhere.  Times printed here are smoke
timings of one run, not benchmark results.  The last line of standard
output is the JSON verdict, printed only when every phase passed.
"""
import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Tuple

ROOT = Path(__file__).resolve().parent

# a failed TPU initialisation must raise, not fall back to the CPU
if not os.environ.get("JAX_PLATFORMS"):
    os.environ["JAX_PLATFORMS"] = "tpu"

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

#: tolerance on kernel outputs (absolute and relative).  The attention
#: kernels run their dots on the MXU, which may take float32 operands in
#: bf16 passes (relative error 2^-8 per operand); the scans are float32
#: elementwise.  A wrong mask, block index or carried state errs by 0.1
#: or more, so 1e-2 separates the two.
KERNEL_TOL = 1e-2

#: tolerance on served bf16 logits against the float32 forward, as the
#: largest per-row relative L2 error.  Weights are the same bf16 values;
#: only activations differ, each bf16 rounding costing up to 2^-9
#: relative, and a 16-layer residual stream rounds some hundred times,
#: which adds up to about 1e-2 in quadrature.  5e-2 leaves room for that
#: and still fails a wrong position, cache slot or layer, which moves the
#: logits by O(1) relative.
LOGITS_TOL = 5e-2


def _say(msg: str) -> None:
    print(msg, flush=True)


# -- 1. device -----------------------------------------------------------------

def device_phase() -> dict:
    devs = jax.devices()
    d = devs[0]
    _say(f"device: platform={d.platform} kind={d.device_kind} "
         f"count={len(devs)} jax={jax.__version__}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (platform "
                         f"{d.platform!r}); refusing to run on it")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# -- 2. kernels ----------------------------------------------------------------

def _pass2(a: jax.Array, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    return a, x


def _pass3(q: jax.Array, k: jax.Array, v: jax.Array
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    return q, k, v


def _pass4(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array
           ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    return r, k, v, w


def _pass5(q: jax.Array, kc: jax.Array, vc: jax.Array, kpos: jax.Array,
           qpos: jax.Array
           ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    return q, kc, vc, kpos, qpos


_GATES = {2: _pass2, 3: _pass3, 4: _pass4, 5: _pass5}


def kernel_cases(*, rows=4, heads=32, kv_heads=4, head_dim=128, seq=512,
                 cache=1024, wkv_heads=32, wkv_dim=64, scan_len=256,
                 width=2560, seed=0):
    """kernel name -> (step kwargs, bound args, batched input columns).
    Defaults are the real widths: yi-9b attention (32 heads, 4 kv heads,
    head dim 128), rwkv6-1.6b's 32 WKV heads of 64, RG-LRU width 2560."""
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rand(shape, scale=0.3):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    # ring-cache positions: row i holds cache - 64*i slots, the rest empty
    fill = jnp.maximum(cache - 64 * jnp.arange(rows), 1)
    kpos = jnp.where(jnp.arange(cache)[None] < fill[:, None],
                     jnp.arange(cache)[None], -1).astype(jnp.int32)
    decay = jax.nn.sigmoid(rand((rows, scan_len, wkv_heads, wkv_dim), 1.0))
    return {
        "flash_attention": (
            {"causal": True}, None,
            [rand((rows, heads, seq, head_dim)),
             rand((rows, kv_heads, seq, head_dim)),
             rand((rows, kv_heads, seq, head_dim))]),
        "decode_attention": (
            {}, None,
            [rand((rows, heads, head_dim)),
             rand((rows, kv_heads, cache, head_dim)),
             rand((rows, kv_heads, cache, head_dim)),
             kpos, (fill - 1).astype(jnp.int32)]),
        "wkv6": (
            {}, {"u": rand((wkv_heads, wkv_dim))},
            [rand((rows, scan_len, wkv_heads, wkv_dim)) for _ in range(3)]
            + [0.5 + 0.45 * decay]),
        "rglru_scan": (
            {}, None,
            [jax.nn.sigmoid(rand((rows, scan_len, width), 1.0)),
             rand((rows, scan_len, width))]),
    }


def kernel_phase(rt, cases, *, interpret: bool) -> list:
    """Serve each kernel through a deployment and check it against its
    oracle; returns the deployments.  ``interpret`` is what the kernels
    must resolve to: False on the chip, True only in the CPU rehearsal of
    this script."""
    from repro.core.compiler import compile_flow
    from repro.core.dataflow import Dataflow
    from repro.core.table import Table
    from repro.kernels import ops as kops

    got_interpret = kops._resolve_interpret(None)
    if got_interpret is not interpret:
        raise AssertionError(f"kernels resolved interpret={got_interpret}, "
                             f"expected {interpret}")
    deps = []
    for name, (params, bound, cols) in cases.items():
        t0 = time.perf_counter()
        spec = kops.KERNEL_REGISTRY[name]
        names = list(spec.args[:len(cols)])
        step = kops.kernel_step(name, bound=bound, **params)
        fl = Dataflow([(c, jax.Array) for c in names])
        fl.output = fl.map(_GATES[len(cols)], names=names, gpu=True) \
            .map(step, names=["out"], gpu=True)
        dep = compile_flow(fl, rt, fusion=True, name=f"smoke-{name}")
        deps.append(dep)
        rows = cols[0].shape[0]
        table = Table([(c, jax.Array) for c in names],
                      [tuple(c[i] for c in cols) for i in range(rows)])
        out = dep.execute(table).result(600)
        got = np.stack([np.asarray(r.values[0], np.float32)
                        for r in out.rows])
        with jax.default_matmul_precision("highest"):
            want = np.asarray(spec.ref(*cols, *(bound or {}).values(),
                                       **params), np.float32)
        chains = [o.op for o in dep.plan.ops if o.kernels]
        if not chains or not all(getattr(c, "batch_dispatches", 0) > 0
                                  for c in chains):
            raise AssertionError(f"{name}: no batched dispatch on a "
                                 f"lowered chain\n{dep.explain()}")
        text = jax.jit(jax.vmap(step.__kernel_placed__)).lower(
            *cols).compile().as_text()
        mosaic = "tpu_custom_call" in text
        err = float(np.max(np.abs(got - want)))
        _say(f"kernel {name}: rows={rows} in={[tuple(c.shape[1:]) for c in cols]} "
             f"max_abs_err={err:.3e} tpu_custom_call={mosaic} "
             f"{time.perf_counter() - t0:.1f}s")
        if not interpret and not mosaic:
            raise AssertionError(f"{name}: no tpu_custom_call compiled")
        np.testing.assert_allclose(got, want, atol=KERNEL_TOL,
                                   rtol=KERNEL_TOL, err_msg=name)
    return deps


# -- 3. model ------------------------------------------------------------------

def _load_example(name: str):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"_smoke_{name}", ROOT / "examples" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_logits(model, params, tokens):
    """The model's own full-sequence forward in float32 at ``highest``
    matmul precision, on the same bf16 weights.  The embedding table and
    final norm are upcast whole; the layer weights stay bf16 and are
    promoted inside the layer scan, one layer at a time, so the reference
    fits beside the served model."""
    f32 = lambda a: a.astype(jnp.float32)                   # noqa: E731
    ref = dict(params, embed=f32(params["embed"]),
               final_norm=jax.tree.map(f32, params["final_norm"]))
    with jax.default_matmul_precision("highest"):
        out, _ = jax.jit(lambda p, t: model.logits(
            p, {"tokens": t}, remat=False))(ref, tokens)
        return np.asarray(out, np.float32)


def _rel_err(got, want) -> float:
    """Largest per-row relative L2 error over the last axis."""
    got = np.asarray(got, np.float32)
    return float(np.max(np.linalg.norm(got - want, axis=-1)
                        / np.linalg.norm(want, axis=-1)))


def _check_logits(label: str, got, want) -> None:
    err = _rel_err(got, want)
    agree = float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))
    _say(f"model ({label}): max rel L2 err {err:.3e} (tol {LOGITS_TOL}), "
         f"argmax agreement {agree:.2f}")
    if not err <= LOGITS_TOL:
        raise AssertionError(f"{label}: logits off the float32 reference "
                             f"by {err:.3e} > {LOGITS_TOL}")


def model_phase(rt, cfg, *, prompts=4, prompt_len=512, cache_len=1024,
                steps=8, seed=0) -> Tuple[dict, list]:
    """Serve ``cfg`` through ``Runtime`` and check it; returns timings
    and the deployments."""
    from repro.core.compiler import compile_flow
    from repro.core.dataflow import Dataflow
    from repro.core.lowering import EXECUTABLE_CACHE
    from repro.core.table import Table
    from repro.models.registry import model_stage_op

    dc = _load_example("decode_cascade")
    t0 = time.perf_counter()
    model, params, pre, dec = dc.build_ops(
        cfg, seq_len=prompt_len, cache_len=cache_len, measure=False,
        seed=seed)
    jax.block_until_ready(params)
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    timings = {"build_s": time.perf_counter() - t0}
    _say(f"model: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads}/"
         f"{cfg.num_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size}; "
         f"layers {cfg.num_layers}; {n_bytes / 1e9:.2f} GB of "
         f"{jnp.dtype(cfg.dtype).name} weights, built in "
         f"{timings['build_s']:.1f}s")

    toks = jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (prompts, prompt_len + steps), 0,
                              cfg.vocab_size, jnp.int32)
    prompt_table = Table([("tokens", jax.Array)],
                         [(toks[i, :prompt_len],) for i in range(prompts)])
    want = reference_logits(model, params, toks)    # [B, P + steps, V]

    # (a) a logits stage served through the runtime
    vocab = cfg.vocab_size

    def gate(tokens: jax.Array) -> jax.Array:
        return jnp.clip(tokens, 0, vocab - 1)

    logits_op = model_stage_op(model, params, "logits", model_name=cfg.name,
                               seq_len=prompt_len, measure=False)
    fl = Dataflow([("tokens", jax.Array)])
    fl.output = fl.map(gate, names=["tokens"], gpu=True).apply_op(
        logits_op, gpu=True)
    dep_logits = compile_flow(fl, rt, fusion=True, name="smoke-logits")
    t0 = time.perf_counter()
    out = dep_logits.execute(prompt_table).result(1200)
    timings["logits_first_s"] = time.perf_counter() - t0
    _check_logits("a: served logits stage",
                  np.stack([r.values[0] for r in out.rows]),
                  want[:, prompt_len - 1])

    # (b) prefill -> decode cascade served through the runtime
    dep = dc.build(rt, pre, dec, steps=steps, name="smoke-cascade")
    t0 = time.perf_counter()
    first = dep.execute(prompt_table).result(1200)
    timings["cascade_first_s"] = time.perf_counter() - t0
    traces = EXECUTABLE_CACHE.traces()
    t0 = time.perf_counter()
    again = dep.execute(prompt_table).result(1200)
    timings["cascade_repeat_s"] = time.perf_counter() - t0
    toks_out = [int(r.values[0]) for r in first.rows]
    pos_out = [int(r.values[1]) for r in first.rows]
    _say(f"model (b: served cascade): {prompts} prompts x {prompt_len} "
         f"tokens, cache {cache_len}, {steps} decode steps; tokens "
         f"{toks_out}, pos {pos_out}; per request {timings['cascade_first_s']:.2f}s "
         f"first (compile included), {timings['cascade_repeat_s']:.3f}s "
         f"repeated (smoke timings)")
    if len(first.rows) != prompts or pos_out != [prompt_len + steps] * prompts:
        raise AssertionError(f"cascade positions {pos_out}, expected "
                             f"{prompt_len + steps} for {prompts} rows")
    if not all(0 <= t < cfg.vocab_size for t in toks_out):
        raise AssertionError(f"cascade tokens out of range: {toks_out}")
    if [int(r.values[0]) for r in again.rows] != toks_out:
        raise AssertionError("repeated identical request changed tokens")
    if EXECUTABLE_CACHE.traces() != traces:
        raise AssertionError(f"repeated request traced "
                             f"{EXECUTABLE_CACHE.traces() - traces} times")

    # (c) the model's own prefill then decode_step, teacher-forced
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t},
                                                 cache_len))
    decode = jax.jit(model.decode_step, donate_argnums=(3,))
    lg, cache = prefill(params, toks[:, :prompt_len])
    got = [lg[:, 0]]
    for i in range(steps):
        pos = jnp.full((prompts,), prompt_len + i, jnp.int32)
        lg, cache = decode(params, toks[:, prompt_len + i][:, None], pos,
                           cache)
        got.append(lg[:, 0])
    _check_logits("c: prefill + decode_step",
                  np.stack([np.asarray(g, np.float32) for g in got], 1),
                  want[:, prompt_len - 1:])
    return timings, [dep_logits, dep]


def check_no_latch(deployments) -> None:
    from repro.obs import EVENTS
    latched = EVENTS.snapshot("lowering/")
    if latched:
        for dep in deployments:
            _say(dep.explain())
        raise AssertionError(f"lowering fallbacks latched: {latched}")


# -- main ----------------------------------------------------------------------

def main() -> int:
    t_start = time.perf_counter()
    device = device_phase()
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs.yi_9b import CONFIG as YI_9B
    from repro.launch.compile_cache import enable_compile_cache
    from repro.runtime import NetModel, Runtime

    _say(f"compile cache: {enable_compile_cache()}")
    rt = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0))
    try:
        t0 = time.perf_counter()
        deps = kernel_phase(rt, kernel_cases(), interpret=False)
        kernels_s = time.perf_counter() - t0
        cfg = dataclasses.replace(YI_9B, num_layers=16)
        _say(f"model cut: {YI_9B.name} depth {YI_9B.num_layers} -> "
             f"{cfg.num_layers} layers, every width as published")
        timings, model_deps = model_phase(rt, cfg)
        check_no_latch(deps + model_deps)
    finally:
        rt.stop()
    total = time.perf_counter() - t_start
    _say(f"smoke timings: total {total:.1f}s, kernels {kernels_s:.1f}s, "
         f"model build {timings['build_s']:.1f}s, first logits request "
         f"{timings['logits_first_s']:.1f}s, first cascade request "
         f"{timings['cascade_first_s']:.1f}s, repeated cascade request "
         f"{timings['cascade_repeat_s']:.3f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
