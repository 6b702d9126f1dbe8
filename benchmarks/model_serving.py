"""Model-serving benchmark: real models and Pallas kernels on the
compiled serving path (``BENCH_model_serving.json``).

Three sections, each on its own runtime:

* **video** — the §5.2 video pipeline (registry VLM detector as a
  ``ModelOp`` + two fused classifier heads): per-request p50/p99, then
  an ``SLOController`` tick planned against the ModelOp's *measured*
  cost curves (``seed_from_model_ops``) — the propose -> hot-apply path
  must complete (``controller`` is ``apply`` or ``steady``).
* **cascade** — transformer prefill -> decode steps fused into one
  device-resident chain: per-request p50/p99 plus greedy-token parity
  against the plain model loop (``tokens_match``).
* **kernel** — a fused chain whose attention step is a placed Pallas
  kernel (``kernel_step("flash_attention")``): numerical agreement with
  the unfused reference-path compile (``outputs_match``), jitted
  kernel-vs-reference step latency at batch shapes, ONE executable
  dispatch per batched request (``batch_dispatches``), and a flat trace
  counter across re-compile + re-registration of the same flow
  (``fresh_traces_reregister`` must be 0 — step identity is memoized, so
  the green generation reuses the blue generation's executables).

Absolute times are CPU/interpret-mode numbers (tiny configs, Pallas
``interpret=True``); the claims under test are structural — parity,
single-dispatch batching, trace stability — not kernel speed.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
import time
from typing import Any, Dict, List, Optional

from typing import Tuple

import numpy as np

from benchmarks.common import percentile, row, run_requests

import jax
import jax.numpy as jnp


def _load_example(name: str):
    p = (pathlib.Path(__file__).resolve().parents[1] / "examples"
         / f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_bench_{name}", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- kernel section ----------------------------------------------------------

_H, _KV, _S, _HD = 2, 2, 64, 16          # tiny interpret-mode shapes
_BATCH = 4


def _scale_q(q: "jax.Array", k: "jax.Array", v: "jax.Array"
             ) -> "Tuple[jax.Array, jax.Array, jax.Array]":
    return q * 0.5, k, v


def _kernel_flow(step):
    from repro.core.dataflow import Dataflow
    fl = Dataflow([("q", jax.Array), ("k", jax.Array), ("v", jax.Array)])
    fl.output = fl.map(_scale_q, names=["q", "k", "v"], gpu=True) \
        .map(step, names=["o"], gpu=True)
    return fl


def _kernel_table(rows: int):
    from repro.core.table import Table
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (rows, _H, _S, _HD), jnp.float32) * 0.3
    k = jax.random.normal(ks[1], (rows, _KV, _S, _HD), jnp.float32) * 0.3
    v = jax.random.normal(ks[2], (rows, _KV, _S, _HD), jnp.float32) * 0.3
    cols = [("q", jax.Array), ("k", jax.Array), ("v", jax.Array)]
    return Table(cols, [(q[i], k[i], v[i]) for i in range(rows)])


def _time_best(fn, runs: int = 3) -> float:
    fn()                                  # warm (trace + compile)
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _kernel_section(n_requests: int) -> Dict[str, Any]:
    from repro.core.lowering import EXECUTABLE_CACHE, forced_batched_routing
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref
    from repro.runtime import NetModel, Runtime

    step = kops.kernel_step("flash_attention", causal=True,
                            block_q=32, block_k=32)
    table = _kernel_table(_BATCH)
    out: Dict[str, Any] = {"kernel": "flash_attention",
                           "shape": f"[{_BATCH},{_H},{_S},{_HD}]"}

    rt = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0))
    try:
        dep = rt_dep = _kernel_flow(step).deploy(rt, fusion=True,
                                                 name="kernel_bench")
        ref_dep = _kernel_flow(step).deploy(
            rt, fusion=False, place_kernels=False, name="kernel_ref")
        got = dep.execute(table).result(120)
        want = ref_dep.execute(table).result(120)
        err = max(float(jnp.max(jnp.abs(g.values[0] - w.values[0])))
                  for g, w in zip(got.rows, want.rows))
        out["max_abs_err"] = err
        out["outputs_match"] = bool(err < 2e-5)
        out["placed"] = [k for o in dep.plan.ops for k in o.kernels]

        # one executable dispatch serves the whole batch: cache lookups
        # (hits + misses) advance once per chain dispatch
        chain_ops = [o.op for o in dep.plan.ops]
        with forced_batched_routing(chain_ops):
            dep.execute(table).result(120)          # warm the bucket
            s0 = EXECUTABLE_CACHE.stats()
            dep.execute(table).result(120)
            s1 = EXECUTABLE_CACHE.stats()
        out["batch_dispatches"] = ((s1["hits"] + s1["misses"])
                                   - (s0["hits"] + s0["misses"]))
        out["fresh_traces_batched"] = s1["traces"] - s0["traces"]

        # re-compiling + re-registering the SAME flow must re-trace
        # nothing: kernel steps and their Pallas twins are memoized, so
        # chain signatures (and executables) are shared across plans
        t_before = EXECUTABLE_CACHE.traces()
        dep2 = _kernel_flow(step).deploy(rt, fusion=True,
                                         name="kernel_bench2")
        dep2.execute(table).result(120)
        out["fresh_traces_reregister"] = \
            EXECUTABLE_CACHE.traces() - t_before

        lats = run_requests(
            lambda i: rt_dep.execute(table).result(120), n_requests)
        out["p50_ms"] = percentile(lats, 50) * 1e3
        out["p99_ms"] = percentile(lats, 99) * 1e3
        out["requests"] = n_requests
    finally:
        rt.stop()

    # step-level latency at the batch shapes: the jitted Pallas kernel
    # (interpret mode on CPU) vs the jitted pure-jnp reference
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (_BATCH, _H, _S, _HD), jnp.float32)
    k = jax.random.normal(ks[1], (_BATCH, _KV, _S, _HD), jnp.float32)
    v = jax.random.normal(ks[2], (_BATCH, _KV, _S, _HD), jnp.float32)
    ref_jit = jax.jit(functools.partial(kref.attention_ref, causal=True))
    out["kernel_step_us"] = _time_best(
        lambda: kops.flash_attention(q, k, v, causal=True, block_q=32,
                                     block_k=32).block_until_ready()) * 1e6
    out["ref_step_us"] = _time_best(
        lambda: ref_jit(q, k, v).block_until_ready()) * 1e6
    return out


# -- pipeline sections -------------------------------------------------------

def _video_section(n_requests: int) -> Dict[str, Any]:
    from repro.core.table import Table
    from repro.profiling.controller import SLOController
    from repro.profiling.profiler import profile_plan, seed_from_model_ops
    from repro.runtime import NetModel, Runtime

    vp = _load_example("video_pipeline")
    rt = Runtime(n_cpu=4, n_gpu=1, net=NetModel(scale=0.0))
    try:
        dep = vp.build(rt, name="video_bench")
        rng = np.random.default_rng(0)

        def frame_table():
            return Table([("tokens", jax.Array)],
                         [(jnp.asarray(rng.integers(0, 500, vp.SEQ),
                                       jnp.int32),)])

        # the controller's model, built BEFORE traffic so the tick sees
        # a fresh arrival window: ModelOp-measured curves for the
        # detector chain, a quick sweep for the rest
        profile = seed_from_model_ops(dep.plan, batch_sizes=(1, 2, 4))
        seeded = len(profile.curves)
        swept = profile_plan(dep.plan, frame_table(), batch_sizes=(1, 2),
                             runs=1, warmup=1)
        for key, c in swept.curves.items():
            profile.curves.setdefault(key, c)

        dep.execute(frame_table()).result(120)      # warm off the clock
        lats = run_requests(
            lambda i: dep.execute(frame_table()).result(120), n_requests)
        ev = SLOController(rt, dep, slo_p99_s=0.5, profile=profile,
                           replan_cooldown_s=1e9).tick()
        return {"p50_ms": percentile(lats, 50) * 1e3,
                "p99_ms": percentile(lats, 99) * 1e3,
                "requests": n_requests,
                "modelop_seeded_curves": seeded,
                "controller": ev.kind}
    finally:
        rt.stop()


def _cascade_section(n_requests: int) -> Dict[str, Any]:
    from repro.core.table import Table
    from repro.runtime import NetModel, Runtime

    dc = _load_example("decode_cascade")
    rt = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0))
    try:
        model, params, pre, dec = dc.build_ops(measure=False)
        dep = dc.build(rt, pre, dec, steps=dc.STEPS,
                       name="cascade_bench")
        toks = jax.random.randint(jax.random.PRNGKey(1), (3, dc.SEQ),
                                  0, model.cfg.vocab_size)
        table = Table([("tokens", jax.Array)],
                      [(toks[i],) for i in range(3)])
        out = dep.execute(table).result(300)        # warm off the clock
        got = [int(r.values[0]) for r in out.rows]
        want = dc.reference_decode(model, params, toks, steps=dc.STEPS)
        lats = run_requests(
            lambda i: dep.execute(table).result(300), n_requests)
        return {"p50_ms": percentile(lats, 50) * 1e3,
                "p99_ms": percentile(lats, 99) * 1e3,
                "requests": n_requests, "steps": dc.STEPS,
                "tokens_match": got == want}
    finally:
        rt.stop()


def run(n_requests: int = 30,
        json_path: Optional[str] = None) -> List[str]:
    from repro.core.lowering import EXECUTABLE_CACHE

    video = _video_section(n_requests)
    cascade = _cascade_section(max(4, n_requests // 3))
    kernel = _kernel_section(max(4, n_requests // 3))
    result = {"suite": "model_serving", "video": video,
              "cascade": cascade, "kernel": kernel,
              "cache_stats": EXECUTABLE_CACHE.stats()}
    if json_path:
        with open(json_path, "w") as f:
            json.dump(result, f, indent=1, sort_keys=True, default=str)

    return [
        row("model_video", video["p50_ms"] * 1e3,
            f"p99={video['p99_ms']:.1f}ms "
            f"controller={video['controller']} n={video['requests']}"),
        row("model_cascade", cascade["p50_ms"] * 1e3,
            f"p99={cascade['p99_ms']:.1f}ms "
            f"tokens_match={cascade['tokens_match']} "
            f"steps={cascade['steps']}"),
        row("kernel_flash_chain", kernel["p50_ms"] * 1e3,
            f"p99={kernel['p99_ms']:.1f}ms "
            f"outputs_match={kernel['outputs_match']} "
            f"dispatches/batch={kernel['batch_dispatches']} "
            f"retraces={kernel['fresh_traces_reregister']}"),
        row("kernel_flash_step", kernel["kernel_step_us"],
            f"ref={kernel['ref_step_us']:.0f}us "
            f"shape={kernel['shape']} interpret-mode"),
    ]


def check_flows():
    """Static-verifier hook (``python -m repro.check``): the kernel flow
    exercises the CF103 tile lint against real inferred operand shapes."""
    from repro.kernels import ops as kops
    step = kops.kernel_step("flash_attention", causal=True,
                            block_q=32, block_k=32)
    return [{"name": "kernel-serving", "flow": _kernel_flow(step),
             "compile": {"fusion": True}, "sample": _kernel_table(2)}]
