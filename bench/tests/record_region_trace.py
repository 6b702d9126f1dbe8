#!/usr/bin/env python3
"""Record the small chip trace that ``test_devregions.py`` reads.

    python3 bench/tests/record_region_trace.py <out dir>

A tiny yi-9b cascade (prefill -> decode -> decode, the registry's tiny
configuration) lowered to one batched chain and served through a
``Runtime`` on one chip, inside a ``bench.traced`` span: two singletons
with ``bench.sleep`` between them; a 0.1 s device program followed at
once by two more singletons, so that their programs queue behind it; a
burst of four that the batcher merges into one batched dispatch.  The
Python tracer is off.  Writes ``region_trace.xplane.pb.gz`` and the same
trace as the profiler's own Perfetto JSON,
``region_trace.perfetto.json.gz``, which the test reads as an
independent witness.
"""
import glob
import gzip
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "tpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from repro.configs import get_tiny_config                    # noqa: E402
from repro.core.compiler import compile_flow                 # noqa: E402
from repro.core.dataflow import Dataflow                     # noqa: E402
from repro.core.lowering import forced_batched_routing       # noqa: E402
from repro.core.table import Table                           # noqa: E402
from repro.models import build_model                         # noqa: E402
from repro.models.registry import model_stage_op             # noqa: E402
from repro.runtime import NetModel, Runtime                  # noqa: E402

SEQ, CACHE = 16, 32


def main(out: str) -> int:
    cfg = get_tiny_config("yi-9b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    kw = dict(model_name="yi", seq_len=SEQ, cache_len=CACHE, measure=False)
    pre = model_stage_op(model, params, "prefill", **kw)
    dec = model_stage_op(model, params, "decode", **kw)
    rt = Runtime(n_cpu=1, n_gpu=1, net=NetModel(scale=0.0), max_batch=4,
                 batch_wait_ms=20.0)
    fl = Dataflow([("tokens", jax.Array)])
    fl.output = fl.apply_op(pre, gpu=True, batching=True) \
        .apply_op(dec, gpu=True, batching=True) \
        .apply_op(dec, gpu=True, batching=True)
    dep = compile_flow(fl, rt, fusion=True, name="rg")
    (chain,) = [o.op for o in dep.plan.ops]
    (node,) = dep.dag.nodes
    rng = np.random.default_rng(0)

    def send():
        return rt.call_dag("rg", Table([("tokens", jax.Array)], [(
            rng.integers(0, cfg.vocab_size, SEQ).astype(np.int32),)]))

    def wait(futs):
        for f in futs:
            jax.block_until_ready(f.result(60).rows[0].values)

    # about 0.1 s of matmuls on one v5e (a stand-in size elsewhere)
    n = 4096 if jax.devices()[0].platform == "tpu" else 128
    busy = jax.jit(lambda x: jax.lax.fori_loop(
        0, 150, lambda _, y: jnp.tanh(y @ y), x))
    x = jnp.full((n, n), 1e-3, jnp.bfloat16)
    try:
        with forced_batched_routing([chain]):
            wait([send()])
            wait([send() for _ in range(4)])
            busy(x).block_until_ready()
            rt.batcher_for("rg", node).adaptive_wait = False
            tmp = tempfile.mkdtemp()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, create_perfetto_trace=True,
                                     profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.traced"):
                for _ in range(2):
                    wait([send()])
                    with jax.profiler.TraceAnnotation("bench.sleep"):
                        time.sleep(0.005)
                y = busy(x)
                futs = [send()]
                time.sleep(0.025)
                futs.append(send())
                wait(futs)
                y.block_until_ready()
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    time.sleep(0.005)
                wait([send() for _ in range(4)])
            jax.profiler.stop_trace()
    finally:
        rt.stop()
    os.makedirs(out, exist_ok=True)
    xp = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    pf = glob.glob(os.path.join(tmp, "**", "*.json.gz"), recursive=True)
    with open(xp[0], "rb") as src, gzip.open(
            os.path.join(out, "region_trace.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.copy(pf[0], os.path.join(out, "region_trace.perfetto.json.gz"))
    shutil.rmtree(tmp)
    print(os.listdir(out), [os.path.getsize(os.path.join(out, p))
                            for p in os.listdir(out)])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
