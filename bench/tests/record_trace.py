#!/usr/bin/env python3
"""Record the small chip trace that ``test_devtrace.py`` reads.

    python3 bench/tests/record_trace.py <out dir>

Six dispatches of a jitted matmul chain with sleeps between them, inside
a ``bench.traced`` span, with ``bench.send`` and ``bench.sleep`` host
spans.  Writes ``chip_trace.xplane.pb`` and the same trace as the
profiler's own Perfetto JSON, ``chip_trace.perfetto.json.gz``, which the
test reads as an independent witness.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "tpu")
import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402


def main(out: str) -> int:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, create_perfetto_trace=True)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(6):
            with jax.profiler.TraceAnnotation("bench.send"):
                y = f(x)
            y.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    os.makedirs(out, exist_ok=True)
    xp = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    pf = glob.glob(os.path.join(tmp, "**", "*.json.gz"), recursive=True)
    shutil.copy(xp[0], os.path.join(out, "chip_trace.xplane.pb"))
    shutil.copy(pf[0], os.path.join(out, "chip_trace.perfetto.json.gz"))
    shutil.rmtree(tmp)
    print(os.listdir(out), [os.path.getsize(os.path.join(out, p))
                            for p in os.listdir(out)])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
