"""Serving launcher: stand up a Cloudflow pipeline over a zoo model and run
batched requests through the serverless runtime (tiny config on CPU).

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --requests 16
"""
from __future__ import annotations

import argparse
import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_tiny_config, ARCH_IDS
from repro.core.dataflow import Dataflow
from repro.core.table import Table
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.netmodel import NetModel
from repro.runtime.runtime import Runtime
from repro.serving.engine import make_engine


def build_flow(arch: str, *, max_new_tokens: int = 8,
               batching: bool = True) -> Tuple[Dataflow, object]:
    cfg = get_tiny_config(arch)
    engine = make_engine(cfg, cache_len=128)
    params = engine.model.init(jax.random.PRNGKey(0))

    def tokenize(text: str) -> np.ndarray:
        toks = np.frombuffer(text.encode()[:16].ljust(16), np.uint8)
        return toks.astype(np.int32) % cfg.vocab_size

    def generate(tokens: np.ndarray) -> np.ndarray:
        batch = {"tokens": jnp.asarray(tokens)[None]}
        if cfg.family == "vlm":
            batch["media"] = jnp.zeros((1, cfg.num_media_tokens, cfg.d_model),
                                       jnp.dtype(cfg.dtype))
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros((1, cfg.encoder_seq, cfg.d_model),
                                        jnp.dtype(cfg.dtype))
        return engine.generate(params, batch, max_new_tokens)[0]

    def detok(out: np.ndarray) -> str:
        return " ".join(str(int(t)) for t in out)

    flow = Dataflow([("text", str)])
    toks = flow.map(tokenize, names=["tokens"])
    gen = toks.map(generate, names=["out"], gpu=False, batching=batching)
    flow.output = gen.map(detok, names=["completion"])
    return flow, engine


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="yi-9b", choices=list(ARCH_IDS))
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--new-tokens", type=int, default=8)
    args = p.parse_args()
    enable_compile_cache()
    flow, _ = build_flow(args.arch, max_new_tokens=args.new_tokens)
    rt = Runtime(n_cpu=2, net=NetModel(scale=0.0))
    flow.deploy(rt, fusion=True)
    t0 = time.time()
    futs = [flow.execute(Table([("text", str)], [(f"request {i}",)]))
            for i in range(args.requests)]
    for i, f in enumerate(futs):
        r = f.result(timeout=120)
        print(f"req {i}: {r.to_dicts()[0]['completion']}")
    dt = time.time() - t0
    print(f"{args.requests} requests in {dt:.2f}s "
          f"({args.requests / dt:.1f} req/s)")
    rt.stop()


if __name__ == "__main__":
    main()
