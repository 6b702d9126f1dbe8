"""Check the chunked WKV recurrence against the one-timestep scan on the
device JAX finds, at rwkv6-1.6b's head widths (32 heads of 64).

    PYTHONPATH=src python3 scripts/wkv_check.py [--chunks 16 32 64]

For each prompt length, row count and chunk size it compiles both forms of
``repro.models.rwkv6`` alone, prints the widest gap between them (in y and
in the final state, as a share of the scan's largest value) and exits 1 if
any gap is above ``TOL``.  The chunk defaults to the module's ``CHUNK``, the
one the model serves.  The same comparison found a compiler fault: on one
TPU v5e (jax 0.9.0) chunk 32 read gaps of 1.67-1.75 at 256 timesteps and 4
or 8 rows, while chunks 16 and 64 read right at every shape (PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp

from repro.models import rwkv6

TOL = 1e-4


def _inputs(B, T, H, hd, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) * 0.3
               for i in range(3))
    u = jax.random.normal(ks[3], (H, hd)) * 0.5
    logw = -jnp.exp(jax.random.uniform(ks[4], (B, T, H, hd),
                                       minval=-3.0, maxval=1.5))
    S0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.1
    return r, k, v, logw, u, S0


def _gap(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def check(chunks=(rwkv6.CHUNK,), lengths=(64, 256), rows=(1, 2, 3, 4, 8),
          heads=32, head_dim=64) -> dict:
    """``{"T<t>_B<b>_C<c>": gap}`` for every shape and chunk."""
    scan = jax.jit(lambda r, k, v, lw, u, S: rwkv6.wkv_scan(
        r, k, v, jnp.exp(lw), u, S))
    chunked = jax.jit(rwkv6.wkv_chunked, static_argnames="chunk")
    gaps = {}
    for T in lengths:
        for B in rows:
            args = _inputs(B, T, heads, head_dim, seed=T + B)
            y0, s0 = scan(*args)
            for C in chunks:
                y, s = chunked(*args, chunk=C)
                gaps[f"T{T}_B{B}_C{C}"] = max(_gap(y, y0), _gap(s, s0))
    return gaps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chunks", type=int, nargs="+", default=[rwkv6.CHUNK])
    a = ap.parse_args(argv)
    gaps = check(tuple(a.chunks))
    bad = {key: g for key, g in gaps.items() if not g <= TOL}
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "gaps": gaps, "above_tol": bad}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
