"""Compile rehearsals for one TPU v5e chip, run without the chip.

The TPU compiler is installed with jaxlib and compiles for a *described*
``v5e:2x2`` topology: nothing runs, but the compiler refuses what the
Pallas interpreter accepts (blocks not aligned to the (8, 128) tiling,
too much VMEM) and ``memory_analysis()`` sizes each program against the
chip's 16 GB of HBM.  Kernels are compiled with ``interpret=False``
passed explicitly: on this backend ``interpret=None`` resolves to True.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every pytest worker imports this
file.  The persistent compilation cache is off around these compiles (an
entry written for a described chip cannot be read back without one).
"""
import dataclasses

import jax
import jax.extend
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.rwkv6_1b6 import CONFIG as RWKV6_1B6
from repro.configs.yi_9b import CONFIG as YI_9B
from repro.core.lowering import chain_consts, compose_steps
from repro.kernels import ops as kops
from repro.models import build_model, rwkv6
from repro.models.registry import model_stage_op
from repro.obs import EVENTS, keys as okeys

HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:           # noqa: BLE001 - any cause skips
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), tree)


# the widths the chip smoke serves: yi-9b attention (32 heads, 4 kv heads,
# head dim 128), rwkv6-1.6b WKV (32 heads of 64), RG-LRU width 2560
_ROWS = 4
_KERNELS = {
    "flash_attention": lambda q, k, v: kops.flash_attention(
        q, k, v, causal=True, interpret=False),
    "decode_attention": lambda q, kc, vc, kp, qp: kops.decode_attention(
        q, kc, vc, kp, qp, interpret=False),
    "wkv6": lambda r, k, v, w, u: kops.wkv6(r, k, v, w, u, interpret=False),
    "rglru_scan": lambda a, x: kops.rglru_scan(a, x, interpret=False),
}


def _kernel_args(name, dt):
    B, H, K, hd = _ROWS, 32, 4, 128
    i32 = jnp.int32
    return {
        "flash_attention": [((B, H, 512, hd), dt), ((B, K, 512, hd), dt),
                            ((B, K, 512, hd), dt)],
        "decode_attention": [((B, H, hd), dt), ((B, K, 1024, hd), dt),
                             ((B, K, 1024, hd), dt), ((B, 1024), i32),
                             ((B,), i32)],
        "wkv6": [((B, 256, 32, 64), dt)] * 4 + [((32, 64), dt)],
        "rglru_scan": [((B, 256, 2560), dt)] * 2,
    }[name]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(kops.KERNEL_REGISTRY))
def test_registry_kernel_compiles_for_v5e(one_chip, name, dtype):
    assert set(_KERNELS) == set(kops.KERNEL_REGISTRY)
    args = [_spec(one_chip, s, d) for s, d in _kernel_args(name, dtype)]
    compiled = jax.jit(_KERNELS[name]).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "not a Mosaic kernel"
    assert _bytes(compiled) < HBM_BYTES


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _yi(layers: int):
    model = build_model(dataclasses.replace(YI_9B, num_layers=layers))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, params


@pytest.mark.parametrize("layers", [2, 16])
def test_yi_9b_decode_step_fits_one_v5e(one_chip, layers):
    """One decode step at published widths (batch 8, cache 1024); 16
    layers is the depth the chip smoke serves."""
    model, params = _yi(layers)
    cache = jax.eval_shape(lambda: model.init_cache(8, 1024))
    compiled = jax.jit(model.decode_step, donate_argnums=(3,)).lower(
        _on(one_chip, params), _spec(one_chip, (8, 1), jnp.int32),
        _spec(one_chip, (8,), jnp.int32), _on(one_chip, cache)).compile()
    used = _bytes(compiled)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB > 16 GB"


@pytest.mark.parametrize("stages", [("logits",), ("prefill",) + ("decode",) * 8])
def test_yi_9b_served_chain_fits_one_v5e(one_chip, stages):
    """The lowered chains the chip smoke serves, built as a batched chain
    executable is (``compose_steps`` under ``vmap``): 16 layers, 4 prompts
    of 512 tokens, a 1024-slot cache.  Stages that share the weights must
    share one argument: passed once per decode step, eight copies of a
    6 GB model do not fit."""
    model, params = _yi(16)
    params = _on(one_chip, params)
    ops = [model_stage_op(model, params, stage, seq_len=512, cache_len=1024,
                          measure=False) for stage in stages]
    steps = [o.fn for o in ops]
    consts = chain_consts(steps)
    assert len(consts) == 1
    chain = jax.vmap(compose_steps(steps, masked_input=False,
                                   with_keep=False), in_axes=(None, 0))
    compiled = jax.jit(chain).lower(
        consts, _spec(one_chip, (4, 512), jnp.int32)).compile()
    used = _bytes(compiled)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB > 16 GB"


def _scan_lengths(jaxpr) -> set:
    """The lengths of every ``lax.scan`` in ``jaxpr`` and its sub-jaxprs."""
    found = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.add(eqn.params["length"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    found |= _scan_lengths(sub)
    return found


def test_rwkv6_served_chain_fits_one_v5e(one_chip):
    """The chain the rwkv6-1.6b cell serves, at published widths: prefill
    of 256 tokens, then 16 decode steps, for 8 rows (its largest bucket).
    The prompt's WKV takes the chunked form, with no loop over its 256
    timesteps, and the program fits one chip."""
    model = build_model(RWKV6_1B6)
    params = _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    chunked = okeys.WKV_CHUNKED
    traced = EVENTS.snapshot(chunked).get(chunked, 0)
    ops = [model_stage_op(model, params, stage, seq_len=256, cache_len=272,
                          measure=False)
           for stage in ("prefill",) + ("decode",) * 16]
    steps = [o.fn for o in ops]
    consts = chain_consts(steps)
    assert len(consts) == 1
    chain = jax.vmap(compose_steps(steps, masked_input=False,
                                   with_keep=False), in_axes=(None, 0))
    lowered = jax.jit(chain).trace(
        consts, _spec(one_chip, (8, 256), jnp.int32))
    assert EVENTS.snapshot(chunked)[chunked] > traced
    lengths = _scan_lengths(lowered.jaxpr.jaxpr)
    assert 256 // rwkv6.CHUNK in lengths and 256 not in lengths, lengths
    used = _bytes(lowered.lower().compile())
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB > 16 GB"
