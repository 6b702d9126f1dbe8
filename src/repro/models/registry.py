"""Uniform model facade over the zoo families.

``build_model(cfg, ax)`` returns a ``Model`` with:

* ``init(key)``                          -> params pytree
* ``logits(params, batch)``              -> [B, S, V] (train forward)
* ``loss(params, batch)``                -> (scalar, metrics)
* ``prefill(params, batch, cache_len)``  -> (logits, cache)
* ``decode_step(params, tokens, pos, cache, media?)`` -> (logits, cache)
* ``init_cache(batch, cache_len)``
* ``input_specs(shape)``                 -> ShapeDtypeStructs for the dry-run

``batch`` is a dict: {"tokens", "labels"?, "media"? (vlm stub patch
embeddings), "frames"? (audio stub frame embeddings)}.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from repro.models import transformer, rwkv6, rglru, whisper
from repro.models.partition import AxisInfo, shard, dp_axes, mp_axis

_FAMILY_MODULES = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "ssm": rwkv6,
    "hybrid": rglru,
    "audio": whisper,
}


def cross_entropy(logits, labels, *, ignore_id: int = -1):
    """logits: [B, S, V] (f32); labels: [B, S] int32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    mask = (labels != ignore_id).astype(jnp.float32)
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    ax: Optional[AxisInfo] = None
    long_context: bool = False
    moe_dispatch: str = "all_to_all"

    @property
    def mod(self):
        return _FAMILY_MODULES[self.cfg.family]

    # -- params ------------------------------------------------------------
    def init(self, key):
        return self.mod.init_params(key, self.cfg, self.ax,
                                    long_context=self.long_context)

    # -- forward / loss ------------------------------------------------------
    def _fwd_kwargs(self, batch, remat):
        kw: Dict[str, Any] = {"remat": remat}
        if self.cfg.family in ("vlm", "moe"):
            kw["moe_dispatch"] = self.moe_dispatch
        if self.cfg.family == "vlm":
            kw["media"] = batch.get("media")
        if self.cfg.family == "audio":
            kw["frames"] = batch.get("frames")
        if self.cfg.family in ("dense", "moe", "vlm"):
            kw["long_context"] = self.long_context
        return kw

    def logits(self, params, batch, *, remat: bool = True):
        out, aux = self.mod.forward(params, batch["tokens"], self.cfg,
                                    self.ax, **self._fwd_kwargs(batch, remat))
        return out, aux

    def loss(self, params, batch, *, remat: bool = True):
        logits, aux = self.logits(params, batch, remat=remat)
        labels = batch.get("labels")
        if labels is None:
            labels = jnp.concatenate(
                [batch["tokens"][:, 1:],
                 jnp.full_like(batch["tokens"][:, :1], -1)], axis=1)
        ce = cross_entropy(logits, labels)
        total = ce + self.cfg.router_aux_loss_coef * aux
        return total, {"ce": ce, "aux": aux}

    # -- serving -------------------------------------------------------------
    def prefill(self, params, batch, cache_len: int):
        out = self.mod.forward(params, batch["tokens"], self.cfg, self.ax,
                               build_cache=True, cache_len=cache_len,
                               **self._fwd_kwargs(batch, remat=False))
        logits, cache, _aux = out
        return logits[:, -1:], cache

    def init_cache(self, batch: int, cache_len: int):
        return self.mod.init_cache(
            self.cfg, self.ax, batch, cache_len,
            long_context=self.long_context)

    def cache_pspecs(self):
        return self.mod.cache_pspecs(self.cfg, self.ax,
                                     long_context=self.long_context)

    def decode_step(self, params, tokens, pos, cache):
        kw = {}
        if self.cfg.family in ("moe",):
            kw["moe_dispatch"] = self.moe_dispatch
        if self.cfg.family in ("dense", "moe", "vlm"):
            kw["long_context"] = self.long_context
        return self.mod.decode_step(params, tokens, pos, cache, self.cfg,
                                    self.ax, **kw)

    # -- dry-run specs ---------------------------------------------------------
    def input_specs(self, shape: InputShape) -> Dict[str, Any]:
        """ShapeDtypeStructs for every model input of the given shape."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        dt = jnp.dtype(cfg.dtype)
        if shape.kind == "train":
            specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                     "labels": jax.ShapeDtypeStruct((B, S), i32)}
            if cfg.family == "vlm":
                specs["media"] = jax.ShapeDtypeStruct(
                    (B, cfg.num_media_tokens, cfg.d_model), dt)
            if cfg.family == "audio":
                specs["frames"] = jax.ShapeDtypeStruct(
                    (B, cfg.encoder_seq, cfg.d_model), dt)
            return specs
        if shape.kind == "prefill":
            specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
            if cfg.family == "vlm":
                specs["media"] = jax.ShapeDtypeStruct(
                    (B, cfg.num_media_tokens, cfg.d_model), dt)
            if cfg.family == "audio":
                specs["frames"] = jax.ShapeDtypeStruct(
                    (B, cfg.encoder_seq, cfg.d_model), dt)
            return specs
        # decode: one token + cache of length S
        cache = jax.eval_shape(lambda: self.init_cache(B, S))
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
                "pos": jax.ShapeDtypeStruct((B,), i32),
                "cache": cache}


def build_model(cfg: ModelConfig, ax: Optional[AxisInfo] = None, *,
                long_context: bool = False,
                moe_dispatch: str = "all_to_all") -> Model:
    if cfg.family not in _FAMILY_MODULES:
        raise ValueError(f"unknown family {cfg.family}")
    return Model(cfg=cfg, ax=ax, long_context=long_context,
                 moe_dispatch=moe_dispatch)


# ---------------------------------------------------------------------------
# plan-operator glue: model stages as first-class dataflow ops (ModelOp)
# ---------------------------------------------------------------------------
#
# ``model_stage_op(model, params, stage)`` wraps one serving stage of a
# built model as a ``repro.core.operators.ModelOp`` — a map step with
# declared ``jax.Array`` annotations (so it typechecks, fuses, and lowers
# into Jitted/BatchedJittedFuse chains) and *native batch semantics*: the
# step is row-wise for the dataflow, but a ``custom_vmap`` rule maps the
# lowered chain's row axis straight onto the model's leading batch
# dimension, so a whole batch runs through the model in ONE dispatch.
#
# Row-wise column contracts (per table row):
#
# * ``logits``  — tokens [S] i32              -> next-token logits [V]
# * ``prefill`` — tokens [S] i32              -> (tok [] i32, pos [] i32,
#                                                 *cache leaves)
# * ``decode``  — (tok, pos, *cache leaves)   -> same shape: one greedy
#                                                 decode step advances them
#
# The KV cache rides the table as per-row columns (one per pytree leaf),
# so prefill -> decode -> decode chains fuse into a single device-resident
# chain with no host round-trip between steps.
#
# The weights are not closed over: the step carries them as
# ``__consts__`` beside ``__pure__(params, *cols)``, and a lowered chain
# passes them to its executable as an argument
# (``repro.core.lowering.chain_consts``).  Closed over, a full-width
# model's gigabytes would be lowered as constants of every program.

def _stage_fn(fname: str, argnames, inner, ret_arity: int):
    """Explicit-positional-arg wrapper (``fn_signature`` reads
    ``__code__``) with jax.Array annotations, delegating to ``inner``."""
    fname = "".join(c if c.isalnum() or c == "_" else "_" for c in fname)
    if not fname or fname[0].isdigit():
        fname = f"m_{fname}"
    src = (f"def {fname}({', '.join(argnames)}):\n"
           f"    return _inner({', '.join(argnames)})")
    ns: Dict[str, Any] = {"_inner": inner}
    exec(src, ns)                                        # noqa: S102
    f = ns[fname]
    ann: Dict[str, Any] = {a: jax.Array for a in argnames}
    if ret_arity == 1:
        ann["return"] = jax.Array
    else:
        from typing import Tuple
        ann["return"] = Tuple[tuple([jax.Array] * ret_arity)]
    f.__annotations__ = ann
    return f


def _named_scope(stage: str, fn):
    """``fn`` with its body under ``jax.named_scope(stage)``: the stage
    names the device operations it lowers to (op metadata only, so the
    compiled program and its compile-cache key are unchanged)."""
    @functools.wraps(fn)
    def scoped(*args):
        with jax.named_scope(stage):
            return fn(*args)
    return scoped


def _rowwise_native_batch(batched, multi: bool):
    """Row-wise view of a natively-batched stage fn ``batched(params,
    *cols)``: untransformed calls run the stage with B=1; under
    ``jax.vmap`` (a batched-lowered chain, params unbatched) the rule
    feeds the whole row batch to the stage in one call."""

    @jax.custom_batching.custom_vmap
    def per_row(params, *cols):
        out = batched(params, *[c[None] for c in cols])
        return tuple(o[0] for o in out) if multi else out[0]

    @per_row.def_vmap
    def _rule(axis_size, in_batched, params, *cols):
        if any(jax.tree_util.tree_leaves(in_batched[0])):
            raise ValueError("model weights cannot be batched")
        cols = [c if b
                else jnp.broadcast_to(c[None], (axis_size,) + c.shape)
                for c, b in zip(cols, in_batched[1:])]
        out = batched(params, *cols)
        return (out, tuple(True for _ in out)) if multi else (out, True)

    return per_row


def _timing_hook(batched, params, arg_maker, *, runs: int = 3,
                 warmup: int = 1):
    """Per-bucket cost hook: measure the jitted natively-batched stage at
    batch size ``b``.  Feeds ``profiling.profiler.seed_from_model_ops`` ->
    ``OpLatencyCurve`` buckets."""
    import statistics
    import time

    jitted = functools.partial(jax.jit(batched), params)

    def hook(b: int) -> Dict[str, Any]:
        args = arg_maker(b)
        out = None
        for _ in range(warmup):
            out = jax.block_until_ready(jitted(*args))
        ts = []
        for _ in range(runs):
            t0 = time.perf_counter()
            out = jax.block_until_ready(jitted(*args))
            ts.append(time.perf_counter() - t0)
        mean = sum(ts) / len(ts)
        cv = (statistics.stdev(ts) / mean) if len(ts) > 1 and mean > 0 \
            else 0.0
        leaves = jax.tree_util.tree_leaves(out)
        ob = int(sum(x.size * x.dtype.itemsize for x in leaves))
        return {"mean_s": mean, "p99_s": max(ts), "cv": cv,
                "runs": len(ts), "out_bytes": ob}

    return hook


def model_stage_op(model: Model, params, stage: str, *,
                   model_name: str = "model", seq_len: int = 32,
                   cache_len: int = 64, measure: bool = True,
                   runs: int = 3):
    """Build a ``ModelOp`` for one serving stage of ``model`` (see module
    comment for the row-wise column contracts).  ``seq_len``/``cache_len``
    fix the token/cache geometry (the cost hook measures at exactly these
    shapes; the op itself serves any row shape the flow feeds it).
    ``measure=False`` skips attaching the timing cost hook."""
    from repro.core import operators as ops

    i32 = jnp.int32
    cache_shape = jax.eval_shape(lambda: model.init_cache(1, cache_len))
    leaves_shape, treedef = jax.tree_util.tree_flatten(cache_shape)
    n_leaves = len(leaves_shape)
    state_names = ["tok", "pos"] + [f"c{i}" for i in range(n_leaves)]

    # Cache leaves are NOT batch-leading in general (a lax.scan over layers
    # stacks the layer axis first), so find each leaf's batch axis by
    # diffing shapes at B=1 vs B=2 and normalize: as table columns, cache
    # leaves are always batch-leading; ``_join``/``_split`` transpose at
    # the model boundary.
    leaves_b2, _ = jax.tree_util.tree_flatten(
        jax.eval_shape(lambda: model.init_cache(2, cache_len)))
    batch_axes = []
    for a, b in zip(leaves_shape, leaves_b2):
        diff = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                if x != y]
        if len(diff) != 1:
            raise ValueError(
                f"cannot identify batch axis of cache leaf {a.shape} "
                f"vs {b.shape}")
        batch_axes.append(diff[0])

    def _split(cache):
        """native cache -> batch-leading leaf columns"""
        return [jnp.moveaxis(l, ax, 0) for l, ax in
                zip(jax.tree_util.tree_leaves(cache), batch_axes)]

    def _join(leaves):
        """batch-leading leaf columns -> native cache"""
        return jax.tree_util.tree_unflatten(
            treedef, [jnp.moveaxis(l, 0, ax)
                      for l, ax in zip(leaves, batch_axes)])

    if stage == "logits":
        def batched(params, tokens):
            out, _ = model.logits(params, {"tokens": tokens}, remat=False)
            return out[:, -1]

        argnames, names = ("tokens",), ["logits"]

        def arg_maker(b):
            return (jnp.zeros((b, seq_len), i32),)

    elif stage == "prefill":
        def batched(params, tokens):
            logits, cache = model.prefill(params, {"tokens": tokens},
                                          cache_len)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(i32)
            pos = jnp.full(tokens.shape[:1], tokens.shape[1], i32)
            return (tok, pos, *_split(cache))

        argnames, names = ("tokens",), list(state_names)

        def arg_maker(b):
            return (jnp.zeros((b, seq_len), i32),)

    elif stage == "decode":
        def batched(params, tok, pos, *leaves):
            cache = _join(leaves)
            logits, new_cache = model.decode_step(params, tok[:, None],
                                                  pos, cache)
            ntok = jnp.argmax(logits[:, -1], axis=-1).astype(i32)
            return (ntok, pos + 1, *_split(new_cache))

        argnames, names = tuple(state_names), list(state_names)

        def arg_maker(b):
            cache = model.init_cache(b, cache_len)
            return (jnp.zeros((b,), i32), jnp.zeros((b,), i32),
                    *_split(cache))

    else:
        raise ValueError(f"unknown stage {stage!r} "
                         "(logits | prefill | decode)")

    multi = stage != "logits"
    pure = _rowwise_native_batch(_named_scope(stage, batched), multi=multi)
    fn = _stage_fn(f"{model_name}_{stage}", argnames,
                   functools.partial(pure, params), len(names))
    fn.__pure__, fn.__consts__ = pure, params
    hook = (_timing_hook(batched, params, arg_maker, runs=runs)
            if measure else None)
    return ops.ModelOp(fn=fn, names=names, model_name=model_name,
                       stage=stage, cost_hook=hook)


def stage_input_specs(model: Model, stage: str, *, seq_len: int = 32,
                      cache_len: int = 64) -> Dict[str, Any]:
    """Row-level input column specs for one serving stage — the
    ``input_specs`` the static verifier (``repro.analysis``) wants for a
    flow feeding this stage's op, at the same ``seq_len``/``cache_len``
    geometry ``model_stage_op`` was built with.  ``logits``/``prefill``
    consume a token column; ``decode`` consumes the normalized
    (batch-leading) cache-state columns ``tok``/``pos``/``c{i}``."""
    i32 = jnp.int32
    if stage in ("logits", "prefill"):
        return {"tokens": jax.ShapeDtypeStruct((seq_len,), i32)}
    if stage != "decode":
        raise ValueError(f"unknown stage {stage!r} "
                         "(logits | prefill | decode)")
    leaves, _ = jax.tree_util.tree_flatten(
        jax.eval_shape(lambda: model.init_cache(1, cache_len)))
    leaves_b2, _ = jax.tree_util.tree_flatten(
        jax.eval_shape(lambda: model.init_cache(2, cache_len)))
    specs: Dict[str, Any] = {"tok": jax.ShapeDtypeStruct((), i32),
                             "pos": jax.ShapeDtypeStruct((), i32)}
    for i, (a, b) in enumerate(zip(leaves, leaves_b2)):
        diff = [j for j, (x, y) in enumerate(zip(a.shape, b.shape))
                if x != y]
        if len(diff) != 1:
            raise ValueError(
                f"cannot identify batch axis of cache leaf {a.shape} "
                f"vs {b.shape}")
        row = tuple(s for j, s in enumerate(a.shape) if j != diff[0])
        specs[f"c{i}"] = jax.ShapeDtypeStruct(row, a.dtype)
    return specs
