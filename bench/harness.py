"""One run of one benchmark cell, driven by its files.

A cell ``<cell>`` is ``workloads/<cell>.json``: the configuration it runs,
its traffic and its runtime settings.  The configuration is
``configs/<config>.json``; its family names the plain reference
``refs/<family>.py`` and the cost model ``costs/<family>.py``.  Each
per-layer metric is read by ``metrics/<metric>.py``.  ``BENCHMARK.json``
at the checkout's root says which metrics a cell reports.

The system under test is the serving program: the configuration's model
from its registry, each stage a ``model_stage_op``, the cascade prefill ->
``decode_steps`` x decode -> a map to (tokens, position) compiled with
``compile_flow(fusion=True)`` into one lowered chain, served by a
``Runtime`` whose one accelerator executor holds the chip.  Requests go
through ``Runtime.call_dag``.

The model the program serves is wrapped to carry one more cache leaf, an
int32 row of the tokens each decode step was fed, which the final map
returns beside the last token.  That is how the check sees every token a
request was served; the model's own computation is unchanged.
"""
import dataclasses
import functools
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import devtrace, stats, traffic
from bench.refs import common as refcommon

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path) -> Any:
    return json.loads(Path(path).read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict
    cfg: dict

    @property
    def prompt_len(self) -> int:
        return self.spec["prompt_len"]

    @property
    def steps(self) -> int:
        return self.spec["decode_steps"]

    @property
    def vocab(self) -> int:
        return self.cfg["vocab_size"]

    @property
    def ref(self):
        return importlib.import_module(f"bench.refs.{self.cfg['family']}")

    @property
    def costs(self):
        return importlib.import_module(f"bench.costs.{self.cfg['family']}")


def load_cell(name: str, *, rehearse: bool = False) -> Cell:
    """The cell's files; ``rehearse`` swaps in their tiny CPU sizes."""
    spec = load_json(BENCH / "workloads" / f"{name}.json")
    cfg = load_json(BENCH / "configs" / f"{spec['config']}.json")
    if rehearse:
        cfg = dict(cfg, **cfg["rehearsal"])
        spec = dict(spec, **spec["rehearsal"])
    return Cell(name, spec, cfg)


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    names = {f.name for f in dataclasses.fields(ModelConfig)}
    mc = ModelConfig(**{k: v for k, v in cfg.items() if k in names})
    mc.validate()
    return mc


def make_weights(cell: Cell, seed: int):
    """Seeded weights, made on the device in one jitted call."""
    fn = jax.jit(functools.partial(cell.ref.make_weights, cell.cfg))
    return jax.block_until_ready(fn(refcommon.key_from_seed(seed)))


class Recorded:
    """The program's model, with one more cache leaf: the tokens its
    decode steps were fed, so the final map can return every served
    token.  The model's own prefill and decode are called unchanged."""

    def __init__(self, model, prompt_len: int, steps: int):
        self.model, self.prompt_len, self.steps = model, prompt_len, steps
        self.cfg = model.cfg

    def init_cache(self, batch, cache_len):
        return {"m": self.model.init_cache(batch, cache_len),
                "served": jnp.zeros((batch, self.steps), jnp.int32)}

    def prefill(self, params, batch, cache_len):
        logits, cache = self.model.prefill(params, batch, cache_len)
        return logits, {"m": cache, "served": jnp.zeros(
            (logits.shape[0], self.steps), jnp.int32)}

    def decode_step(self, params, tokens, pos, cache):
        logits, m = self.model.decode_step(params, tokens, pos, cache["m"])
        slot = (pos - self.prompt_len)[:, None] == jnp.arange(self.steps)
        return logits, {"m": m, "served": jnp.where(slot, tokens,
                                                    cache["served"])}


def projection(n_leaves: int):
    """The flow's last map: (tok, pos, *cache leaves) -> (every served
    token, pos).  The served-token leaf sorts last among the leaves."""
    args = ["tok", "pos"] + [f"c{i}" for i in range(n_leaves)]
    ns = {"jnp": jnp}
    exec(f"def to_tokens({', '.join(args)}):\n"
         f"    return jnp.concatenate([c{n_leaves - 1}, tok[None]]), pos\n",
         ns)
    fn = ns["to_tokens"]
    fn.__annotations__ = dict({a: jax.Array for a in args},
                              **{"return": Tuple[jax.Array, jax.Array]})
    return fn


class System:
    """The deployed serving program for one cell and one seed's weights."""

    def __init__(self, cell: Cell, params, seed: int, tracer=None):
        from repro.core.compiler import compile_flow
        from repro.core.dataflow import Dataflow
        from repro.core.lowering import BatchedJittedFuse
        from repro.models.registry import build_model, model_stage_op
        from repro.runtime import NetModel, Runtime

        self.cell, self.seed = cell, seed
        spec, P, steps = cell.spec, cell.prompt_len, cell.steps
        self.model = build_model(model_config(cell.cfg))
        want = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                           params)
        if got != want:
            raise ValueError(f"{cell.name}: the program's parameter layout "
                             f"is not the reference's:\n{want}\n{got}")
        rec = Recorded(self.model, P, steps)
        kw = dict(model_name=cell.cfg["name"], seq_len=P,
                  cache_len=P + steps, measure=False)
        pre = model_stage_op(rec, params, "prefill", **kw)
        dec = model_stage_op(rec, params, "decode", **kw)
        self.rt = Runtime(net=NetModel(scale=0.0), tracer=tracer,
                          max_batch=spec["max_batch"], **spec["runtime"])
        fl = Dataflow([("tokens", jax.Array)])
        node = fl.apply_op(pre, gpu=True, batching=True)
        for _ in range(steps):
            node = node.apply_op(dec, gpu=True, batching=True)
        fl.output = node.map(projection(len(dec.names) - 2),
                             names=["tokens_out", "pos"], gpu=True,
                             batching=True)
        self.dep = compile_flow(fl, self.rt, fusion=True, name=cell.name)
        ops = [o.op for o in self.dep.plan.ops]
        if len(ops) != 1 or not isinstance(ops[0], BatchedJittedFuse):
            raise ValueError(f"{cell.name}: the flow did not lower to one "
                             f"batched chain:\n{self.dep.explain()}")
        self.chain = ops[0]

    def table(self, idx: int):
        from repro.core.table import Table
        return Table([("tokens", jax.Array)], [(traffic.prompt(
            self.seed, idx, self.cell.prompt_len, self.cell.vocab),)])

    def send(self, idx: int):
        return self.rt.call_dag(self.cell.name, self.table(idx))

    def warm(self) -> dict:
        """Compile or load every program the cell's traffic uses: the
        per-row executable and the batched buckets, at the one prompt
        length; then one live request, so the batcher and executor
        threads exist before the window."""
        from repro.profiling.replan import warm_deployment
        out = warm_deployment(self.rt, self.dep, self.table(-1),
                              buckets=list(self.cell.spec["buckets"]))
        self.send(-2).result(600)
        return out

    def counters(self) -> dict:
        """The chain's public dispatch counters."""
        return {"row_dispatches": self.chain.row_dispatches,
                "batch_dispatches": self.chain.batch_dispatches,
                "rows_batched": self.chain.rows_batched}

    def stop(self) -> None:
        from repro.core.lowering import EXECUTABLE_CACHE
        self.rt.stop()
        EXECUTABLE_CACHE.clear()
        self.rt = self.dep = self.chain = None
        gc.collect()


def counter_delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def batch_rows(spans, t_a: float, t_b: float, c: dict) -> Optional[list]:
    """Rows of each batched dispatch of the chain between ``t_a`` and
    ``t_b``: the sizes of the runtime's ``batch@`` spans of more than one
    row that started then, where they agree with the chain's counters
    over the same time; else, for one batched dispatch or none, what the
    counters say.  None where neither settles it."""
    sizes = [s.attrs["size"] for s in spans
             if t_a <= s.t0 < t_b and s.attrs.get("size", 1) > 1]
    if (len(sizes) == c["batch_dispatches"]
            and sum(sizes) == c["rows_batched"]):
        return sizes
    if c["batch_dispatches"] <= 1:
        return [c["rows_batched"]] if c["batch_dispatches"] else []
    return None


class CompileWatch:
    """Counts JAX tracing and compilation events while ``on``."""

    def __init__(self):
        self.on, self.events = False, []
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **_):
        if self.on and event.startswith("/jax/core/compile/"):
            self.events.append(event)


class Profiler:
    """A ``jax.profiler`` trace of part of the window, on a thread of its
    own: starts ``lead`` seconds into the window, lasts ``seconds``, and
    reads the chain's counters at its two ends."""

    def __init__(self, system: System, lead: float, seconds: float):
        self.system, self.lead, self.seconds = system, lead, seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.counters = None
        self.t_a = self.t_b = None
        self.error = None
        self.thread = None

    def start(self, t0: float) -> None:
        self.thread = threading.Thread(target=self._run, args=(t0,),
                                       daemon=True)
        self.thread.start()

    def _run(self, t0: float) -> None:
        try:
            time.sleep(max(0.0, t0 + self.lead - time.perf_counter()))
            jax.profiler.start_trace(self.dir)
            try:
                with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
                    self.t_a, a = time.perf_counter(), self.system.counters()
                    time.sleep(self.seconds)
                    self.t_b, b = time.perf_counter(), self.system.counters()
            finally:
                jax.profiler.stop_trace()
            self.counters = counter_delta(a, b)
        except Exception as e:                 # reported, never fatal
            self.error = f"{type(e).__name__}: {e}"

    def reduce(self, keep: Optional[str] = None) -> Optional[dict]:
        self.thread.join()
        try:
            path = devtrace.find_xplane(self.dir)
            if self.error or path is None:
                return None
            if keep:
                shutil.copytree(self.dir, keep, dirs_exist_ok=True)
            with jax.profiler.TraceAnnotation("bench.reduce"):
                return devtrace.reduce(devtrace.load(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def least_seconds(cell: Cell, rows: int, peaks: dict) -> float:
    """Least time of one dispatch of ``rows`` rows on the chip: per stage,
    the larger of its operations over peak FLOP/s and its bytes over
    peak bandwidth."""
    return sum(max(f / peaks["bf16_flops_per_s"],
                   b / peaks["hbm_bytes_per_s"])
               for f, b in cell.costs.stages(cell.cfg, rows, cell.prompt_len,
                                             cell.steps))


def request_flops(cell: Cell) -> float:
    return sum(f for f, _ in cell.costs.stages(cell.cfg, 1, cell.prompt_len,
                                               cell.steps))


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def read_metric(name: str, ctx: dict) -> Optional[float]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


# -- the check ---------------------------------------------------------------

def sample(cell: Cell, reqs: List[traffic.Request], seed: int) -> list:
    """The requests the check compares: ``check_requests`` of those done,
    drawn from the seed (every request has the same length).  Those that
    a batched dispatch served come first, up to half of the sample, so
    that a window in which the batcher merged requests always has its
    merge, demux and padding compared."""
    rng = np.random.default_rng([int(seed), 1])
    done = [r for r in reqs if r.done is not None]
    k = min(cell.spec["check_requests"], len(done))
    merged = [r for r in done if r.batched]
    pick = [merged[i] for i in rng.permutation(len(merged))[:k // 2]]
    rest = [r for r in done if all(r is not q for q in pick)]
    pick += [rest[i] for i in rng.permutation(len(rest))[:k - len(pick)]]
    return sorted(pick, key=lambda r: r.idx)


def reference_logits(cell: Cell, params, tokens: np.ndarray, mm: str):
    """float32 reference logits at the served positions, in blocks of
    ``check_block`` rows."""
    fn = jax.jit(functools.partial(cell.ref.logits, cfg=cell.cfg,
                                   mm=refcommon.MATMULS[mm]),
                 static_argnums=2)
    blk = cell.spec["check_block"]
    n = len(tokens)
    pad = -n % blk
    toks = np.concatenate([tokens, tokens[:1].repeat(pad, 0)]) if pad \
        else tokens
    out = []
    for i in range(0, len(toks), blk):
        lg = fn(params, jnp.asarray(toks[i:i + blk]), cell.prompt_len - 1)
        out.append(np.asarray(lg, np.float32))
    return np.concatenate(out)[:n]


def logit_gaps(ref: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best, per row and position."""
    best = ref.max(-1)
    return best - np.take_along_axis(ref, chosen[..., None], -1)[..., 0]


def check(cell: Cell, params, reqs: List[traffic.Request], seed: int,
          control: bool = False) -> dict:
    """Readings of the check: malformed outputs among those sampled, and
    the widest gap of a served token below the reference's best; with
    ``control`` also the widest gap of the token the fp8 reference puts
    first, at the same positions."""
    picked = sample(cell, reqs, seed)
    P, steps, V = cell.prompt_len, cell.steps, cell.vocab
    malformed = 0
    served = []
    for r in picked:
        toks, pos = np.asarray(r.out[0]), int(np.asarray(r.out[1]))
        ok = (toks.shape == (steps + 1,) and pos == P + steps
              and bool(np.all((toks >= 0) & (toks < V))))
        malformed += not ok
        served.append(np.clip(toks.reshape(-1)[:steps + 1], 0, V - 1)
                      if toks.size >= steps + 1
                      else np.zeros(steps + 1, np.int32))
    out = {"sampled": len(picked), "malformed": malformed,
           "sampled_batched": sum(r.batched for r in picked),
           "served_tokens": len(picked) * (steps + 1)}
    if not picked:
        return out
    served = np.stack(served).astype(np.int32)
    prompts = np.stack([traffic.prompt(seed, r.idx, P, V) for r in picked])
    tokens = np.concatenate([prompts, served[:, :steps]], 1)
    with jax.profiler.TraceAnnotation("bench.check"):
        ref = reference_logits(cell, params, tokens, "exact")
        out["logit_gap"] = float(logit_gaps(ref, served).max())
        if control:
            low = reference_logits(cell, params, tokens, "fp8")
            out["control_logit_gap"] = float(
                logit_gaps(ref, low.argmax(-1)).max())
    return out


def checks_of(cell: Cell, reqs: List[traffic.Request], readings: dict,
              gap: Optional[float]) -> dict:
    """Each number the check compares, beside its limit."""
    return {
        "failed_requests": {"value": sum(r.done is None for r in reqs),
                            "limit": 0},
        "malformed_outputs": {"value": readings["malformed"], "limit": 0},
        "checked_requests": {"value": readings["sampled"],
                             "limit": min(cell.spec["check_requests"],
                                          len(reqs))},
        "max_logit_gap": {"value": gap,
                          "limit": cell.spec["check"]["max_logit_gap"]},
    }


def verdict(checks: dict) -> bool:
    """``correct``: nothing failed or malformed, the whole sample checked,
    and the widest gap within its limit."""
    c = checks
    return bool(c["failed_requests"]["value"] <= c["failed_requests"]["limit"]
                and c["malformed_outputs"]["value"]
                <= c["malformed_outputs"]["limit"]
                and c["checked_requests"]["value"]
                >= c["checked_requests"]["limit"]
                and c["max_logit_gap"]["value"] is not None
                and c["max_logit_gap"]["value"]
                <= c["max_logit_gap"]["limit"])


# -- one run -----------------------------------------------------------------

@dataclasses.dataclass
class RunResult:
    line: dict
    readings: dict


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, device: dict, control: bool = False,
        keep_trace: Optional[str] = None,
        rehearse: bool = False) -> RunResult:
    """Set up, measure for ``seconds``, check; returns the result line."""
    from repro.core.lowering import EXECUTABLE_CACHE
    from repro.obs import EVENTS
    from repro.obs.trace import Tracer

    spec = cell.spec
    watch = CompileWatch()
    params = make_weights(cell, seed)
    say(f"weights: {sum(a.nbytes for a in jax.tree.leaves(params)) / 1e9:.3f}"
        f" GB made in {time.perf_counter() - t_start:.1f} s since start")
    tracer = (Tracer(enabled=True, sample_rate=1.0, capacity=1 << 16)
              if trace else None)
    system = System(cell, params, seed, tracer=tracer)
    warm = system.warm()
    say(f"warm: buckets {warm['buckets']}, traces {warm['traces_before']} "
        f"-> {warm['traces_after']}")
    if tracer is not None:
        tracer.clear()
    prof = (Profiler(system, spec["trace_lead_s"], spec["trace_s"])
            if trace and not rehearse else None)

    traces_before = EXECUTABLE_CACHE.traces()
    c_before = system.counters()
    watch.on = True
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    if prof is not None:
        prof.start(t0)
    reqs = traffic.open_loop(system.send, spec["rate_per_s"], seconds,
                             spec["arrival_seed"], t0)
    watch.on = False
    c_window = counter_delta(c_before, system.counters())
    traces_after = EXECUTABLE_CACHE.traces()
    latched = EVENTS.snapshot("lowering/")
    router = system.chain.profile().snapshot()
    devs = jax.devices()
    stats_mem = devs[0].memory_stats() or {}
    memory_peak = int(stats_mem.get("peak_bytes_in_use", 0))

    failed = [r for r in reqs if r.done is None]
    say(f"window: {len(reqs)} requests, {len(failed)} failed; compiles in "
        f"window: executable cache traces {traces_before} -> "
        f"{traces_after}, jax compile events {len(watch.events)}")
    say(f"window counters: {json.dumps(c_window)}")
    say(f"router: {json.dumps(router, default=str)}")
    if latched:
        say(f"lowering fallbacks latched: {latched}")

    give_up = t0 + seconds + traffic.WAIT_AFTER_S
    lat = [((r.done if r.done is not None else give_up) - r.due) * 1e3
           for r in reqs]
    lag = [(r.sent - r.due) * 1e3 for r in reqs if r.sent]
    e2e = {"setup_s": setup_s, "latency_p50_ms": stats.percentile(lat, 50),
           "latency_p95_ms": stats.percentile(lat, 95)}
    say(f"generator lag ms: p50 {stats.percentile(lag, 50):.3f} p95 "
        f"{stats.percentile(lag, 95):.3f} max {max(lag):.3f} "
        f"({len(lag)} sends)")

    red = None
    if trace:
        red = prof.reduce(keep_trace) if prof is not None else None
        if prof is not None and prof.error:
            say(f"profiler: {prof.error}")
        if red is not None:
            say(f"trace: busy {red['busy_s']:.6f} s of {red['window_s']:.6f}"
                f" s; programs {json.dumps(red['programs'])}")
        got = prof is not None and prof.counters is not None
        ctx = {"cell": cell, "traces": tracer.kept(cell.name),
               "counters": prof.counters if got else None,
               "batch_rows": batch_rows(tracer.batch_spans(), prof.t_a,
                                        prof.t_b, prof.counters)
               if got else None,
               "window_counters": c_window, "trace": red,
               "peaks": device.get("peaks"),
               "least_seconds": functools.partial(least_seconds, cell),
               "request_flops": request_flops(cell)}
    metrics = {}
    for m in benchmark()["per_layer" if trace else "end_to_end"]:
        if applies(m, cell.name):
            v = read_metric(m["name"], ctx) if trace else e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    system.stop()
    readings = check(cell, params, reqs, seed, control=control)
    del params
    checks = checks_of(cell, reqs, readings, readings.get("logit_gap"))
    correct = verdict(checks)
    if control:
        readings["control_correct"] = verdict(checks_of(
            cell, reqs, readings, readings.get("control_logit_gap")))
    dev = {k: device[k] for k in ("platform", "kind", "count")}
    dev["memory_peak_bytes"] = memory_peak
    line = {"correct": correct, "attempted": len(reqs),
            "failed": len(failed), "metrics": metrics, "device": dev}
    if red is not None:
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    line["checks"] = checks
    say(f"check sample: {readings.get('sampled_batched', 0)} of "
        f"{readings['sampled']} requests served by a batched dispatch")
    for k, v in checks.items():
        say(f"check {k}: {v['value']} (limit {v['limit']})")
    return RunResult(line, dict(readings, e2e=e2e, window=c_window))
