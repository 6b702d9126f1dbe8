"""XLA-level lowering of fused JAX chains (tentpole of the compilation
pipeline).

Graph-level fusion (``FuseChainsPass``) collapses a linear chain into one
``Fuse`` node, but that node still *interprets* its sub-operators one Python
call at a time — per-row, per-op dispatch plus runtime typechecks.  When the
chain is entirely JAX-array ``Map``/``Filter`` operators placed on a
GPU-class executor, we can do better: compose the per-op functions into one
program and hand the whole thing to ``jax.jit``, so XLA fuses the
arithmetic across operator boundaries and the runtime pays a single
dispatch per row.

``JittedFuse`` keeps the exact ``Fuse`` interface (schema/grouping
propagation, ``ops`` list) so every graph-level invariant still holds; only
``apply`` changes.  ``jax.jit`` compiles lazily on first call and re-uses
the executable across rows and requests (shapes are stable in a serving
pipeline, which is what makes this profitable).

``BatchedJittedFuse`` goes further (paper §4 Batching, Fig 8): it executes
the whole chain as a single ``jax.vmap``-over-rows ``jax.jit`` dispatch per
batch.  Row counts are padded up to power-of-two buckets so XLA recompiles
are bounded (O(log max_batch) shapes per chain instead of one per batch
size), and compiled executables live in a process-wide cache keyed on
``(chain signature, bucket shapes, dtypes)`` so identical chains across
re-registrations and plans reuse XLA programs instead of re-tracing.
Ragged batches (rows whose arrays differ in shape) are split into
shape-uniform groups — one dispatch per group — and anything that cannot
be stacked or traced falls back to the per-row jitted / interpreted path.

Three engine capabilities live at this layer:

* **Device residency** — ``apply_batched`` accepts and (with
  ``emit_device=True``) emits a :class:`~repro.core.table.DeviceTable`, so
  a chain of adjacent device-lowered DAG nodes pays ONE host->device stack
  at entry and ONE device->host gather at the demux boundary instead of a
  full round-trip per node.  Buffers the pipeline exclusively owns are
  donated to XLA (``donate_argnums``) so output batches reuse input
  allocations.
* **Filter-in-jit** — ``Filter`` operators lower into the jitted body as
  boolean masking: the mask rides along as a device column and dropped rows
  are compacted only at the device->host boundary, so filter-containing
  chains still execute as one dispatch.
* **Cost-based exec-path routing** — the executable cache records measured
  per-row vs batched latencies per chain (``ChainProfile``); small batches
  below the measured crossover are routed to the per-row executable
  automatically, which removes the stacking overhead that made tiny batches
  slower than per-row execution.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core import operators as ops
from repro.core.table import DeviceTable, Table, note_host_copy
from repro.obs import keys as okeys
from repro.obs.metrics import EVENTS
from repro.obs.trace import region

import jax
import jax.numpy as jnp

#: annotation types treated as "JAX array" for lowering.  Deliberately NOT
#: np.ndarray: the jitted chain emits jax.Array values, so only fns that
#: already declare jax.Array keep their downstream value types unchanged.
_ARRAY_TYPES: Tuple[type, ...] = (jax.Array,)

#: value types jit commits directly (leaf, not pytree) — these skip the
#: per-column normalization on the per-row hot path
_FAST_ROW_TYPES: Tuple[type, ...] = (jax.Array, np.ndarray, np.generic,
                                     float, int, bool, complex)


def _array_annotation(t) -> bool:
    return any(t is a for a in _ARRAY_TYPES)


def array_annotation(t) -> bool:
    """Is ``t`` an array annotation for lowering purposes?  Public name
    for the eligibility test ``map_is_jax_lowerable``/
    ``filter_is_jax_lowerable`` apply per argument — the static verifier
    (``repro.analysis``) gates abstract interpretation on the same
    predicate so the two can never disagree about what lowers."""
    return _array_annotation(t)


def map_is_jax_lowerable(m: ops.Operator) -> bool:
    """A ``Map`` whose argument and return annotations are all arrays.
    ``m._schema`` already holds the expanded return types (tuple returns
    included) from ``operators._ret_schema``."""
    if not isinstance(m, ops.Map):
        return False
    arg_types = m._arg_types
    if not arg_types or any(a is None or not _array_annotation(a)
                            for a in arg_types):
        return False
    return all(_array_annotation(t) for _, t in m._schema)


def filter_is_jax_lowerable(f: ops.Operator) -> bool:
    """A ``Filter`` whose arguments are all arrays and whose predicate is
    declared ``-> bool``: it lowers into the jitted body as a boolean
    mask column (rows compacted only at the device->host boundary)."""
    if not isinstance(f, ops.Filter):
        return False
    arg_types, ret = ops.fn_signature(f.fn)
    if ret is not bool:
        return False
    return bool(arg_types) and all(a is not None and _array_annotation(a)
                                   for a in arg_types)


def op_is_jax_lowerable(op: ops.Operator) -> bool:
    return map_is_jax_lowerable(op) or filter_is_jax_lowerable(op)


def fuse_is_jax_lowerable(fuse: ops.Operator, placement: str,
                          min_ops: int = 2) -> bool:
    """Eligibility: a ``Fuse`` of >= ``min_ops`` JAX-array maps/filters
    placed on a GPU-class node (accelerator-attached executor)."""
    return (isinstance(fuse, ops.Fuse)
            and not isinstance(fuse, JittedFuse)
            and placement == "gpu"
            and len(fuse.ops) >= min_ops
            and all(op_is_jax_lowerable(m) for m in fuse.ops))


def _chain_steps(chain_ops: List[ops.Operator]) -> Tuple[Tuple[str, Any], ...]:
    return tuple(("filter" if isinstance(m, ops.Filter) else "map", m.fn)
                 for m in chain_ops)


def _as_steps(steps) -> Tuple[Tuple[str, Any], ...]:
    return tuple(s if isinstance(s, tuple) else ("map", s) for s in steps)


def _const_slots(steps) -> Tuple[List[Any], List[Optional[int]]]:
    """(distinct consts, per-step index into them or None).  Steps that
    share weights (every decode step of a cascade) share one argument."""
    consts: List[Any] = []
    slots: List[Optional[int]] = []
    for _, fn in steps:
        if getattr(fn, "__pure__", None) is None:
            slots.append(None)
            continue
        c = fn.__consts__
        i = next((j for j, d in enumerate(consts) if d is c), None)
        if i is None:
            i = len(consts)
            consts.append(c)
        slots.append(i)
    return consts, slots


def chain_consts(steps) -> Tuple[Any, ...]:
    """The arrays the chain's steps compute with but do not take as
    columns (a model stage's weights), each distinct object once.  A step
    declares them as ``__consts__`` next to ``__pure__(consts, *cols)``;
    its plain call is ``__pure__`` with the consts bound.  Executables
    take them as arguments: arrays a traced function merely closes over
    become constants of the compiled program, and gigabytes of weights as
    constants exhaust the host's memory while lowering."""
    return tuple(_const_slots(_as_steps(steps))[0])


def compose_steps(steps, *, masked_input: bool, with_keep: bool,
                  counter: Optional[List[int]] = None) -> Callable:
    """The ONE definition of chain composition, shared by the per-row and
    vmapped executables (the router swaps between them, so their keep-mask
    semantics must be identical): apply maps in sequence, AND every
    filter's predicate into the keep bit.

    The callable's first argument is the tuple of :func:`chain_consts`;
    then, with ``masked_input``, the keep mask (device-resident batches
    thread an upstream mask through); then the columns.  ``with_keep`` —
    prepend the final keep to the outputs (always true when
    ``masked_input``); ``counter`` — trace counter, bumped once per
    (re-)trace, never per compiled call.
    """
    steps = _as_steps(steps)
    slots = _const_slots(steps)[1]
    emit_keep = masked_input or with_keep

    def composed(consts, *args):
        if counter is not None:
            counter[0] += 1
        if masked_input:
            keep, vals = args[0], args[1:]
        else:
            keep, vals = jnp.bool_(True), args
        for (kind, fn), slot in zip(steps, slots):
            out = (fn(*vals) if slot is None
                   else fn.__pure__(consts[slot], *vals))
            if kind == "filter":
                keep = jnp.logical_and(keep, out)
            else:
                vals = out if isinstance(out, tuple) else (out,)
        return ((keep,) + tuple(vals)) if emit_keep else tuple(vals)

    return composed


@dataclasses.dataclass
class JittedFuse(ops.Fuse):
    """A fused chain of JAX map/filter operators compiled to ONE jitted
    callable.

    The composed function applies every constituent ``fn`` in sequence
    inside a single trace, so XLA fuses across operator boundaries and each
    row costs one dispatch instead of ``len(ops)`` interpreted calls.
    Filters contribute a boolean ``keep`` output rather than control flow;
    the caller drops rows whose keep is False.
    """

    def __post_init__(self):
        steps = _chain_steps(self.ops)
        self._steps = steps
        self._has_filter = any(k == "filter" for k, _ in steps)
        self._sig = chain_signature(self.ops)
        self._jitted = functools.partial(
            jax.jit(compose_steps(steps, masked_input=False,
                                  with_keep=self._has_filter)),
            chain_consts(steps))
        last_map = next((m for m in reversed(self.ops)
                         if isinstance(m, ops.Map)), None)
        self._out_arity = (len(last_map._schema) if last_map is not None
                           else len(self.ops[0]._arg_types))
        self._fallback = False
        self._jit_succeeded = False
        #: latch kind -> the error that set it (``explain()`` prints it)
        self.latched: Dict[str, str] = {}
        self.row_dispatches = 0     # jitted per-row XLA dispatches issued
        self._prof: Optional[ChainProfile] = None
        self._prof_version = -1
        self._timing_tick = 0
        self._force_time = False    # set by a per-row routing probe
        self._node = self.name      # names this chain's profiler regions

    def profile(self) -> "ChainProfile":
        """This chain's measured cost profile (cached handle into the
        process-wide executable cache; refreshed after a cache clear)."""
        v = EXECUTABLE_CACHE.version
        if self._prof is None or self._prof_version != v:
            self._prof = EXECUTABLE_CACHE.profile(self._sig)
            self._prof_version = v
        return self._prof

    @property
    def name(self):
        return "jit[" + ",".join(o.name for o in self.ops) + "]"

    def _latch(self, kind: str, err: BaseException) -> None:
        """Give up an executable for the rest of the deployment (``fuse``:
        back to the interpreted ``Fuse``; ``vmap``: per-row jit only),
        counted in ``repro.obs.EVENTS`` and kept for ``explain()`` — a
        kernel the device's compiler refuses must not vanish silently."""
        if kind == "fuse":
            self._fallback = True
        else:
            self._vmap_fallback = True
        self.latched[kind] = f"{type(err).__name__}: {err}"[:300]
        EVENTS.inc(okeys.lowering_latch(kind))

    @property
    def jitted_fn(self):
        """The single compiled callable (one per fused chain)."""
        return self._jitted

    def _row_call(self, r, path: str = "row"):
        """One per-row jitted dispatch; returns the output Row, or None for
        a row a fused filter dropped.  Array/scalar values go to the
        executable as-is (jit commits them itself — no per-column
        ``jnp.asarray`` on the hot path); anything else (a Python list
        smuggled past an array annotation) is normalized first, because
        jit would treat it as a pytree and silently compute nonsense.
        ``path`` (``row`` or ``probe``) labels the dispatch's region."""
        with region("dispatch", node=self._node, path=path, rows=1):
            out = self._jitted(*(v if isinstance(v, _FAST_ROW_TYPES)
                                 else jnp.asarray(v) for v in r.values))
        self.row_dispatches += 1
        keep = None
        if self._has_filter:
            keep, out = out[0], tuple(out[1:])
        if len(out) != self._out_arity:
            raise ops.TypecheckError(
                f"{self.name}: returned {len(out)} values, schema "
                f"expects {self._out_arity}")
        self._jit_succeeded = True
        if keep is not None and not bool(keep):
            return None
        return r.replace(tuple(out))

    def apply(self, tables: List[Table], ctx=None) -> Table:
        if self._fallback:
            return ops.Fuse.apply(self, tables, ctx)
        (t,) = tables
        schema = self.out_schema([t.schema])
        rows = []
        # router timing is SAMPLED: warm multi-row calls of a chain whose
        # router actually consults the measurement (adaptive routing on a
        # batched lowering — plain per-row chains would pay the sync for
        # nothing), one in TIMING_SAMPLE_EVERY — the host sync drains the
        # async dispatch pipeline, so it must not tax every call
        timed = False
        if getattr(self, "adaptive_routing", False) and \
                self._jit_succeeded and len(t.rows) > 1:
            timed = self._force_time or \
                self._timing_tick % TIMING_SAMPLE_EVERY == 0
            self._timing_tick += 1
        path = "probe" if self._force_time else "row"
        self._force_time = False
        t0 = time.perf_counter()
        try:
            for r in t.rows:
                out = self._row_call(r, path)
                if out is not None:
                    rows.append(out)
        except ops.TypecheckError:
            raise
        except (jax.errors.JAXTypeError, TypeError,
                NotImplementedError) as e:
            # annotations said "array" but the fn is not jax-traceable
            # (data-dependent control flow, numpy side effects, ...).
            # Tracing happens on the first call, so only latch the
            # permanent fallback before any jitted call has succeeded;
            # a per-request data error on a proven-traceable chain (and
            # transient runtime errors like XLA OOM) propagates instead
            # of silently disabling the jitted path for the deployment.
            if self._jit_succeeded:
                raise
            self._latch("fuse", e)
            return ops.Fuse.apply(self, tables, ctx)
        if timed and rows:
            # feed the exec-path router: measured warm per-row cost (cold
            # calls include the XLA trace and would poison the estimate;
            # singleton calls don't amortize the fixed per-call overhead
            # and would overstate the marginal per-row cost at larger n).
            # Block on the outputs first — on async backends the dispatches
            # return immediately, and an unsynced timing would make the
            # router believe per-row costs microseconds, pinning batches to
            # the slow path (note_batched times host-to-host; this must be
            # symmetric)
            jax.block_until_ready([r.values for r in rows])
            self.profile().note_per_row(
                (time.perf_counter() - t0) / len(t.rows))
        out_t = Table(schema, grouping=t.grouping)
        out_t.rows = rows
        return out_t


# ---------------------------------------------------------------------------
# batched (vmap-over-rows) execution: shape buckets + executable cache
# ---------------------------------------------------------------------------

#: default row-count buckets: powers of two.  A batch of n rows is padded up
#: to the smallest bucket >= n, bounding recompiles to O(log max_batch)
#: distinct shapes per chain.
DEFAULT_BUCKETS: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)


# ---------------------------------------------------------------------------
# degraded serving: cheap execution variants under overload
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DegradePolicy:
    """How a low-priority request executes under overload pressure — only
    variants the executable cache already holds, so degrading never pays a
    fresh XLA trace on the hot path:

    * ``per_row`` — route to the per-row jitted executable (always compiled
      by the time any traffic flows; skips stack/pad/gather entirely);
    * ``bucket_cap`` — when the request does batch, cap its padding bucket
      (small buckets are the first ones traffic warms);
    * ``competitive`` — False disables competitive replication for the
      request (the runtime dispatches ONE replica of each wait-any group
      instead of racing all of them — tail suppression is a luxury a
      best-effort request does not get under overload).
    """
    per_row: bool = True
    bucket_cap: Optional[int] = 8
    competitive: bool = False


#: thread-local carrying the active DegradePolicy: the executor sets it
#: around a degraded request's node fn, and the exec-path router consults
#: it — the policy must travel WITH the work onto the executor thread, so
#: a context variable on the submitting thread would be invisible here
_DEGRADE_TLS = threading.local()


@contextlib.contextmanager
def degraded_execution(policy: Optional["DegradePolicy"]):
    """Execute the enclosed chain calls under ``policy`` (None = no-op).
    The exec-path router (``BatchedJittedFuse``) reads the active policy
    via :func:`active_degrade` and picks the cheap, already-compiled
    variant instead of the throughput-optimal one."""
    prev = getattr(_DEGRADE_TLS, "policy", None)
    _DEGRADE_TLS.policy = policy
    try:
        yield
    finally:
        _DEGRADE_TLS.policy = prev


def active_degrade() -> Optional["DegradePolicy"]:
    """The DegradePolicy in effect on this thread, or None."""
    return getattr(_DEGRADE_TLS, "policy", None)

#: per-row router timing is sampled 1-in-N (the measurement's host sync
#: drains the async dispatch pipeline — it must not tax every
#: steady-state per-row call); aligned with ChainProfile.PROBE_EVERY
TIMING_SAMPLE_EVERY = 16


def bucket_rows(n: int, buckets: Tuple[int, ...] = DEFAULT_BUCKETS) -> int:
    """Smallest bucket >= n; beyond the table, next power of two."""
    for b in buckets:
        if n <= b:
            return b
    b = buckets[-1] if buckets else 1
    while b < n:
        b *= 2
    return b


def chain_signature(chain_ops: List[ops.Operator]) -> Tuple[Any, ...]:
    """Identity of a fused chain: the tuple of its (op kind, function)
    pairs.  Two ``Fuse`` nodes built from the same function objects (the
    common case across re-registrations of the same flow) share compiled
    executables; redefining a function yields a new object and, correctly,
    a new entry."""
    return _chain_steps(chain_ops)


def crossover_from_costs(per_row_s: Optional[float],
                         batched_s: Dict[int, float],
                         max_n: int = 1024) -> Optional[int]:
    """THE crossover rule, shared by the live router (``ChainProfile``)
    and the offline profiler's ``OpLatencyCurve`` — the smallest batch
    size n at which one batched dispatch at n's covering measured bucket
    beats n per-row dispatches, or None while either path is unmeasured.
    One definition, so the optimizer's offline decision and the runtime
    router's live decision cannot silently diverge."""
    if per_row_s is None or not batched_s:
        return None
    measured = sorted(batched_s)
    for n in range(1, min(max_n, measured[-1]) + 1):
        b = next((batched_s[m] for m in measured if m >= n), None)
        if b is not None and n * per_row_s >= b:
            return n
    return None


class ChainProfile:
    """Measured execution costs of one chain, feeding the exec-path router.

    ``per_row_s`` is an EWMA of warm per-row jitted latency (seconds per
    row); ``batched_s[bucket]`` an EWMA of warm whole-batch latency
    (seconds per dispatch, host->host) at that padded bucket size.  The
    router batches a table of n rows only when the measured batched cost at
    its bucket beats n per-row dispatches — which is what removes the
    small-batch regression where stacking costs more than it saves."""

    __slots__ = ("alpha", "per_row_s", "per_row_samples",
                 "batched_s", "batched_samples", "_since_probe", "_lock")

    #: after this many consecutive same-path routings at a bucket, take
    #: the other path once — a single slow early sample must not pin the
    #: router forever (estimates go stale unless refreshed)
    PROBE_EVERY = 16

    #: never probe the per-row direction with more rows than this: a
    #: per-row probe pays n sequential dispatches, which on a large batch
    #: would turn every PROBE_EVERY-th request into a p99 outlier.  Large
    #: batches therefore stay vmapped unless small-batch traffic has
    #: already measured the per-row path.
    PROBE_ROW_CAP = 8

    def __init__(self, alpha: float = 0.3):
        self.alpha = alpha
        self.per_row_s: Optional[float] = None
        self.per_row_samples = 0
        self.batched_s: Dict[int, float] = {}
        self.batched_samples: Dict[int, int] = {}
        self._since_probe: Dict[int, int] = {}
        # mutated from every executor thread serving the chain; snapshot()
        # iterates the dicts, so unsynchronized inserts could blow up a
        # concurrent export with "dict changed size during iteration"
        self._lock = threading.Lock()

    def _ewma(self, old: Optional[float], new: float) -> float:
        if old is None:
            return new
        # clamp the sample: a scheduler stall can be 100x the true cost,
        # and an unclamped EWMA (mean-like) would need many clean samples
        # to recover — genuine 2-3x shifts still move the estimate fast
        return (1.0 - self.alpha) * old + self.alpha * min(new, 3.0 * old)

    def note_per_row(self, seconds_per_row: float) -> None:
        if seconds_per_row <= 0:
            return
        with self._lock:
            self.per_row_s = self._ewma(self.per_row_s, seconds_per_row)
            self.per_row_samples += 1

    def note_batched(self, bucket: int, seconds: float) -> None:
        if seconds <= 0:
            return
        with self._lock:
            n = self.batched_samples.get(bucket, 0) + 1
            self.batched_samples[bucket] = n
            if n == 1:
                # the first warm execution still pays one-time costs
                # (allocator growth, page faults); folding it into the
                # EWMA overstates the batched path and misroutes
                return
            self.batched_s[bucket] = self._ewma(
                self.batched_s.get(bucket), seconds)

    def prefer_per_row(self, n: int, bucket: int) -> bool:
        """True when n per-row dispatches are measured cheaper than one
        batched dispatch at ``bucket``.  Unmeasured paths prefer batching
        (the batched call doubles as the probe that measures it)."""
        with self._lock:
            b = self.batched_s.get(bucket)
            if b is None or self.per_row_s is None:
                return False
            return n * self.per_row_s < b

    def route_decision(self, n: int, bucket: int) -> Tuple[bool, bool]:
        """``(route_per_row, is_probe)``: ``prefer_per_row`` plus
        SYMMETRIC probing — every ``PROBE_EVERY``-th decision at a bucket
        takes the other path, so the unused path's estimate stays fresh
        and gets measured at all when it has never run.  Per-row probes
        are capped at ``PROBE_ROW_CAP`` rows (see above); a probe call
        must always be measured (its n dispatches are the measurement)."""
        prefer = self.prefer_per_row(n, bucket)
        with self._lock:
            seen = self._since_probe.get(bucket, 0) + 1
            if seen >= self.PROBE_EVERY:
                self._since_probe[bucket] = 0
                if prefer:
                    return False, True             # refresh batched cost
                return n <= self.PROBE_ROW_CAP, True   # refresh per-row
            self._since_probe[bucket] = seen
            return prefer, False

    def route_per_row(self, n: int, bucket: int) -> bool:
        return self.route_decision(n, bucket)[0]

    def crossover_rows(self, max_n: int = 1024) -> Optional[int]:
        """Smallest batch size at which the vmapped path is measured to
        win, or None while either path is unmeasured.  Candidate buckets
        are the MEASURED ones (the chain may have been lowered with custom
        ``bucket_sizes``; assuming the defaults would report a crossover
        for buckets that never exist)."""
        with self._lock:
            per_row_s = self.per_row_s
            batched_s = dict(self.batched_s)
        return crossover_from_costs(per_row_s, batched_s, max_n)

    # -- serialization (profiler persistence across processes) ---------------
    def to_dict(self) -> Dict[str, Any]:
        """Stable JSON-serializable state: the EWMAs and sample counts the
        router needs, with bucket keys as strings (JSON objects only have
        string keys — ``from_dict`` restores ints)."""
        with self._lock:
            return {
                "alpha": self.alpha,
                "per_row_s": self.per_row_s,
                "per_row_samples": self.per_row_samples,
                "batched_s": {str(b): s for b, s in self.batched_s.items()},
                "batched_samples": {str(b): n for b, n
                                    in self.batched_samples.items()},
            }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ChainProfile":
        p = cls(alpha=float(d.get("alpha", 0.3)))
        per_row = d.get("per_row_s")
        p.per_row_s = float(per_row) if per_row is not None else None
        p.per_row_samples = int(d.get("per_row_samples", 0))
        p.batched_s = {int(b): float(s)
                       for b, s in (d.get("batched_s") or {}).items()}
        p.batched_samples = {int(b): int(n)
                             for b, n in (d.get("batched_samples") or {})
                             .items()}
        return p

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            per_row_s = self.per_row_s
            per_row_samples = self.per_row_samples
            batched_s = dict(self.batched_s)
            batched_samples = dict(self.batched_samples)
        return {
            "per_row_ms": (per_row_s * 1e3
                           if per_row_s is not None else None),
            "per_row_samples": per_row_samples,
            "batched_ms": {b: s * 1e3 for b, s in sorted(batched_s.items())},
            "batched_samples": dict(sorted(batched_samples.items())),
            "crossover_rows": self.crossover_rows(),
        }


class ExecutableCache:
    """Process-wide cache of compiled batched chain executables.

    Entries are keyed on ``(chain signature, bucket shapes, dtypes, masked,
    donate)``.  All entries for one chain share its composed functions (XLA
    specializes per shape under ``jax.jit``); the explicit per-key
    bookkeeping is what lets callers *observe* reuse: ``misses`` count new
    combinations, ``traces`` count actual re-traces of the composed
    function — zero new traces for a repeated identical chain is the
    cache's contract.

    Two executable variants exist per chain: *masked* (a boolean liveness
    column threads through the body — used when the chain fuses a Filter or
    consumes an upstream-masked ``DeviceTable``) and *donating* (inputs are
    handed to XLA for buffer reuse — used when the caller exclusively owns
    the batch buffers).  The cache also carries each chain's measured
    :class:`ChainProfile` for exec-path routing.
    """

    def __init__(self, max_chains: int = 128):
        self._lock = threading.Lock()
        self.max_chains = max_chains
        #: bumped on clear() so ops can cache their ChainProfile handle
        self.version = 0
        # chain signature -> {"counter": [traces], "jitted": {(masked,
        # donate): callable}}; insertion/access order maintained for LRU
        # eviction — signatures hold the chain's fn objects, so an
        # unbounded cache would pin every deploy-time closure (and its
        # jitted executable) forever
        self._fns: "collections.OrderedDict[Tuple, Dict[str, Any]]" = \
            collections.OrderedDict()
        # (chain signature, shapes, dtypes, masked, donate) -> hit count
        self._entries: Dict[Tuple, int] = {}
        # independently LRU-bounded: profiles are also created for chains
        # that never compile a vmapped executable (per-row-only chains),
        # and their signatures pin fn closures just like _fns entries do
        self._profiles: "collections.OrderedDict[Tuple, ChainProfile]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def executable(self, sig: Tuple, steps, shapes: Tuple, dtypes: Tuple,
                   *, masked: bool = False, donate: bool = False) -> Callable:
        """The compiled callable for this (chain, bucket shapes, dtypes),
        with the chain's :func:`chain_consts` bound as its leading
        argument.

        ``shapes``/``dtypes`` describe the value columns only; the masked
        variant takes the boolean liveness column as its first argument.
        With ``donate=True`` every column buffer is donated to XLA
        (``donate_argnums``) — callers must own them exclusively."""
        with self._lock:
            rec = self._fns.get(sig)
            if rec is None:
                rec = {"counter": [0], "jitted": {}}
                self._fns[sig] = rec
                while len(self._fns) > self.max_chains:
                    old_sig, _ = self._fns.popitem(last=False)
                    self._entries = {k: v for k, v in self._entries.items()
                                     if k[0] != old_sig}
                    if self._profiles.pop(old_sig, None) is not None:
                        # invalidate cached profile handles: a still-live
                        # op of the evicted chain must not keep feeding an
                        # orphaned profile while fresh lookups get a new one
                        self.version += 1
                    self.evictions += 1
            else:
                self._fns.move_to_end(sig)
            variant = (bool(masked), bool(donate))
            fn = rec["jitted"].get(variant)
            if fn is None:
                composed = compose_steps(steps, masked_input=masked,
                                         with_keep=masked,
                                         counter=rec["counter"])
                n_args = len(shapes) + (1 if masked else 0)
                fn = functools.partial(
                    jax.jit(jax.vmap(composed,
                                     in_axes=(None,) + (0,) * n_args),
                            donate_argnums=(tuple(range(1, n_args + 1))
                                            if donate else ())),
                    chain_consts(steps))
                rec["jitted"][variant] = fn
            key = (sig, shapes, dtypes) + variant
            if key in self._entries:
                self._entries[key] += 1
                self.hits += 1
            else:
                self._entries[key] = 0
                self.misses += 1
            return fn

    def profile(self, sig: Tuple) -> ChainProfile:
        """The chain's measured cost profile (created on first access)."""
        with self._lock:
            p = self._profiles.get(sig)
            if p is None:
                p = self._profiles[sig] = ChainProfile()
                while len(self._profiles) > self.max_chains:
                    self._profiles.popitem(last=False)
                    # invalidate cached handles (see eviction above)
                    self.version += 1
            else:
                self._profiles.move_to_end(sig)
            return p

    def traces(self, sig: Optional[Tuple] = None) -> int:
        """Total composed-fn traces (compilations), optionally per chain."""
        with self._lock:
            if sig is not None:
                rec = self._fns.get(sig)
                return rec["counter"][0] if rec else 0
            return sum(r["counter"][0] for r in self._fns.values())

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"chains": len(self._fns), "entries": len(self._entries),
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "traces": sum(r["counter"][0]
                                  for r in self._fns.values())}

    def clear(self):
        with self._lock:
            self._fns.clear()
            self._entries.clear()
            self._profiles.clear()
            self.hits = self.misses = self.evictions = 0
            self.version += 1


#: the process-wide cache: identical fused chains across plans and
#: re-registrations reuse compiled XLA programs instead of re-tracing.
EXECUTABLE_CACHE = ExecutableCache()


@dataclasses.dataclass
class BatchedJittedFuse(JittedFuse):
    """A jitted fused chain executed as ONE vmapped dispatch per batch.

    ``apply_batched`` stacks the table's rows into a device-resident
    :class:`DeviceTable` (padding the row count up to a power-of-two
    bucket), looks up the compiled executable in the process-wide
    ``EXECUTABLE_CACHE``, and issues a single XLA dispatch for the whole
    batch.  Rows with heterogeneous array shapes are split into
    shape-uniform groups (one dispatch each) — ragged dims participate in
    the cache key, so recompiles stay bounded per distinct shape.

    Device residency: when handed a ``DeviceTable`` the chain runs without
    touching the host, and with ``emit_device=True`` it returns one — the
    runtime threads batches through adjacent device nodes this way, paying
    one stack at chain entry and one gather at the demux boundary.
    Exclusively-owned input buffers are donated to XLA so the output batch
    reuses their allocation.

    Exec-path routing: the chain's measured :class:`ChainProfile` decides
    per call whether n rows run as one vmapped dispatch or n per-row
    dispatches — singletons always take the per-row executable (no
    stacking at all), larger tables batch once the measured crossover says
    it pays.  The per-row jitted path and the interpreted ``Fuse`` path
    remain as fallbacks for non-stackable values and non-traceable
    functions.
    """
    bucket_sizes: Tuple[int, ...] = DEFAULT_BUCKETS
    adaptive_routing: bool = True

    def __post_init__(self):
        super().__post_init__()
        self._batch_succeeded = False
        self._vmap_fallback = False   # vmap untraceable; per-row jit works
        # dispatch + host-copy accounting (read by benchmarks and metrics)
        self.batch_dispatches = 0
        self.rows_batched = 0
        self.host_stacks = 0
        self.host_gathers = 0

    @property
    def name(self):
        return "vjit[" + ",".join(o.name for o in self.ops) + "]"

    # -- exec-path routing ---------------------------------------------------
    def _route_per_row(self, n: int) -> bool:
        """True when n rows should take the per-row executable: singletons
        always (stacking a batch of one only adds overhead), larger tables
        when the chain's measured crossover says per-row wins."""
        if n <= 1:
            return True
        pol = active_degrade()
        if pol is not None and pol.per_row:
            # degraded request: the per-row executable is always warm and
            # skips stack/pad/gather — take it regardless of the measured
            # crossover, and don't let the call probe/feed the EWMA
            return True
        if not self.adaptive_routing:
            return False
        route, probe = self.profile().route_decision(
            n, bucket_rows(n, self.bucket_sizes))
        if route and probe:
            # a per-row probe exists to measure: force the timing sample
            self._force_time = True
        return route

    # -- batched execution ---------------------------------------------------
    def _stack_groups(self, rows):
        """Group rows by per-column (shape, dtype); returns
        [(indices, [col lists])] preserving original order within groups.
        Values are materialized as host (numpy) arrays in ONE
        ``jax.device_get`` for the whole table (row values are frequently
        jax arrays already committed to the device — per-value conversion
        would pay one host sync per row); stacking then happens as one
        memcpy + ONE device_put per column, instead of an n-arg XLA
        concatenate whose dispatch costs about as much as the n per-row
        calls the batching is meant to eliminate."""
        host_vals = [list(r.values) for r in rows]
        if any(isinstance(v, jax.Array) for rv in host_vals for v in rv):
            with region("stack", node=self._node) as reg:
                if reg:
                    reg.set_metadata(bytes=sum(
                        getattr(v, "nbytes", 0) for rv in host_vals
                        for v in rv))
                host_vals = jax.device_get(host_vals)
            # honest accounting: this readback IS bulk row payload
            # crossing the boundary (rows arriving as host numpy — the
            # normal serving case — skip it entirely)
            note_host_copy("gathers")
            self.host_gathers += 1
        groups: Dict[Tuple, Tuple[List[int], List[List[Any]]]] = {}
        for i, rvals in enumerate(host_vals):
            arrs = [np.asarray(v) for v in rvals]
            key = tuple((a.shape, str(a.dtype)) for a in arrs)
            idxs, cols = groups.setdefault(
                key, ([], [[] for _ in arrs]))
            idxs.append(i)
            for c, a in zip(cols, arrs):
                c.append(a)
        return list(groups.values())

    def _run_device(self, dt: DeviceTable, donate: bool) -> DeviceTable:
        """ONE vmapped XLA dispatch over a device-resident batch; the
        result stays on the device.  The mask column (chain filters and/or
        upstream mask) threads through the executable."""
        masked = self._has_filter or dt.mask is not None
        shapes = tuple(tuple(c.shape) for c in dt.columns)
        dtypes = tuple(str(c.dtype) for c in dt.columns)
        do = bool(donate and dt.donatable)
        fn = EXECUTABLE_CACHE.executable(self._sig, self._steps, shapes,
                                         dtypes, masked=masked, donate=do)
        with region("dispatch", node=self._node, path="batch",
                    rows=dt.nrows, bucket=dt.cap):
            if masked:
                mask = dt.mask
                if mask is None:
                    mask = jnp.asarray(np.ones(dt.cap, np.bool_))
                outs = fn(mask, *dt.columns)
                new_mask, out_cols = outs[0], outs[1:]
            else:
                out_cols = fn(*dt.columns)
                new_mask = None
        if len(out_cols) != self._out_arity:
            raise ops.TypecheckError(
                f"{self.name}: returned {len(out_cols)} values, schema "
                f"expects {self._out_arity}")
        self.batch_dispatches += 1
        self.rows_batched += dt.nrows
        if do:
            # donated buffers are gone; make accidental reuse loud
            dt.donatable = False
        return DeviceTable(self.out_schema([dt.schema]), list(out_cols),
                           dt.nrows, dt.row_ids, dt.groups,
                           grouping=dt.grouping, mask=new_mask,
                           donatable=True)

    def _apply_device(self, dt: DeviceTable, ctx, emit_device: bool,
                      donate_out: bool):
        """Device-resident fast path: DeviceTable in, DeviceTable (or host
        table, at the chain boundary) out — no host copy in between."""
        if self._fallback:
            self.host_gathers += 1
            return ops.Fuse.apply(self, [dt.to_table(self._node)], ctx)
        if self._vmap_fallback:
            self.host_gathers += 1
            return JittedFuse.apply(self, [dt.to_table(self._node)], ctx)
        try:
            out_dt = self._run_device(dt, donate=True)
        except ops.TypecheckError:
            raise
        except (jax.errors.JAXTypeError, TypeError, NotImplementedError,
                ValueError) as e:
            if self._batch_succeeded and self._jit_succeeded:
                raise
            if self._jit_succeeded:
                self._latch("vmap", e)
                self.host_gathers += 1
                return JittedFuse.apply(self, [dt.to_table(self._node)], ctx)
            if self._batch_succeeded:
                raise
            self._latch("fuse", e)
            self.host_gathers += 1
            return ops.Fuse.apply(self, [dt.to_table(self._node)], ctx)
        self._batch_succeeded = True
        if emit_device:
            out_dt.donatable = donate_out
            return out_dt
        self.host_gathers += 1
        return out_dt.to_table(self._node)

    def apply_batched(self, tables: List[Table], ctx=None, *,
                      emit_device: bool = False,
                      donate_out: bool = False):
        (t,) = tables
        if isinstance(t, DeviceTable):
            return self._apply_device(t, ctx, emit_device, donate_out)
        if self._fallback:
            return ops.Fuse.apply(self, tables, ctx)
        if self._vmap_fallback:
            return JittedFuse.apply(self, tables, ctx)
        n = len(t.rows)
        if n == 1 and not emit_device:
            # singleton fast-path: straight to the per-row executable —
            # no stacking, no padding, no profile consult
            return JittedFuse.apply(self, tables, ctx)
        if not t.rows:
            return Table(self.out_schema([t.schema]), grouping=t.grouping)
        if not emit_device and self._route_per_row(n):
            # measured crossover says n per-row dispatches beat one
            # stack+vmap+gather round-trip
            return JittedFuse.apply(self, tables, ctx)
        t_start = time.perf_counter()      # honest: stacking cost included
        try:
            groups = self._stack_groups(t.rows)
        except Exception:
            # non-array values slipped past the annotations: the batched
            # path cannot stack them — per-row jitted path still applies
            return JittedFuse.apply(self, tables, ctx)
        out_rows: List[Any] = [None] * n
        vmapped_any = False      # did a vmapped dispatch succeed THIS call?
        try:
            for idxs, cols in groups:
                k = len(idxs)
                if k == 1 and (len(groups) > 1 or not emit_device):
                    # stray singleton in a ragged table: the per-row
                    # executable avoids the stack/pad/gather round-trip
                    i = idxs[0]
                    out_rows[i] = self._row_call(t.rows[i])
                    continue
                bucket = bucket_rows(k, self.bucket_sizes)
                pol = active_degrade()
                if pol is not None and pol.bucket_cap:
                    # degraded: pad into the smallest already-configured
                    # bucket <= cap that still fits — never a fresh shape,
                    # so no fresh XLA trace on the overloaded hot path
                    capped = tuple(b for b in self.bucket_sizes
                                   if b <= pol.bucket_cap)
                    if capped and k <= capped[-1]:
                        bucket = bucket_rows(k, capped)
                # pad the row LIST (repeating row 0) before stacking, so
                # stacked shapes are always bucket-sized — padding on
                # device would compile a fresh XLA program per distinct n,
                # defeating the bucketing entirely
                dt = DeviceTable.from_columns(
                    t.schema, cols, [t.rows[i].row_id for i in idxs],
                    [t.rows[i].group for i in idxs], pad_to=bucket,
                    grouping=t.grouping, node=self._node)
                self.host_stacks += 1
                was_fresh = EXECUTABLE_CACHE.misses
                out_dt = self._run_device(dt, donate=True)
                vmapped_any = True
                if emit_device and len(groups) == 1:
                    self._batch_succeeded = True
                    out_dt.donatable = donate_out
                    return out_dt
                # ONE host sync per group: slicing a device array per row
                # would issue n gather dispatches — as many as the per-row
                # path — while numpy row views are free.  Downstream
                # consumers (jnp ops, lowered chains) take ndarray
                # transparently via jnp.asarray.
                for pos, row in out_dt.host_rows(self._node):
                    out_rows[idxs[pos]] = row
                self.host_gathers += 1
                if len(groups) == 1 and EXECUTABLE_CACHE.misses == was_fresh:
                    # warm uniform batch: feed the router's batched-cost
                    # EWMA with the WHOLE path cost — stack + dispatch +
                    # gather — so the crossover reflects what a request
                    # actually pays (cold calls include the XLA trace and
                    # are skipped)
                    self.profile().note_batched(
                        bucket, time.perf_counter() - t_start)
        except ops.TypecheckError:
            raise
        except (jax.errors.JAXTypeError, TypeError, NotImplementedError,
                ValueError) as e:
            # latching policy mirrors the per-row path, but the two
            # executables are judged separately: a chain can be jit-traceable
            # per row yet fail under vmap (callbacks, batching-hostile
            # primitives) — then the per-row executable keeps serving.
            # Proven executables never latch; their errors are data errors.
            if self._batch_succeeded and self._jit_succeeded:
                raise
            if self._jit_succeeded:
                # per-row proven; the vmapped path is the suspect
                self._latch("vmap", e)
                return JittedFuse.apply(self, tables, ctx)
            if self._batch_succeeded:
                # vmap proven but the per-row (singleton) call failed:
                # composed fn traced fine under vmap, so treat as data error
                raise
            self._latch("fuse", e)
            return ops.Fuse.apply(self, tables, ctx)
        if vmapped_any:
            # a singleton-only table proves the per-row executable, not the
            # vmapped one — conflating them would turn a later first vmap
            # trace failure into a permanent request-time error
            self._batch_succeeded = True
        out_t = Table(self.out_schema([t.schema]), grouping=t.grouping)
        out_t.rows = [r for r in out_rows if r is not None]
        return out_t

    def apply(self, tables: List[Table], ctx=None) -> Table:
        return self.apply_batched(tables, ctx)

    # -- cache warming (blue/green replanning) -------------------------------
    def warm(self, tables: List[Table], ctx=None, *,
             emit_device: bool = False, donate_out: bool = False):
        """Execute the chain once with the exec-path router BYPASSED
        (always the vmapped executable), so this call traces/loads the
        batch's bucket executable through ``EXECUTABLE_CACHE`` regardless
        of what the measured crossover would route.  The blue/green
        replanner walks a freshly compiled plan through this at every
        bucket size before any traffic is swapped onto it — the first
        post-swap request must find every executable already compiled
        (``EXECUTABLE_CACHE.traces()`` flat across the swap).

        Same contract as ``apply_batched`` (the warm-up result doubles as
        a correctness canary); a singleton input still warms the per-row
        executable, exactly the path a live singleton takes."""
        with forced_batched_routing([self]):
            return self.apply_batched(tables, ctx, emit_device=emit_device,
                                      donate_out=donate_out)


@contextlib.contextmanager
def forced_batched_routing(chain_ops):
    """Temporarily disable adaptive exec-path routing on the given lowered
    chains, so every multi-row call takes the vmapped executable — the
    cache-warming walk must trace the batched path at every bucket even
    where the live router would (correctly) route small batches per-row.
    Restores each chain's previous routing flag on exit."""
    prev = [(o, o.adaptive_routing) for o in chain_ops
            if isinstance(o, BatchedJittedFuse)]
    for o, _ in prev:
        o.adaptive_routing = False
    try:
        yield
    finally:
        for o, flag in prev:
            o.adaptive_routing = flag


def lower_fuse(fuse: ops.Fuse, *, batched: bool = False,
               bucket_sizes: Tuple[int, ...] = DEFAULT_BUCKETS) -> JittedFuse:
    """Lower an interpreted ``Fuse`` into a ``JittedFuse`` (or, with
    ``batched=True``, a ``BatchedJittedFuse``).  Annotations are the
    caller's job — this only swaps the execution strategy."""
    if batched:
        lowered: JittedFuse = BatchedJittedFuse(list(fuse.ops),
                                                bucket_sizes=bucket_sizes)
    else:
        lowered = JittedFuse(list(fuse.ops))
    lowered.resource_class = fuse.resource_class
    lowered.batching = fuse.batching
    lowered.high_variance = fuse.high_variance
    lowered.competitive_replicas = fuse.competitive_replicas
    return lowered
