"""Abstract interpretation over the ``PhysicalPlan`` IR.

Propagates per-edge ``jax.ShapeDtypeStruct``s through the topo-sorted
plan with ``jax.eval_shape`` — tracing annotated map/filter/kernel/
ModelOp steps abstractly, never compiling anything — so shape/dtype
mismatches (CF101) and non-traceable steps destined for jit lowering
(CF102) surface *before the first XLA trace*.  Fused chains are walked
step by step (the live router would too), and batch-lowered chains are
re-evaluated under ``jax.vmap`` at every padding bucket, which is
exactly the set of shapes ``warm_deployment`` will trace.

Shape inference needs concrete input shapes: pass ``input_specs`` (a
``{column: ShapeDtypeStruct}`` dict, or derive one from a sample request
with :func:`specs_from_table`).  Without specs — or without jax — the
shape-dependent diagnostics skip gracefully; schema/placement/residency
inference still runs off the IR's type annotations alone.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.analysis.diagnostics import Diagnostic, Report
from repro.core import operators as ops
from repro.core.ir import SOURCE_ID, PhysicalPlan
from repro.core.lowering import BatchedJittedFuse, array_annotation

import jax

#: exception types that mean "the step cannot be traced" (data-dependent
#: python control flow, concretization of tracers) as opposed to a plain
#: shape error.  Resolved lazily because jax may be absent.
def _trace_error_types():
    errs = []
    for name in ("ConcretizationTypeError", "TracerArrayConversionError",
                 "TracerBoolConversionError", "TracerIntegerConversionError"):
        t = getattr(getattr(jax, "errors", None), name, None)
        if t is not None:
            errs.append(t)
    return tuple(errs)


@dataclasses.dataclass
class EdgeType:
    """What the verifier knows about one plan edge (an op's output)."""
    schema: Tuple[Tuple[str, type], ...]
    grouping: Optional[str] = None
    #: per-column ShapeDtypeStructs at ROW level (no batch dim); None
    #: entries are columns whose shape is unknown (non-array types,
    #: un-analyzable producers)
    specs: Optional[Tuple[object, ...]] = None
    placement: str = "cpu"
    device_resident: bool = False

    def spec_map(self) -> Dict[str, object]:
        if self.specs is None:
            return {}
        return {name: s for (name, _t), s in zip(self.schema, self.specs)
                if s is not None}


def specs_from_table(table) -> Optional[Dict[str, object]]:
    """Derive row-level input specs from a sample request table (row 0's
    values).  Non-numeric columns map to None (shape unknown)."""
    if not getattr(table, "rows", None):
        return None
    out: Dict[str, object] = {}
    row = table.rows[0]
    for (name, _t), v in zip(table.schema, row.values):
        try:
            a = np.asarray(v)
            if a.dtype.kind in "OUS":       # strings/objects: no shape
                out[name] = None
            else:
                out[name] = jax.ShapeDtypeStruct(a.shape, a.dtype)
        except Exception:
            out[name] = None
    return out


def _chain_of(op) -> Optional[List[object]]:
    """The map/filter step list of a fusable op (Fuse and its jitted
    subclasses), a single-element list for a bare Map/Filter, or None
    for ops abstract interpretation cannot step through."""
    if isinstance(op, ops.Fuse):
        return list(op.ops)
    if isinstance(op, (ops.Map, ops.Filter)):
        return [op]
    return None


def _jit_destined(phys_op) -> bool:
    """Will this op's steps run under jit?  Already-lowered chains did;
    gpu-placed fusable chains will when jit lowering is on."""
    from repro.core.lowering import JittedFuse
    if isinstance(phys_op.op, JittedFuse):
        return True
    return phys_op.placement == "gpu" and _chain_of(phys_op.op) is not None


def _eval_step(step, in_specs, *, vmapped: bool = False):
    """eval_shape one map/filter step against positional column specs;
    returns the output spec list (filters pass their input through).
    ``vmapped`` means the specs already carry a leading batch dim and the
    step runs under ``jax.vmap`` (the batched-lowered dispatch shape)."""
    fn = step.fn
    if vmapped:
        fn = jax.vmap(fn)
    out = jax.eval_shape(fn, *in_specs)
    if isinstance(step, ops.Filter):
        return list(in_specs)       # a filter only drops rows
    return list(out) if isinstance(out, (tuple, list)) else [out]


def _steps_analyzable(steps, in_specs) -> bool:
    """All step annotations are jax arrays and every input column has a
    known spec — the precondition for abstract interpretation."""
    if in_specs is None or any(s is None for s in in_specs):
        return False
    for s in steps:
        # a fused chain can carry non-Map/Filter sub-ops (e.g. a Lookup
        # merged in by the locality pass) — those have no annotations and
        # no pure step function, so the chain is not abstractly steppable
        arg_types = getattr(s, "_arg_types", None)
        if arg_types is None:
            return False
        if any(not array_annotation(t) for t in arg_types):
            return False
        if isinstance(s, ops.Map) and \
                any(not array_annotation(t) for _n, t in s._schema):
            return False
    return True


def _walk_chain(phys_op, steps, in_specs, report: Report,
                *, bucket: int = 0) -> Optional[List[object]]:
    """Step through a (possibly fused) chain with eval_shape, emitting
    CF101/CF102 on failure.  Returns the final column specs or None."""
    destined = _jit_destined(phys_op)
    cur = list(in_specs)
    if bucket:      # the padded dispatch shape: batch dim added ONCE
        cur = [jax.ShapeDtypeStruct((bucket,) + tuple(s.shape), s.dtype)
               for s in cur]
    trace_errs = _trace_error_types()
    for step in steps:
        at = f" at bucket {bucket}" if bucket else ""
        try:
            cur = _eval_step(step, cur, vmapped=bool(bucket))
        except trace_errs as e:
            if destined:
                report.add(Diagnostic(
                    "CF102", f"step {step.name!r} is not traceable for "
                    f"jit lowering{at}: {_first_line(e)}",
                    op_id=phys_op.op_id,
                    hint="remove data-dependent python control flow or "
                         "drop the jax.Array annotations so the step "
                         "stays eager"))
            return None
        except Exception as e:
            report.add(Diagnostic(
                "CF101", f"step {step.name!r} rejects the inferred input "
                f"shapes{at} "
                f"({', '.join(_fmt_spec(s) for s in cur)}): "
                f"{_first_line(e)}",
                op_id=phys_op.op_id,
                hint="fix the producing op's output shape or this step's "
                     "expected operand shapes"))
            return None
    if bucket:      # strip the batch dim back off for edge storage
        cur = [jax.ShapeDtypeStruct(tuple(s.shape[1:]), s.dtype)
               for s in cur]
    return cur


def _fmt_spec(s) -> str:
    try:
        return f"{np.dtype(s.dtype).name}{list(s.shape)}"
    except Exception:
        return repr(s)


def _first_line(e: BaseException) -> str:
    return f"{type(e).__name__}: {str(e).splitlines()[0] if str(e) else ''}"


def infer(plan: PhysicalPlan,
          input_specs: Optional[Dict[str, object]] = None,
          report: Optional[Report] = None,
          *, check_buckets: bool = True
          ) -> Tuple[Dict[int, EdgeType], Report]:
    """Propagate schemas + shape specs through the plan.  Returns the
    per-op-id edge types and the report the walk appended to."""
    report = report if report is not None else Report()
    types: Dict[int, EdgeType] = {}

    # schemas/groupings come from the IR typechecker; a failure there IS
    # the shape/dtype-mismatch diagnostic, at schema granularity
    try:
        schemas = plan.typecheck()
    except Exception as e:
        report.add(Diagnostic(
            "CF101", f"plan typecheck failed: {_first_line(e)}",
            hint="fix the op annotations so consecutive schemas agree"))
        return types, report

    src_specs = None
    if input_specs is not None:
        src_specs = tuple(input_specs.get(name)
                          for name, _t in plan.input_schema)
    types[SOURCE_ID] = EdgeType(schema=tuple(plan.input_schema),
                                specs=src_specs)

    for o in plan.ops:
        schema, grouping = schemas[o.op_id]
        et = EdgeType(schema=tuple(schema), grouping=grouping,
                      placement=o.placement,
                      device_resident=o.device_resident)
        ins = [types.get(i) for i in o.inputs]
        steps = _chain_of(o.op)
        if steps is not None and len(ins) == 1 and ins[0] is not None:
            in_specs = ins[0].specs
            if _steps_analyzable(steps, in_specs):
                out = _walk_chain(o, steps, list(in_specs), report)
                if out is not None and isinstance(o.op, BatchedJittedFuse) \
                        and check_buckets:
                    for b in o.op.bucket_sizes:
                        if _walk_chain(o, steps, list(in_specs), report,
                                       bucket=b) is None:
                            break       # one bucket failure explains all
                if out is not None and len(out) == len(schema):
                    et.specs = tuple(out)
        elif isinstance(o.op, (ops.AnyOf, ops.Union)) and ins and \
                all(i is not None and i.specs is not None for i in ins):
            # pass-through ops: every input must agree; AnyOf/Union
            # schemas were already checked compatible by the typechecker
            first = ins[0].specs
            if all(_specs_eq(i.specs, first) for i in ins):
                et.specs = first
        types[o.op_id] = et
    return types, report


def _specs_eq(a, b) -> bool:
    if a is None or b is None or len(a) != len(b):
        return False
    for x, y in zip(a, b):
        if x is None or y is None:
            if x is not y:
                return False
            continue
        if tuple(x.shape) != tuple(y.shape) or \
                np.dtype(x.dtype) != np.dtype(y.dtype):
            return False
    return True


def edge_signature(types: Dict[int, EdgeType]) -> Dict[int, Tuple]:
    """A comparable per-op-id summary of inferred edge types — what the
    differential pass verifier (CF502) asserts every pass preserves."""
    out: Dict[int, Tuple] = {}
    for op_id, et in types.items():
        cols = tuple((name, getattr(t, "__name__", str(t)))
                     for name, t in et.schema)
        out[op_id] = (cols, et.grouping)
    return out
