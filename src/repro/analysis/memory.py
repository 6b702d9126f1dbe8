"""Static device-memory footprint bound (CF301).

``warm_deployment`` walks every batch-lowered chain at every padding
bucket — including the covering bucket a full batcher merge pads to —
so the first warm materializes each chain's live columns at the LARGEST
bucket.  This module bounds that footprint statically (live columns ×
bucket cap × dtype itemsize, walked step by step through each fused
chain with ``jax.eval_shape``) and diagnoses chains whose peak exceeds
a configurable budget *before* the warm OOMs the device.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from repro.analysis.diagnostics import Diagnostic
from repro.analysis.infer import EdgeType, _chain_of, _eval_step
from repro.core.ir import PhysicalPlan
from repro.core.lowering import BatchedJittedFuse, bucket_rows


def _row_bytes(specs) -> int:
    total = 0
    for s in specs:
        if s is None:
            return -1
        total += int(np.prod(s.shape, dtype=np.int64) *
                     np.dtype(s.dtype).itemsize)
    return total


def chain_peak_row_bytes(steps, in_specs) -> Optional[int]:
    """Peak live bytes per ROW through a fused chain: at every step the
    step's inputs and outputs are live simultaneously (donation can at
    best alias one of them — we bound, not model, the allocator)."""
    cur = list(in_specs)
    if any(s is None for s in cur):
        return None
    peak = _row_bytes(cur)
    for step in steps:
        try:
            nxt = _eval_step(step, cur)
        except Exception:
            return None         # the shape checks own that failure
        live = _row_bytes(cur) + _row_bytes(nxt)
        peak = max(peak, live)
        cur = nxt
    return peak


def footprint_diagnostics(plan: PhysicalPlan, types: Dict[int, EdgeType],
                          *, budget_bytes: Optional[int],
                          max_batch_of=None) -> List[Diagnostic]:
    """CF301 for every device-resident batch-lowered chain.  ``types``
    must carry inferred input specs (from :func:`repro.analysis.infer`);
    chains without specs are skipped.  ``max_batch_of(op_id)`` supplies
    the effective merge cap (defaults to 1 = no batching)."""
    out: List[Diagnostic] = []
    if budget_bytes is None or budget_bytes <= 0:
        return out
    for o in plan.ops:
        op = o.op
        if not isinstance(op, BatchedJittedFuse):
            continue
        steps = _chain_of(op)
        if steps is None or len(o.inputs) != 1:
            continue
        et = types.get(o.inputs[0])
        if et is None or et.specs is None:
            continue
        per_row = chain_peak_row_bytes(steps, list(et.specs))
        if per_row is None or per_row < 0:
            continue
        mb = int(max_batch_of(o.op_id)) if max_batch_of is not None else 1
        sizes = set(op.bucket_sizes or (1,))
        if mb > 1:
            sizes.add(bucket_rows(mb, op.bucket_sizes))
        cap = max(sizes)
        peak = per_row * cap
        if peak > budget_bytes:
            out.append(Diagnostic(
                "CF301",
                f"op {o.op_id} ({op.name}) peaks at "
                f"~{peak / 2**20:.1f} MiB on device at bucket {cap} "
                f"({per_row / 2**20:.3f} MiB/row), over the "
                f"{budget_bytes / 2**20:.1f} MiB budget — "
                f"warm_deployment would OOM on first warm",
                op_id=o.op_id,
                hint="shrink the bucket table / max_batch, split the "
                     "chain, or raise the device-memory budget"))
    return out
