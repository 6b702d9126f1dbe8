"""Cloudflow's core data structures: a small in-memory relational Table,
plus its device-resident columnar twin (``DeviceTable``).

A Table has a *schema* (list of (name, type) column descriptors), an optional
*grouping column*, and rows.  Every row carries a hidden ``row_id`` assigned
at dataflow execution time which persists through the pipeline (paper §3.1)
and is the default join key.

A ``DeviceTable`` holds the same logical rows as columns — one accelerator
array per schema column, rows stacked along axis 0 — so a chain of lowered
GPU operators can hand whole batches from stage to stage without a host
round-trip: ONE host->device stack when the batch enters the device chain,
ONE device->host gather when it leaves.  Row identity (``row_ids``,
``groups``) stays on the host; row *liveness* is a boolean ``mask`` column
carried on the device, which is how fused Filter operators drop rows
without forcing a compaction (masked rows are compacted only at the
device->host boundary in ``host_rows``/``to_table``).
"""
from __future__ import annotations

import itertools
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.obs.trace import region

Schema = List[Tuple[str, type]]

_counter = itertools.count()


class Row:
    __slots__ = ("values", "row_id", "group")

    def __init__(self, values: Tuple[Any, ...], row_id: Optional[int] = None,
                 group: Any = None):
        self.values = tuple(values)
        self.row_id = row_id if row_id is not None else next(_counter)
        self.group = group

    def replace(self, values: Tuple[Any, ...], group=...) -> "Row":
        return Row(values, self.row_id,
                   self.group if group is ... else group)

    def __repr__(self):
        return f"Row(id={self.row_id}, {self.values!r})"


class Table:
    def __init__(self, schema: Schema, rows: Optional[Iterable] = None,
                 grouping: Optional[str] = None):
        self.schema: Schema = [(str(n), t) for n, t in schema]
        self.grouping = grouping
        self.rows: List[Row] = []
        if rows:
            for r in rows:
                self.insert(r)

    # -- construction -------------------------------------------------------
    def insert(self, values, group: Any = None) -> Row:
        if isinstance(values, Row):
            self.rows.append(values)
            return values
        if not isinstance(values, (tuple, list)):
            values = (values,)
        if len(values) != len(self.schema):
            raise ValueError(
                f"row arity {len(values)} != schema arity {len(self.schema)}")
        row = Row(tuple(values), group=group)
        self.rows.append(row)
        return row

    @property
    def columns(self) -> List[str]:
        return [n for n, _ in self.schema]

    def column_index(self, name: str) -> int:
        for i, (n, _) in enumerate(self.schema):
            if n == name:
                return i
        raise KeyError(f"no column {name!r} in {self.columns}")

    def column(self, name: str) -> List[Any]:
        i = self.column_index(name)
        return [r.values[i] for r in self.rows]

    def with_rows(self, rows: List[Row], grouping=...) -> "Table":
        t = Table(self.schema, grouping=self.grouping
                  if grouping is ... else grouping)
        t.rows = list(rows)
        return t

    # -- python sugar ---------------------------------------------------------
    def __len__(self):
        return len(self.rows)

    def __iter__(self):
        return iter(self.rows)

    def __repr__(self):
        g = f", grouped by {self.grouping!r}" if self.grouping else ""
        return (f"Table({self.columns}{g}, {len(self.rows)} rows)\n" +
                "\n".join(f"  {r}" for r in self.rows[:10]))

    def to_dicts(self) -> List[Dict[str, Any]]:
        return [dict(zip(self.columns, r.values)) for r in self.rows]

    @staticmethod
    def from_dicts(schema: Schema, dicts: Sequence[Dict[str, Any]]) -> "Table":
        t = Table(schema)
        for d in dicts:
            t.insert(tuple(d[n] for n, _ in schema))
        return t


def schema_compatible(a: Schema, b: Schema) -> bool:
    return len(a) == len(b) and all(ta == tb for (_, ta), (_, tb)
                                    in zip(a, b))


# ---------------------------------------------------------------------------
# device-resident columnar batches
# ---------------------------------------------------------------------------

#: process-wide host<->device copy accounting (read by benchmarks/tests):
#: a "stack" is one host->device columnar upload event, a "gather" one
#: device->host readback event.  Index uploads and mask bookkeeping (a few
#: bytes) are deliberately not counted — the counters track the bulk row
#: payload crossing the PCIe boundary.
HOST_COPIES: Dict[str, int] = {"stacks": 0, "gathers": 0}


def note_host_copy(kind: str) -> None:
    """Count one host<->device bulk copy ('stacks' or 'gathers')."""
    HOST_COPIES[kind] += 1


def reset_host_copies() -> None:
    HOST_COPIES["stacks"] = 0
    HOST_COPIES["gathers"] = 0


class DeviceTable:
    """A shape-uniform batch of rows living on the accelerator.

    ``columns[j]`` stacks column j of every row along axis 0, padded up to a
    bucketed capacity (``cap``); only the first ``nrows`` entries are
    logical rows, and of those only the ones whose ``mask`` entry is True
    (``mask is None`` means all live).  ``row_ids``/``groups`` keep per-row
    identity on the host so demultiplexing never needs device data.

    ``donatable=True`` marks a table whose buffers have no other live
    consumer — the executing chain may donate them to XLA
    (``donate_argnums``) so the output batch reuses the input allocation.
    Donated buffers are DELETED after the call; only ever set it on arrays
    this table exclusively owns.
    """

    __slots__ = ("schema", "grouping", "columns", "mask", "nrows",
                 "row_ids", "groups", "donatable")

    def __init__(self, schema: Schema, columns: Sequence[Any], nrows: int,
                 row_ids: Sequence[int], groups: Sequence[Any],
                 grouping: Optional[str] = None, mask: Any = None,
                 donatable: bool = False):
        self.schema: Schema = [(str(n), t) for n, t in schema]
        self.columns = list(columns)
        self.nrows = int(nrows)
        self.row_ids = list(row_ids)
        self.groups = list(groups)
        self.grouping = grouping
        self.mask = mask
        self.donatable = donatable

    # -- construction -------------------------------------------------------
    @staticmethod
    def from_columns(schema: Schema, host_cols: Sequence[Sequence[Any]],
                     row_ids: Sequence[int], groups: Sequence[Any],
                     pad_to: Optional[int] = None,
                     grouping: Optional[str] = None,
                     node: Optional[str] = None) -> "DeviceTable":
        """Build from per-column lists of per-row host (numpy) arrays: one
        ``np.stack`` memcpy + ONE device upload per column.  The row count
        is padded up to ``pad_to`` by repeating row 0 so device shapes stay
        bucket-sized; padding rows carry no mask entry — ``nrows`` bounds
        the live range.  ``node`` names the upload's ``repro.stack``
        region."""
        n = len(row_ids)
        cap = max(pad_to or n, n)
        columns = []
        with region("stack", node=node) as reg:
            for col in host_cols:
                col = list(col)
                stacked = np.stack(col + col[:1] * (cap - n)) if col else \
                    np.zeros((0,))
                columns.append(jnp.asarray(stacked))
            if reg:
                reg.set_metadata(bytes=sum(c.nbytes for c in columns))
        note_host_copy("stacks")
        return DeviceTable(schema, columns, n, row_ids, groups,
                           grouping=grouping, mask=None, donatable=True)

    @staticmethod
    def from_table(t: Table, pad_to: Optional[int] = None) -> "DeviceTable":
        """Stack a (shape-uniform) host table.  Raises ``ValueError`` when
        rows are ragged or values are not array-convertible — callers fall
        back to per-row execution."""
        arrs = [[np.asarray(v) for v in r.values] for r in t.rows]
        if arrs:
            key0 = [(a.shape, a.dtype) for a in arrs[0]]
            for row_arrs in arrs[1:]:
                if [(a.shape, a.dtype) for a in row_arrs] != key0:
                    raise ValueError("ragged rows cannot form a DeviceTable")
        host_cols = [[row_arrs[j] for row_arrs in arrs]
                     for j in range(len(t.schema))]
        return DeviceTable.from_columns(
            t.schema, host_cols, [r.row_id for r in t.rows],
            [r.group for r in t.rows], pad_to=pad_to, grouping=t.grouping)

    # -- accessors ----------------------------------------------------------
    def __len__(self) -> int:
        return self.nrows

    @property
    def cap(self) -> int:
        return int(self.columns[0].shape[0]) if self.columns else self.nrows

    @property
    def nbytes(self) -> int:
        return int(sum(getattr(c, "nbytes", 0) for c in self.columns))

    @property
    def column_names(self) -> List[str]:
        return [n for n, _ in self.schema]

    def column_index(self, name: str) -> int:
        for i, (n, _) in enumerate(self.schema):
            if n == name:
                return i
        raise KeyError(f"no column {name!r} in {self.column_names}")

    def __repr__(self):
        shapes = [tuple(getattr(c, "shape", ())) for c in self.columns]
        return (f"DeviceTable({self.column_names}, rows={self.nrows}"
                f"/cap={self.cap}, shapes={shapes}"
                f"{', masked' if self.mask is not None else ''})")

    # -- device-side row selection (no host copy) ----------------------------
    def take(self, positions: Sequence[int],
             pad_to: Optional[int] = None) -> "DeviceTable":
        """A new DeviceTable holding ``positions`` (indices < nrows), padded
        to ``pad_to``.  The gather runs on the device — no host round-trip
        beyond the tiny index/validity upload — so batcher demultiplexing
        can split a merged batch per request while staying device-resident."""
        pos = [int(p) for p in positions]
        k = len(pos)
        cap = max(pad_to or k, k)
        idx_host = np.asarray(pos + pos[:1] * (cap - k), np.int32)
        idx = jnp.asarray(idx_host)
        cols = [jnp.take(c, idx, axis=0) for c in self.columns]
        mask = None
        if self.mask is not None:
            mask = jnp.take(self.mask, idx, axis=0)
        if cap > k:
            valid = jnp.asarray(np.arange(cap) < k)
            mask = valid if mask is None else jnp.logical_and(mask, valid)
        return DeviceTable(self.schema, cols, k,
                           [self.row_ids[p] for p in pos],
                           [self.groups[p] for p in pos],
                           grouping=self.grouping, mask=mask, donatable=True)

    # -- device->host boundary ----------------------------------------------
    def host_rows(self, node: Optional[str] = None) -> List[Tuple[int, Row]]:
        """Materialize live rows as ``(position, Row)`` pairs with ONE
        device->host readback (a ``repro.gather`` region named after
        ``node``); masked-out (filtered) and padding rows are compacted
        away here — and only here."""
        payload = tuple(self.columns)
        if self.mask is not None:
            payload = payload + (self.mask,)
        with region("gather", node=node) as reg:
            if reg:
                reg.set_metadata(bytes=sum(a.nbytes for a in payload))
            host = jax.device_get(payload)
        note_host_copy("gathers")
        ncol = len(self.columns)
        mask_h = host[ncol] if self.mask is not None else None
        out: List[Tuple[int, Row]] = []
        for i in range(self.nrows):
            if mask_h is not None and not bool(mask_h[i]):
                continue
            out.append((i, Row(tuple(c[i] for c in host[:ncol]),
                               self.row_ids[i], self.groups[i])))
        return out

    def to_table(self, node: Optional[str] = None) -> Table:
        t = Table(self.schema, grouping=self.grouping)
        t.rows = [r for _, r in self.host_rows(node)]
        return t


#: the paper-facing name: a schema-tagged columnar batch (device-resident).
ColumnBatch = DeviceTable
