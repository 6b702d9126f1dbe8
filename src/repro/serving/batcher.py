"""Request batcher — the paper's Batching optimization (§4, Fig 8).

Collects individual requests into one batched model invocation (pad to the
batch bucket), runs a single jitted call, and demultiplexes the results.
Used by the runtime's batch-aware executor; also usable standalone.

Deadline awareness (overload protection): items may carry an absolute
``deadline_t``.  The flush loop orders its backlog earliest-deadline-first
(plain FIFO when no item has a deadline, so the steady-state path is
untouched), and items whose deadline has already passed are *expired*
before dispatch — they fail fast with a typed
:class:`~repro.serving.admission.DeadlineExceeded` instead of occupying
batch slots, and ``on_drop`` + the ``expired`` counter surface every such
decision to the runtime's metrics.

Device gate: a batcher in front of a program that runs on an accelerator
may be given a :class:`DeviceGate`.  Its flush thread then collects the
next batch only once the device has finished the last one, so requests
that arrive meanwhile wait in this queue, where they merge, and not one
by one in the device's program stream.
"""
from __future__ import annotations

import itertools
import threading
import queue
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.obs.trace import region
from repro.serving.admission import DeadlineExceeded


#: queued by close() to wake the batch loop out of its poll immediately —
#: without it, close() blocks its caller (possibly an executor callback
#: thread on the serving path) for up to the full poll timeout
_WAKE = object()


class BatchItem:
    __slots__ = ("args", "event", "result", "error", "enqueue_t",
                 "deadline_t", "done")

    def __init__(self, args, deadline_t: Optional[float] = None):
        self.args = args
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.enqueue_t = time.perf_counter()
        # absolute perf_counter time after which dispatching is pointless
        self.deadline_t = deadline_t
        # completion is idempotent: exactly ONE path (flush, expiry, close
        # drain, call-timeout) decrements the accepted-minus-completed
        # counter, whichever claims the item first
        self.done = False


class DeviceGate:
    """Holds a batcher's next batch back while the device runs its last.

    The batch fn takes a slot with :meth:`hold` as it hands a batch to
    the device; the batch's completion gives the slot back with
    :meth:`release` on every path (result, error, expiry, exhausted
    retries), passing ``ready``: a callable that blocks until the
    batch's device arrays are computed and says whether it had to.  The
    flush thread calls :meth:`wait` before it collects a batch: it blocks
    while ``slots()`` batches are held, then runs the released batches'
    ``ready`` itself, so the executor thread that released them never
    waits on the device.  A hold lapses at its ``until`` (the batch's
    deadline, else ``LIMIT_S`` after the hold): a lost completion must
    not wedge the node."""

    #: how long a hold without a deadline may keep the next batch back
    LIMIT_S = 30.0

    def __init__(self, node: str, slots: Callable[[], int]):
        self.node = node            # names the ``repro.gate`` region
        self.slots = slots          # batches the device may run at once
        self._cv = threading.Condition()
        self._held: Dict[int, float] = {}     # token -> when it lapses
        self._ready: List[Callable[[], bool]] = []
        self._tokens = itertools.count()
        self._open = False

    def hold(self, until: Optional[float] = None) -> int:
        """Take a slot for a batch about to reach the device; returns the
        token its completion passes to :meth:`release`."""
        with self._cv:
            tok = next(self._tokens)
            if not self._open:
                self._held[tok] = (until if until is not None
                                   else time.perf_counter() + self.LIMIT_S)
            return tok

    def release(self, token: int,
                ready: Optional[Callable[[], bool]] = None) -> None:
        """Give back ``token``'s slot; a no-op once it lapsed or was
        released."""
        with self._cv:
            if self._held.pop(token, None) is None:
                return
            if ready is not None:
                self._ready.append(ready)
            self._cv.notify_all()

    def open(self) -> None:
        """Hold nothing from now on (the batcher is closing)."""
        with self._cv:
            self._open = True
            self._held.clear()
            self._ready.clear()
            self._cv.notify_all()

    def in_flight(self) -> int:
        """Batches not yet known to have finished on the device."""
        with self._cv:
            return len(self._held) + len(self._ready)

    def wait(self) -> Tuple[bool, bool]:
        """Block until a slot is free and every batch released since the
        last wait has finished on the device.  Returns ``(in_flight,
        lapsed)``: whether a batch was still running, and whether a hold
        lapsed."""
        in_flight = lapsed = False
        with self._cv:
            while len(self._held) >= max(1, self.slots()):
                in_flight = True
                tok = min(self._held, key=self._held.get)
                left = self._held[tok] - time.perf_counter()
                if left <= 0:
                    del self._held[tok]
                    lapsed = True
                else:
                    self._cv.wait(left)
            ready, self._ready = self._ready, []
        for r in ready:
            in_flight = r() or in_flight
        return in_flight, lapsed


class Batcher:
    """Micro-batching queue in front of a batched function.

    ``fn`` maps a list of per-request arg dicts to a list of results (it is
    responsible for stacking/padding).  ``max_batch`` bounds the bucket
    (paper default: 10); ``max_wait_ms`` bounds queueing delay.

    A batch takes every item already queued when it is collected, up to
    ``max_batch``; the wait window only governs arrivals not yet queued.
    The window is *adaptive*: an EWMA of recent inter-arrival gaps
    decides how much of ``max_wait`` is actually worth spending.  Under
    dense traffic (gaps well inside the window) the full window is used and
    requests coalesce; under sparse traffic the wait shrinks toward zero —
    a lone request should not sit out the whole window when the expected
    next arrival lies beyond it.  ``adaptive_wait=False`` restores the
    fixed-deadline behavior.

    ``gate``, when the runtime sets one (a :class:`DeviceGate`), holds
    each batch back until the device has finished the batch before it;
    ``gate_waits`` counts the batches that found it still running and
    ``gate_wait_s`` the seconds they waited, ``gate_lapses`` the holds
    that ran out before their completion released them.
    """

    #: EWMA smoothing for inter-arrival gaps.
    GAP_ALPHA = 0.3

    def __init__(self, fn: Callable[[List[Any]], List[Any]], *,
                 max_batch: int = 10, max_wait_ms: float = 2.0,
                 adaptive_wait: bool = True,
                 on_drop: Optional[Callable[[Any, BaseException],
                                            None]] = None):
        self.fn = fn
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1000.0
        self.adaptive_wait = adaptive_wait
        # called (args, error) for items failed by close()'s drain: a
        # caller whose waiters are callbacks buried in ``args`` (the
        # runtime) would otherwise hang them — nobody waits on
        # ``item.event`` there, so the event alone reaches no one
        self.on_drop = on_drop
        self.q: "queue.Queue[BatchItem]" = queue.Queue()
        self._stop = False
        self._lock = threading.Lock()       # serializes submit vs close
        # items accepted but not yet completed (queued OR popped into an
        # in-progress flush).  ``q.empty()`` alone is NOT a drain signal:
        # the batch loop pops items before running fn, so the queue can be
        # empty while a flush still holds live requests
        self._pending = 0
        # items popped off the queue but deferred past a full flush (EDF
        # overflow): owned by the batch loop thread; close() drains it
        # after joining that thread
        self._backlog: List[BatchItem] = []
        self._gap_ewma: Optional[float] = None
        self._last_submit_t: Optional[float] = None
        #: items failed before dispatch because their deadline passed
        self.expired = 0
        #: batches whose members were EDF-reordered out of arrival order
        self.reorders = 0
        #: whether the batch currently being flushed was EDF-reordered —
        #: written by the flush thread just before it invokes ``fn``, read
        #: by the batch fn (same thread) to annotate the batch-level span
        self.last_reordered = False
        self.gate: Optional[DeviceGate] = None
        self.gate_waits = 0
        self.gate_wait_s = 0.0
        self.gate_lapses = 0
        #: seconds the batch being flushed waited at the gate (None when
        #: the device had finished): read by the batch fn, as above
        self.last_gate_wait_s: Optional[float] = None
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        self.batch_sizes: List[int] = []

    def _complete(self, item: BatchItem) -> bool:
        """Claim ``item``'s completion: True for exactly one caller.  The
        winner decrements the pending counter; losers must not touch the
        item's result/error."""
        with self._lock:
            if item.done:
                return False
            item.done = True
            self._pending -= 1
            return True

    def submit(self, args, deadline_t: Optional[float] = None) -> BatchItem:
        item = BatchItem(args, deadline_t)
        with self._lock:
            if self._stop:
                raise RuntimeError("batcher is closed")
            if self._last_submit_t is not None:
                # clamp the sample: beyond ~4 windows a gap is just "idle",
                # and folding a minutes-long pause into the EWMA would pin
                # the wait at zero for dozens of requests into the next
                # dense burst (clamped, recovery takes ~3 samples)
                gap = min(item.enqueue_t - self._last_submit_t,
                          4.0 * self.max_wait)
                self._gap_ewma = gap if self._gap_ewma is None else \
                    ((1.0 - self.GAP_ALPHA) * self._gap_ewma
                     + self.GAP_ALPHA * gap)
            self._last_submit_t = item.enqueue_t
            self._pending += 1
            self.q.put(item)
        return item

    def pending(self) -> int:
        """Live requests in this batcher: accepted and not yet completed
        (queued, mid-flush, or dispatched awaiting their callback).  The
        counter the accountancy tests reconcile against offered traffic —
        it must return to zero after every fault-recovery path."""
        with self._lock:
            return self._pending

    def quiescent(self) -> bool:
        """True when the batcher holds NO live requests: nothing queued
        *and* no flush in progress.  This is the drain signal retirement
        logic must use — ``q.empty()`` races with an active flush whose
        popped items are still being served."""
        with self._lock:
            return self._pending == 0

    def reconfigure(self, *, max_batch: Optional[int] = None,
                    max_wait_ms: Optional[float] = None) -> None:
        """Hot-apply new batching knobs (the SLO controller's safe config
        delta).  The batch loop reads ``max_batch``/``max_wait`` fresh on
        every iteration, so the change takes effect on the next batch —
        in-flight batches are untouched."""
        with self._lock:
            if max_batch is not None:
                self.max_batch = max(1, int(max_batch))
            if max_wait_ms is not None:
                self.max_wait = max(0.0, float(max_wait_ms)) / 1000.0

    def arrival_gap_s(self) -> Optional[float]:
        """The EWMA of recent inter-arrival gaps (None before 2 submits) —
        the controller's cheap read on how dense this node's traffic is."""
        with self._lock:
            return self._gap_ewma

    def effective_wait(self) -> float:
        """How long the batch loop holds a partial batch open for
        arrivals not yet queued (what is queued is taken at once).
        Arrivals expected WITHIN the window keep the full window (so every
        merge the fixed deadline achieved still happens); beyond it the
        wait shrinks linearly, reaching zero at twice the window — a lone
        request during sparse traffic fires immediately."""
        if not self.adaptive_wait:
            return self.max_wait
        with self._lock:
            gap = self._gap_ewma
        if gap is None or gap <= self.max_wait:
            return self.max_wait
        return max(0.0, 2.0 * self.max_wait - gap)

    def call(self, args, timeout: Optional[float] = 30.0,
             deadline_t: Optional[float] = None):
        item = self.submit(args, deadline_t)
        if not item.event.wait(timeout):
            if self._complete(item):
                # claimed: the flush loop will skip this item, and the
                # accepted-minus-completed counter stays honest — a timed
                # out call must never wedge quiescent()/retirement
                item.error = TimeoutError("batched call timed out")
                item.event.set()
                raise item.error
            # lost the race: the flush completed it concurrently with our
            # timeout — fall through to its real result
        if item.error is not None:
            raise item.error
        return item.result

    def _fail_undispatched(self, item: BatchItem, err: BaseException):
        """Fail an item that never reached a dispatch (expiry, close
        drain); no-op if another path already claimed it."""
        if not self._complete(item):
            return
        item.error = err
        item.event.set()
        if self.on_drop is not None:
            try:
                self.on_drop(item.args, err)
            except BaseException:
                pass

    def _pass_gate(self) -> None:
        """Wait at the device gate, if there is one, and count the wait
        when the device was still running the last batch."""
        self.last_gate_wait_s = None
        gate = self.gate
        if gate is None:
            return
        t0 = time.perf_counter()
        with region("gate", node=gate.node) as reg:
            if reg:
                reg.set_metadata(in_flight=gate.in_flight())
            in_flight, lapsed = gate.wait()
        self.gate_lapses += lapsed
        if in_flight:
            waited = time.perf_counter() - t0
            self.gate_waits += 1
            self.gate_wait_s += waited
            self.last_gate_wait_s = waited

    def _collect(self) -> List[BatchItem]:
        """One flush worth of items: the first arrival (or the deferred
        backlog); then, past the device gate, every item already queued;
        then arrivals within the adaptive window, held open only when
        there is no backlog.  Merged with the backlog, expired items
        failed, the rest EDF-ordered."""
        items: List[BatchItem] = []
        if not self._backlog:
            try:
                first = self.q.get(timeout=0.1)
            except queue.Empty:
                return []
            if first is _WAKE:
                return []                   # close() signal; re-check _stop
            items = [first]
        self._pass_gate()
        woke = False
        while len(items) + len(self._backlog) < self.max_batch:
            try:
                nxt = self.q.get_nowait()
            except queue.Empty:
                break
            if nxt is _WAKE:
                woke = True                 # flush what we hold, then exit
                break
            items.append(nxt)
        if not self._backlog and not woke:
            # no window with a backlog: deferred items already waited
            # one out
            deadline = time.perf_counter() + self.effective_wait()
            while len(items) < self.max_batch:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    nxt = self.q.get(timeout=remaining)
                except queue.Empty:
                    break
                if nxt is _WAKE:
                    break
                items.append(nxt)
        pool = self._backlog + items        # backlog first: it is older
        self._backlog = []
        now = time.perf_counter()
        live: List[BatchItem] = []
        for it in pool:
            if it.done:
                continue                    # call() timeout already claimed
            if it.deadline_t is not None and it.deadline_t <= now:
                self.expired += 1
                self._fail_undispatched(it, DeadlineExceeded(
                    "deadline passed before dispatch",
                    deadline_s=it.deadline_t))
            else:
                live.append(it)
        reordered = False
        if any(it.deadline_t is not None for it in live):
            # earliest deadline first; deadline-less items ride behind in
            # arrival order (sort is stable).  Plain FIFO traffic never
            # reaches this sort.
            before = list(live)
            live.sort(key=lambda it: (it.deadline_t is None,
                                      it.deadline_t or 0.0))
            reordered = live != before
            if reordered:
                self.reorders += 1
        self.last_reordered = reordered
        self._backlog = live[self.max_batch:]
        return live[:self.max_batch]

    def _loop(self):
        while not self._stop:
            items = self._collect()
            if not items:
                continue
            self.batch_sizes.append(len(items))
            try:
                results = self.fn([it.args for it in items])
                for it, r in zip(items, results):
                    it.result = r
            except BaseException as e:  # propagate to all waiters
                for it in items:
                    it.error = e
            for it in items:
                if self._complete(it):
                    it.event.set()

    def close(self):
        """Stop the batch thread and fail anything still queued.

        ``submit``/``close`` are serialized by ``_lock``: after close wins
        the race, concurrent submitters get an immediate ``RuntimeError``
        instead of a silently dropped item, and items enqueued before the
        close are drained with an error so no waiter sits out its full
        ``call`` timeout."""
        with self._lock:
            if self._stop:
                return
            self._stop = True
        if self.gate is not None:
            self.gate.open()
        # wake the loop out of its poll so the join below returns
        # promptly — close() may run on an executor callback thread (the
        # generation-drain path), where a poll-timeout-long block would
        # stall the serving hot path
        self.q.put(_WAKE)
        if threading.current_thread() is not self._thread:
            self._thread.join(timeout=1.0)
        # drain the EDF backlog as well as the queue: deferred items are
        # just as undispatched as queued ones
        leftovers, self._backlog = list(self._backlog), []
        while True:
            try:
                it = self.q.get_nowait()
            except queue.Empty:
                break
            if it is _WAKE:
                continue
            leftovers.append(it)
        for it in leftovers:
            self._fail_undispatched(
                it, RuntimeError("batcher closed before dispatch"))
