"""The program's regions on a profiler trace, matched to the device.

The serving program marks its host work with ``repro.<kind>`` annotations
(``repro.obs.region``): ``repro.call``, ``repro.flush``, ``repro.exec``,
``repro.dispatch``, ``repro.stack``, ``repro.gather``, ``repro.demux``.
Each carries ``perf_ns``, its start on the program's own clock.  The
stage functions run under ``jax.named_scope("prefill" | "decode" |
"logits")``, which the device's op metadata keeps as ``tf_op``.  This
module reads, from the same loaded trace as ``devtrace.reduce``:

* dispatch -> run: each ``repro.dispatch`` region that ends inside the
  window, matched to the run of its chain program
  (``readers.CHAIN_PROGRAM``) on the device, among the runs wholly inside
  the window that the host saw complete (``CompleteCallbacks``: a run the
  profiler's stop cut short has none); a dispatch whose run ends later
  goes unmatched.  By run id where the trace
  has one on both sides (the host's ``DoEnqueueProgram`` the region
  caused, inside it or at the far end of a flow leaving it, and the
  device's program run); else in dispatch order on the one device
  stream.  Per match, the queue (region end -> program start) and
  the run (program start -> end).  The device clock can read early
  against the host's: where run ids match, device times are moved late
  by the least amount that starts no program before its enqueue
  (``device_lead_ms``);
* stage shares: device time of the operations under each stage scope,
  each instant counted once (a loop and its body overlap), over the
  device time of the chain's programs that lie wholly inside the window;
* idle gaps: the same gaps ``devtrace.reduce`` finds, each labelled with
  the ``repro.*`` region open over most of it, at least half (ties to
  the innermost), or ``none``;
* the clock offset: the median of (profiler start - ``perf_ns``) over
  the regions, which maps the program's retroactive spans onto the trace.

The stage names live in the event metadata's ``tf_op``, which
``ProfileData`` does not expose; ``op_scopes`` reads them from the
serialized trace.  An executable loaded from a compile cache filled
before the scopes existed keeps its old metadata (the scopes do not
enter the cache key): its operations carry no stage, and the shares are
left out.
"""
import bisect
import collections
import re
import statistics
from typing import Dict, List, Optional, Tuple

from bench import devtrace, readers

REGION = "repro."
DISPATCH = "repro.dispatch"
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"
STAGES = ("prefill", "decode", "logits")
_STAGE_SEG = re.compile(r"(?:\w+\()*(%s)\)*" % "|".join(STAGES))


# -- the stage scopes, from the serialized XSpace ----------------------------

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes):
    """``(field, wire type, value)`` of one protobuf message; a
    length-delimited value is its bytes, the others their integer."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = int.from_bytes(b[i:i + 8], "little"), i + 8
        elif wt == 5:
            v, i = int.from_bytes(b[i:i + 4], "little"), i + 4
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield f, wt, v


def _map_values(entry: bytes) -> bytes:
    return next((v for f, _, v in _fields(entry) if f == 2), b"")


def op_scopes(xspace: bytes) -> Dict[str, str]:
    """Device operation name -> its ``tf_op`` metadata (the JAX name
    stack), for every device plane of a serialized XSpace.  Keyed by the
    event metadata's name and by its display name; a name that two
    entries give different ``tf_op`` values is left out."""
    out: Dict[str, str] = {}
    clash = set()
    for f, _, plane in _fields(xspace):
        if f != 1:                                  # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for pf, _, v in _fields(plane):
            if pf == 2:                             # XPlane.name
                name = v.decode("utf-8", "replace")
            elif pf == 4:                           # event_metadata map
                events.append(_map_values(v))
            elif pf == 5:                           # stat_metadata map
                sm = dict((k, x) for k, _, x in _fields(_map_values(v))
                          if k in (1, 2))
                stat_names[sm.get(1, 0)] = sm.get(2, b"").decode()
        if not devtrace.DEVICE_PLANE.match(name):
            continue
        for em in events:
            keys, tf_op = [], None
            for ef, _, v in _fields(em):
                if ef in (2, 4) and v:              # name, display_name
                    keys.append(v.decode("utf-8", "replace"))
                elif ef == 5:                       # XEventMetadata.stats
                    stat = {sf: sv for sf, _, sv in _fields(v)}
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    if 5 in stat:                   # str_value
                        tf_op = stat[5].decode("utf-8", "replace")
                    elif 7 in stat:                 # ref_value
                        tf_op = stat_names.get(stat[7])
            if tf_op is None:
                continue
            for k in keys:
                if out.get(k, tf_op) != tf_op:
                    clash.add(k)
                out[k] = tf_op
    for k in clash:
        del out[k]
    return out


def stage_of(tf_op: Optional[str]) -> Optional[str]:
    """The stage a name stack puts an operation under: a segment
    ``prefill`` / ``decode`` / ``logits``, bare or inside a transform
    (``vmap(decode)``)."""
    for seg in (tf_op or "").split("/"):
        m = _STAGE_SEG.fullmatch(seg)
        if m:
            return m.group(1)
    return None


def load(path: str):
    """The trace at ``path`` as ``(ProfileData, op_scopes)``."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    return ProfileData.from_serialized_xspace(raw), op_scopes(raw)


# -- the reduction -----------------------------------------------------------

def _stats(e) -> dict:
    return {k: v for k, v in e.stats}


def _depths(regions) -> List[int]:
    """Per region, how many regions of its own thread enclose it."""
    depth = [0] * len(regions)
    by_line = collections.defaultdict(list)
    for i, r in enumerate(regions):
        by_line[r["line"]].append(i)
    for idx in by_line.values():
        idx.sort(key=lambda i: (regions[i]["start"], -regions[i]["end"]))
        open_: List[int] = []
        for i in idx:
            while open_ and regions[open_[-1]]["end"] < regions[i]["end"]:
                open_.pop()
            depth[i] = len(open_)
            open_.append(i)
    return depth


def _enqueues_of(d, host) -> List[Tuple[float, int]]:
    """``(start, run id)`` of the program enqueues dispatch region ``d``
    caused: those inside it (a dispatch enqueued on its own thread), and
    those inside the far end of a flow that leaves it (a dispatch handed
    to the runtime's own thread, as the TPU client does)."""
    ends = [(None, d["start"], d["end"])]
    i = bisect.bisect_left(host["producers"], (d["start"],))
    while i < len(host["producers"]) and \
            host["producers"][i][0] <= d["end"]:
        far = host["consumers"].get(host["producers"][i][1])
        if far is not None:
            ends.append(far)
        i += 1
    out = []
    for line, a, b in ends:
        j = bisect.bisect_left(host["enqueues"], (a,))
        while j < len(host["enqueues"]) and host["enqueues"][j][0] <= b:
            t, ln, run_id = host["enqueues"][j]
            if line is None or ln == line:
                out.append((t, run_id))
            j += 1
    return sorted(out)


def _completed(modules, done) -> List[dict]:
    """The program runs of one device up to the last whose completion
    the host recorded (the stream runs in order; a run still going when
    the profiler stopped ends short in the trace).  All of them where
    the trace records no completions."""
    if not done:
        return modules
    runs = sorted(modules, key=lambda m: m["start"])
    last = max((i for i, m in enumerate(runs) if m["run_id"] in done),
               default=-1)
    return runs[:last + 1]


def _match_by_run_id(dispatches, host, runs) -> Dict[int, dict]:
    """Dispatch index -> chain run: the earliest not yet taken enqueue
    of a chain run that the dispatch caused."""
    by_id = {r["run_id"]: r for r in runs if r.get("run_id") is not None}
    out: Dict[int, dict] = {}
    taken = set()
    for i in sorted(range(len(dispatches)),
                    key=lambda i: dispatches[i]["start"]):
        for t, run_id in _enqueues_of(dispatches[i], host):
            if run_id in by_id and run_id not in taken:
                taken.add(run_id)
                out[i] = dict(by_id[run_id], enqueue=t)
                break
    return out


def _match_in_order(dispatches, runs) -> Dict[int, dict]:
    """Dispatch index -> chain run on one device stream, first in first
    out: each dispatch takes the first unmatched run that ends after the
    dispatch region starts (the device clock may read early, so a start
    can precede its dispatch)."""
    order = sorted(range(len(dispatches)),
                   key=lambda i: dispatches[i]["start"])
    runs = sorted(runs, key=lambda r: r["start"])
    out: Dict[int, dict] = {}
    k = 0
    for i in order:
        while k < len(runs) and runs[k]["end"] < dispatches[i]["start"]:
            k += 1
        if k == len(runs):
            break
        out[i] = runs[k]
        k += 1
    return out


def reduce(profile, scopes: Optional[Dict[str, str]] = None) -> Dict:
    """Dispatch-to-run matches, stage shares, program-labelled idle gaps
    and the clock offset of a loaded trace (see the module docstring).
    ``scopes`` is ``op_scopes`` of the same trace; without it the stage
    shares are left out."""
    regions, window, devices = [], [], []
    # the host's program enqueues (start, line, run id), and its flows:
    # (start, flow id) where one starts, flow id -> (line, start, end)
    # of the event where it ends
    host = {"enqueues": [], "producers": [], "consumers": {}, "done": set()}
    for p, plane in enumerate(profile.planes):
        if devtrace.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            mods = [dict(name=e.name, start=e.start_ns, end=e.end_ns,
                         run_id=_stats(e).get("run_id"))
                    for e in (lines[devtrace.MODULES_LINE].events
                              if devtrace.MODULES_LINE in lines else [])]
            # the operations devtrace.reduce reads: the per-op line, or
            # without one every device event that is not a program run
            op_lines = ([lines[devtrace.OPS_LINE]]
                        if devtrace.OPS_LINE in lines else
                        [ln for n, ln in lines.items()
                         if n != devtrace.MODULES_LINE])
            ops = [(e.name, e.start_ns, e.end_ns)
                   for ln in op_lines for e in ln.events]
            devices.append({"modules": mods, "ops": ops})
        elif plane.name.startswith("/host:"):
            for k, ln in enumerate(plane.lines):
                for e in ln.events:
                    st = _stats(e)
                    if "_p" in st:
                        host["producers"].append((e.start_ns, st["_p"]))
                    if "_c" in st:
                        host["consumers"][st["_c"]] = ((p, k), e.start_ns,
                                                       e.end_ns)
                    if e.name.startswith(REGION):
                        regions.append(dict(kind=e.name, line=(p, k),
                                            start=e.start_ns,
                                            end=e.end_ns, args=st))
                    elif e.name == ENQUEUE and "run_id" in st:
                        host["enqueues"].append((e.start_ns, (p, k),
                                                 st["run_id"]))
                    elif e.name == COMPLETE and "run_id" in st:
                        host["done"].add(st["run_id"])
                    elif e.name == devtrace.WINDOW_SPAN:
                        window.append((e.start_ns, e.end_ns))
    host["producers"].sort()
    host["enqueues"].sort()
    if not devices:
        raise ValueError("trace holds no TPU device plane")
    all_ops = [ev for d in devices for ev in d["ops"]]
    lo = min((e[1] for e in all_ops), default=0.0)
    hi = max((e[2] for e in all_ops), default=0.0)
    if window and window[0][0] < hi and window[0][1] > lo:
        lo, hi = window[0]

    # dispatch -> run, over the chain's runs wholly inside the window
    # that the host saw complete
    runs = [m for d in devices for m in _completed(d["modules"], host["done"])
            if readers.CHAIN_PROGRAM in m["name"]
            and m["start"] >= lo and m["end"] <= hi]
    dispatches = [r for r in regions
                  if r["kind"] == DISPATCH and lo <= r["end"] <= hi]
    matched = _match_by_run_id(dispatches, host, runs)
    how = "run_id"
    if not matched:
        matched, how = _match_in_order(dispatches, runs), "order"
    lead = max([m["enqueue"] - m["start"] for m in matched.values()
                if "enqueue" in m] + [0.0])
    queue_ms, run_ms, paths = [], [], collections.Counter()
    for i, m in sorted(matched.items()):
        queue_ms.append((m["start"] + lead - dispatches[i]["end"]) / 1e6)
        run_ms.append((m["end"] - m["start"]) / 1e6)
        paths[dispatches[i]["args"].get("path")] += 1

    # stage shares over the same runs
    chain_ivs = [(m["start"], m["end"]) for m in runs]
    chain_s = sum(b - a for a, b in chain_ivs) / 1e9
    stage_s: Optional[Dict[str, float]] = None
    if scopes:
        per: Dict[str, list] = collections.defaultdict(list)
        for d in devices:
            for name, a, b in d["ops"]:
                stage = stage_of(scopes.get(name))
                if stage is not None:
                    per[stage].append((a, b))
        if per:
            stage_s = {}
            chain_u = devtrace._union(chain_ivs)
            for stage in STAGES:
                u = devtrace._union(per.get(stage, []))
                stage_s[stage] = sum(
                    min(b, cb) - max(a, ca) for a, b in u
                    for ca, cb in chain_u if b > ca and a < cb) / 1e9

    # idle gaps, as devtrace.reduce finds them, labelled by region
    gaps = []
    for d in devices:
        merged = devtrace._union(devtrace._clip(
            [(a, b) for _, a, b in d["ops"]], lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    depth = _depths(regions)

    def label(a, b):
        best, key = "none", ((b - a) / 2, -1)
        for r, dep in zip(regions, depth):
            c = min(b, r["end"]) - max(a, r["start"])
            if (c, dep) >= key:
                best, key = r["kind"], (c, dep)
        return best

    return {
        "window_s": (hi - lo) / 1e9,
        "regions": dict(collections.Counter(r["kind"] for r in regions)),
        "clock_offset_ns": statistics.median(
            r["start"] - r["args"]["perf_ns"] for r in regions
            if "perf_ns" in r["args"]) if regions else None,
        "dispatches": len(dispatches),
        "matched": len(matched),
        "match": how,
        "paths": dict(paths),
        "device_lead_ms": lead / 1e6,
        "queue_ms": queue_ms,
        "run_ms": run_ms,
        "queue_p50_ms": statistics.median(queue_ms) if queue_ms else None,
        "run_p50_ms": statistics.median(run_ms) if run_ms else None,
        "chain_runs": len(chain_ivs),
        "chain_s": chain_s,
        "stage_s": stage_s,
        "decode_share": (readers.share(stage_s["decode"], chain_s)
                         if stage_s is not None else None),
        "idle_gaps_program": [[label(a, b), (b - a) / 1e9]
                              for a, b in gaps[:10]],
    }
