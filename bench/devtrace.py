"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData`` alone.  Device planes are
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation run and the ``XLA Modules`` line one per program run.  The
benchmark's own host spans (``bench.*``, from ``TraceAnnotation``) lie on
the host plane, on the same clock.

* window: the ``bench.traced`` host span when present, else the extent of
  the device events;
* busy: the union of the operation intervals inside the window, averaged
  over the devices;
* idle gaps: the holes in that union, each labelled with the ``bench.*``
  host span that overlaps it most (``none`` when no such span does);
* device operations: total time per operation name;
* programs: runs and total time per program name, counting only runs
  that lie wholly inside the window.
"""
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced"


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def _short(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the device
    planes name an operation by its whole HLO instruction."""
    if name.startswith("%"):
        return name[1:].split(" = ", 1)[0]
    return name


def _events(line) -> List[Tuple[str, float, float]]:
    return [(_short(e.name), e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce(profile, top: int = 10) -> Dict:
    """Busy time, window, top operations, longest labelled idle gaps and
    per-program runs of a loaded trace (see the module docstring)."""
    host_spans = []
    devices = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host_spans += [e for e in _events(ln)
                               if e[0].startswith("bench.")]
    if not devices:
        raise ValueError("trace holds no TPU device plane")
    for d in devices:
        if OPS_LINE not in d:       # no per-op line: every device event
            d[OPS_LINE] = [e for name, evs in d.items()
                           if name != MODULES_LINE for e in evs]
    all_ops = [ev for d in devices for ev in d[OPS_LINE]]
    if not all_ops:
        raise ValueError("trace holds no device operation")
    lo = min(e[1] for e in all_ops)
    hi = max(e[2] for e in all_ops)
    window = [s for s in host_spans if s[0] == WINDOW_SPAN]
    if window and window[0][1] < hi and window[0][2] > lo:
        lo, hi = window[0][1], window[0][2]
    labels = [s for s in host_spans if s[0] != WINDOW_SPAN]

    busy_ns = 0.0
    gaps = []
    op_time: Dict[str, float] = collections.defaultdict(float)
    programs: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0, 0.0])
    for d in devices:
        ops = _clip([(a, b) for _, a, b in d.get(OPS_LINE, [])], lo, hi)
        merged = _union(ops)
        busy_ns += sum(b - a for a, b in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
        for name, a, b in d.get(OPS_LINE, []):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                op_time[name] += b - a
        for name, a, b in d.get(MODULES_LINE, []):
            if a >= lo and b <= hi:
                programs[name][0] += 1
                programs[name][1] += (b - a) / 1e9
    n = len(devices)

    def label(a, b):
        best, cover = "none", 0.0
        for name, s, e in labels:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = name, c
        return best

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
        "programs": {k: {"runs": v[0], "seconds": v[1]}
                     for k, v in programs.items()},
    }
