"""Reduction of a profiler trace to the per-layer metrics and the breakdown.

The harness wraps its window in ``bench.window`` and its calls into the
program in ``bench.solve``, ``bench.submit``, ``bench.drain``,
``bench.wait_arrival`` and ``bench.check``
(``jax.profiler.TraceAnnotation``), so they land in the
trace on the same clock as the device's operations. Device operations are
the events of the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane.

    python3 bench/trace.py <profile dir>   # print planes, lines and spans
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
DEVICE_LINE = "XLA Ops"
SPANS = ("bench.window", "bench.solve", "bench.submit", "bench.drain",
         "bench.wait_arrival", "bench.check")
TOP = 10


@dataclasses.dataclass
class Trace:
    """Device operations and harness spans, in ns on the trace's clock."""

    ops: dict      # device index -> [(start, end, name)], sorted by start
    spans: list    # [(name, start, end)] of the harness's spans
    window: tuple  # (start, end) of ``bench.window``

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(profile_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def load(profile_dir: str) -> Trace | None:
    """The trace under ``profile_dir``; None without a ``bench.window``."""
    from jax.profiler import ProfileData
    path = find_xplane(profile_dir)
    if path is None:
        return None
    ops, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == DEVICE_LINE:
                ops.setdefault(int(dev.group(1)), []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events)
            elif dev is None:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in SPANS)
    windows = [(s, e) for name, s, e in spans if name == "bench.window"]
    if not windows:
        return None
    for evs in ops.values():
        evs.sort()
    return Trace(ops=ops, spans=spans, window=windows[0])


def merge(intervals) -> np.ndarray:
    """Union of ``(start, end, ...)`` intervals as sorted ``[n, 2]`` rows."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64).reshape(-1, 2)


def covered(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the merged intervals cover."""
    span = np.minimum(merged[:, 1], hi) - np.maximum(merged[:, 0], lo)
    return float(np.clip(span, 0.0, None).sum())


def busy_s(trace: Trace) -> float | None:
    """Seconds of the window in which an operation ran, averaged over the
    devices that ran any; None where the trace holds no device."""
    if not trace.ops:
        return None
    lo, hi = trace.window
    per = [covered(merge(evs), lo, hi) for evs in trace.ops.values()]
    return sum(per) / len(per) * 1e-9


def idle_share(trace: Trace | None) -> float | None:
    """Percent of the window in which no operation ran on the device."""
    busy = None if trace is None else busy_s(trace)
    if busy is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / trace.window_s)


def host_gap(trace: Trace | None, span: str) -> float | None:
    """Percent of the time inside the ``span`` spans of the window in which
    no operation ran on the device."""
    if trace is None or not trace.ops:
        return None
    lo, hi = trace.window
    inside = [(max(s, lo), min(e, hi)) for name, s, e in trace.spans
              if name == span and e > lo and s < hi]
    total = sum(e - s for s, e in inside)
    if total <= 0:
        return None
    per = []
    for evs in trace.ops.values():
        merged = merge(evs)
        per.append(sum(covered(merged, s, e) for s, e in inside))
    return 100.0 * (1.0 - sum(per) / len(per) / total)


def label(trace: Trace, t: float) -> str:
    """The innermost harness span open at ``t``."""
    best = None
    for name, s, e in trace.spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "none"


def breakdown(trace: Trace | None) -> dict | None:
    """The device operations that took most time (a ``while`` counts the
    operations of its body too), and the longest idle gaps of the window,
    each named by the harness span open over it."""
    if trace is None or not trace.ops:
        return None
    lo, hi = trace.window
    dev = min(trace.ops)
    per_op = {}
    for s, e, text in trace.ops[dev]:
        d = min(e, hi) - max(s, lo)
        name = text.split(" = ", 1)[0]     # "%fusion.12 = f32[...] fusion(..."
        if d > 0:
            per_op[name] = per_op.get(name, 0.0) + d * 1e-9
    gaps, t = [], lo
    for s, e in [*merge(trace.ops[dev]).tolist(), [hi, hi]]:
        s, e = max(s, lo), min(e, hi)
        if s > t:
            gaps.append((s - t, t))
        t = max(t, e)
    gaps.sort(reverse=True)
    return {
        "device_ops": [[n, v] for n, v in sorted(per_op.items(),
                                                 key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label(trace, t0 + d / 2), d * 1e-9]
                      for d, t0 in gaps[:TOP]],
    }


def describe(profile_dir: str) -> None:
    """Print every plane and line with its event count, a few event names
    and the time span, to check by hand what the reduction reads."""
    from jax.profiler import ProfileData
    path = find_xplane(profile_dir)
    print("xplane", path)
    for plane in ProfileData.from_file(path).planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            t0 = min((e.start_ns for e in evs), default=0)
            t1 = max((e.start_ns + e.duration_ns for e in evs), default=0)
            print(f"  line {line.name!r}: {len(evs)} events, "
                  f"{len(names)} names, [{t0:.0f}, {t1:.0f}] ns:",
                  names[:8])


if __name__ == "__main__":
    describe(sys.argv[1])
