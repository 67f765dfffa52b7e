"""The engine's round phases in a ``jax.profiler`` trace, interval by
interval, and the device's idle time by the span the host was in.

``xplane.reduce`` gives each ``bench.*`` or ``serve.*`` annotation its
device busy time summed over its intervals, and labels the longest idle
gaps.  This module reads the same trace for what the engine's own spans
(``serve.step`` > ``serve.schedule``, ``serve.upload``,
``serve.dispatch``, ``serve.result_wait``, ``serve.record``) need beyond
that: host and device-busy seconds of every interval inside the window,
and every idle nanosecond of the first chip booked to the innermost
annotation covering it.  Run it on a trace kept by
``run.py --trace 1 --keep-trace <path>``:

    python3 benchmarks/chip/spans.py <path>

It prints one JSON object: the round's phases in milliseconds
(``summary``) and idle seconds by innermost label (``idle_by_label``).
"""
from __future__ import annotations

import argparse
import heapq
import json
import statistics
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))

import xplane  # noqa: E402

OUTSIDE = "outside any annotation"


@dataclass
class Spans:
    window_s: float
    idle_s: float                          # of the first chip
    # host annotation -> [(host seconds, device busy seconds)], one pair
    # per interval inside the window; busy is the mean over the chips
    intervals: Dict[str, List[Tuple[float, float]]] = field(
        default_factory=dict)
    # innermost host annotation (or OUTSIDE) -> idle seconds of the
    # first chip; the values sum to ``idle_s``
    idle_by_label: Dict[str, float] = field(default_factory=dict)

    def pairs(self, label: str) -> Optional[List[Tuple[float, float]]]:
        """(host seconds, device busy seconds) of each interval of the
        host annotation ``label``; None when it never ran there."""
        return self.intervals.get(label) or None

    def host_ms_median(self, label: str) -> Optional[float]:
        """Median host milliseconds of one interval of ``label``."""
        pairs = self.pairs(label)
        if pairs is None:
            return None
        return 1000.0 * statistics.median(host for host, _ in pairs)

    def idle_ms_median(self, label: str) -> Optional[float]:
        """Median over the intervals of ``label`` of the milliseconds
        the device sat idle inside one."""
        pairs = self.pairs(label)
        if pairs is None:
            return None
        return 1000.0 * statistics.median(h - b for h, b in pairs)

    def idle_ms_per(self, label: str, per: str) -> Optional[float]:
        """Milliseconds the device sat idle inside every interval of
        ``label``, over the number of intervals of ``per``: a rare long
        stall counts as much as it costs the window."""
        pairs, rounds = self.pairs(label), self.pairs(per)
        if pairs is None or rounds is None:
            return None
        return 1000.0 * sum(h - b for h, b in pairs) / len(rounds)

    def busy_ms_mean(self, label: str) -> Optional[float]:
        """Mean device milliseconds busy inside one interval of
        ``label``."""
        pairs = self.pairs(label)
        if pairs is None:
            return None
        return 1000.0 * sum(b for _, b in pairs) / len(pairs)

    def summary(self) -> Dict[str, Optional[float]]:
        """The round's phases, in milliseconds."""
        return {
            "step_device_ms": self.busy_ms_mean("serve.step"),
            "step_host_ms": self.idle_ms_median("serve.step"),
            "result_wait_ms": self.idle_ms_per("serve.result_wait",
                                               "serve.step"),
            "schedule_ms": self.host_ms_median("serve.schedule"),
            "upload_ms": self.host_ms_median("serve.upload"),
        }


def idle_by_innermost(idle: List[Tuple[float, float]],
                      spans: List[Tuple[float, float, str]]
                      ) -> Dict[str, float]:
    """Length of ``idle`` (sorted, disjoint intervals) by the innermost
    of ``spans`` (``(start, end, label)``) covering each part of it,
    taken as the shortest, as ``xplane``'s gap labels take it; a part no
    span covers goes to ``OUTSIDE``.  The values sum to the idle
    length."""
    points = sorted({t for a, b in idle for t in (a, b)}
                    | {t for a, b, _ in spans for t in (a, b)})
    by_start = sorted(spans)
    out: Dict[str, float] = {}
    heap: List[Tuple[float, float, str]] = []   # (length, end, label)
    j = k = 0
    for t, t_next in zip(points, points[1:]):
        while j < len(by_start) and by_start[j][0] <= t:
            a, b, label = by_start[j]
            heapq.heappush(heap, (b - a, b, label))
            j += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)       # ended; a longer ended span may
            #                           stay below a live top, unread
        while k < len(idle) and idle[k][1] <= t:
            k += 1
        if k == len(idle) or idle[k][0] > t:
            continue                  # [t, t_next) is busy
        label = heap[0][2] if heap else OUTSIDE
        out[label] = out.get(label, 0.0) + (t_next - t)
    return out


def read(path: str) -> Spans:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window: Optional[Tuple[float, float]] = None
    host: List[Tuple[float, float, str]] = []
    chips: List[List[Tuple[float, float]]] = []
    for plane in data.planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            chips.append([(ev.start_ns, ev.start_ns + ev.duration_ns)
                          for line in plane.lines
                          if line.name == xplane.OPS_LINE
                          for ev in line.events])
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    if ev.name == xplane.WINDOW:
                        window = (ev.start_ns, end)
                    elif ev.name.startswith(xplane.HOST_LABELS):
                        host.append((ev.start_ns, end, ev.name))
    if window is None:
        raise ValueError(f"{path}: no {xplane.WINDOW!r} annotation")
    if not chips:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    w0, w1 = window
    clip: Dict[str, List[Tuple[float, float]]] = {}
    for a, b, name in host:
        if b > w0 and a < w1:
            clip.setdefault(name, []).append((max(a, w0), min(b, w1)))
    busy = {name: [0.0] * len(v) for name, v in clip.items()}
    first: List[Tuple[float, float]] = []
    for i, ops in enumerate(chips):
        merged = xplane._union([(max(a, w0), min(b, w1)) for a, b in ops
                                if b > w0 and a < w1])
        for name, v in clip.items():
            for j, iv in enumerate(v):
                busy[name][j] += xplane._overlap([iv], merged) / len(chips)
        if i == 0:
            first = merged
    idle, t = [], w0
    for a, b in first:
        if a > t:
            idle.append((t, a))
        t = max(t, b)
    if t < w1:
        idle.append((t, w1))
    ns = xplane.NS
    return Spans(
        window_s=(w1 - w0) * ns,
        idle_s=sum(b - a for a, b in idle) * ns,
        intervals={name: [((b - a) * ns, busy[name][j] * ns)
                          for j, (a, b) in enumerate(v)]
                   for name, v in clip.items()},
        idle_by_label={label: v * ns for label, v in
                       idle_by_innermost(idle, host).items()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane", help="a trace kept by run.py --keep-trace")
    got = read(ap.parse_args(argv).xplane)
    print(json.dumps({"window_s": got.window_s, "idle_s": got.idle_s,
                      "summary": got.summary(),
                      "idle_by_label": got.idle_by_label}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
