"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to what metrics read.

Device planes are ``/device:TPU:<n>``.  On each, op events (line
``XLA Ops``) give busy time as the union of their intervals and time per
op name.  The host annotation ``bench.window``, which the harness opens
around the measured window, fixes the window on the trace's clock.  Each
other ``bench.*`` or ``serve.*`` annotation gets the device's busy time
inside its intervals (``bench.step`` is one engine round, device work
included, since the engine's step waits for its results); idle gaps are
labelled by the innermost such annotation the host was in at the gap's
middle.
"""
from __future__ import annotations

import bisect
import glob
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
HOST_LABELS = ("bench.", "serve.")
NS = 1e-9
OP_KIND = re.compile(
    r"^%?([A-Za-z_][A-Za-z0-9_-]*?)(?:\.(?:\d+|remat\d*))*(?: =|$)")


@dataclass
class Reduced:
    window_s: float
    busy_s: float                          # mean over the chips used
    ops: Dict[str, List[float]] = field(default_factory=dict)
    # op name -> [seconds, count]; op_text: name -> searchable text
    op_text: Dict[str, str] = field(default_factory=dict)
    # host annotation -> [device busy seconds inside it, intervals]
    annotated: Dict[str, List[float]] = field(default_factory=dict)
    gaps: List[Tuple[float, str]] = field(default_factory=list)
    chips: int = 1

    def op_seconds(self, patterns) -> Optional[float]:
        """Device seconds of the ops whose name or text matches any of
        ``patterns`` (regular expressions); None when none ran."""
        rx = [re.compile(p) for p in patterns]
        hit = [v[0] for k, v in self.ops.items()
               if any(r.search(k) or r.search(self.op_text.get(k, ""))
                      for r in rx)]
        return sum(hit) / self.chips if hit else None

    def busy_in(self, label: str) -> Optional[Tuple[float, int]]:
        """(device busy seconds, intervals) of the host annotation
        ``label`` inside the window; None when it never ran there."""
        got = self.annotated.get(label)
        if not got or got[1] == 0:
            return None
        return got[0], int(got[1])

    def breakdown(self, n: int = 10) -> Dict[str, List[List]]:
        """Device time by kind of op (an HLO instruction's name without
        its number, ``%fusion.438 = ...`` -> ``fusion``), and the longest
        idle gaps by what the host was doing."""
        kinds: Dict[str, float] = {}
        for name, (seconds, _) in self.ops.items():
            kind = OP_KIND.match(name)
            key = kind.group(1) if kind else name[:64]
            kinds[key] = kinds.get(key, 0.0) + seconds
        ops = sorted(kinds.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, s / self.chips] for k, s in ops],
                "idle_gaps": [[label, s] for s, label in self.gaps[:n]]}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _overlap(spans: List[Tuple[float, float]],
             busy: List[Tuple[float, float]]) -> float:
    """Nanoseconds of ``busy`` (sorted, disjoint) inside ``spans``."""
    starts = [a for a, _ in busy]
    total = 0.0
    for a, b in spans:
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(busy) and busy[i][0] < b:
            total += max(0.0, min(b, busy[i][1]) - max(a, busy[i][0]))
            i += 1
    return total


def _text(event) -> str:
    return " ".join(v for _, v in event.stats if isinstance(v, str))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def reduce(path: str, n_gaps: int = 10) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window: Optional[Tuple[float, float]] = None
    host: List[Tuple[float, float, str]] = []
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif name.startswith(HOST_LABELS):
                    host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation in the trace")
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    w0, w1 = window
    red = Reduced(window_s=(w1 - w0) * NS, busy_s=0.0, chips=len(devices))
    spans: Dict[str, List[Tuple[float, float]]] = {}
    for a, b, name in host:
        if b > w0 and a < w1:
            spans.setdefault(name, []).append((max(a, w0), min(b, w1)))
    red.annotated = {name: [0.0, len(v)] for name, v in spans.items()}
    busy_total = 0.0
    first_busy: List[Tuple[float, float]] = []
    for i, plane in enumerate(devices):
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = ev.start_ns
                b = a + ev.duration_ns
                if b <= w0 or a >= w1:
                    continue
                a, b = max(a, w0), min(b, w1)
                slot = red.ops.setdefault(ev.name, [0.0, 0])
                slot[0] += (b - a) * NS
                slot[1] += 1
                intervals.append((a, b))
                if ev.name not in red.op_text:
                    red.op_text[ev.name] = _text(ev)
        merged = _union(intervals)
        busy_total += sum(b - a for a, b in merged) * NS
        for name, v in spans.items():
            red.annotated[name][0] += _overlap(v, merged) * NS / len(devices)
        if i == 0:
            first_busy = merged
    red.busy_s = busy_total / len(devices)
    # Idle gaps of the first chip, longest first, by what the host did.
    gaps, t = [], w0
    for a, b in first_busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    for a, b in gaps[:n_gaps]:
        mid = (a + b) / 2
        inside = [(e - s, name) for s, e, name in host if s <= mid <= e]
        label = min(inside)[1] if inside else "outside any annotation"
        red.gaps.append(((b - a) * NS, label))
    return red


def describe(path: str, top: int = 15) -> str:
    """Planes, lines and the busiest event names: for reading a new
    trace by hand before writing a metric against it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            tot: Dict[str, List[float]] = {}
            text: Dict[str, str] = {}
            for ev in line.events:
                s = tot.setdefault(ev.name, [0.0, 0])
                s[0] += ev.duration_ns * NS
                s[1] += 1
                if ev.name not in text:
                    text[ev.name] = _text(ev)[:160]
            n = sum(v[1] for v in tot.values())
            out.append(f"  line {line.name!r}: {n} events")
            for k, v in sorted(tot.items(), key=lambda kv: -kv[1][0])[:top]:
                out.append(f"    {v[0]:.6f}s x{v[1]} {k[:80]} | {text[k]}")
    return "\n".join(out)
