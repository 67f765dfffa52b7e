"""Reductions that metric files share: each ``metrics/<name>.py`` calls
one of these with its own parameters.  A reader that finds nothing to
read returns None, and the metric is left out of the line."""
from __future__ import annotations

import math
from typing import Any, Dict, Iterable, List, Optional

import work
from harness import RunRecord


def nearest_rank(values: List[float], q: float) -> Optional[float]:
    """The ``q``-th percentile by nearest rank; None when empty."""
    if not values:
        return None
    xs = sorted(values)
    return xs[min(len(xs) - 1, max(0, math.ceil(q / 100 * len(xs)) - 1))]


def ttft_s(run: RunRecord) -> List[float]:
    """Every request due in the window, from its due time to its first
    token; one still without a token counts the time it has waited."""
    out = []
    for s in run.sent:
        if run.t_start <= s.due < run.t_end:
            first = s.req.first_token_time if s.req is not None else None
            out.append((first if first is not None else run.t_end) - s.due)
    return out


def tpot_s(run: RunRecord) -> List[float]:
    """Per request with two or more tokens: time per output token after
    the first, from the program's own first and last emission times."""
    out = []
    for s in run.sent:
        r = s.req
        if r is not None and len(r.tokens) >= 2 and \
                r.first_token_time is not None:
            out.append((r.last_emit_time - r.first_token_time)
                       / (len(r.tokens) - 1))
    return out


def occupancy_pct(run: RunRecord) -> Optional[float]:
    if not run.rounds:
        return None
    return 100.0 * sum(r.running for r in run.rounds) / (
        len(run.rounds) * run.max_batch)


def pages_pct(run: RunRecord) -> Optional[float]:
    if not run.rounds:
        return None
    return 100.0 * sum(r.pages_used for r in run.rounds) / (
        len(run.rounds) * run.num_blocks)


def busy_ms(run: RunRecord, label: str) -> Optional[float]:
    """Mean device milliseconds busy inside one interval of the host
    annotation ``label``."""
    if run.trace is None:
        return None
    got = run.trace.busy_in(label)
    return None if got is None else 1000.0 * got[0] / got[1]


def window_work(run: RunRecord) -> Dict[str, Any]:
    """What every round of the window computed, as the counts of
    ``work.py`` take it."""
    return {"rows": sum(r.rows for r in run.rounds),
            "ctx_rows": sum(r.ctx_rows for r in run.rounds),
            "kv_rows": sum(r.kv_rows for r in run.rounds),
            "slots": [s for r in run.rounds for s in r.slots]}


def roofline_pct(run: RunRecord, kernels: Iterable[str],
                 count: str = "paged_attention") -> Optional[float]:
    """Share of a kernel's roofline over the window: the least time the
    chip needs for the work of every round, as the architecture's count
    ``count`` gives it, over the kernel's device time."""
    if run.trace is None or not run.rounds:
        return None
    t = run.trace.op_seconds(kernels)
    if not t:
        return None
    w = work.count(run.config, count, **window_work(run))
    least = work.roofline_seconds(w["flops"], w["bytes"], run.peaks)
    run.facts.setdefault("roofline_bound", {})[",".join(kernels)] = \
        least["bound"]
    return 100.0 * least["seconds"] / t


def mfu_pct(run: RunRecord) -> Optional[float]:
    """Model operations of every row the window computed, over the
    window's length times the chip's peak."""
    if not run.rounds or run.window_s <= 0:
        return None
    done = window_work(run)
    flops = work.forward_flops(run.config, rows=done["rows"],
                               ctx_rows=done["ctx_rows"],
                               slots=done["slots"])
    return 100.0 * flops / (run.window_s * run.peaks["flops_per_s"])


def idle_pct(run: RunRecord) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def histogram_pct_ms(run: RunRecord, name: str, q: float
                     ) -> Optional[float]:
    v = nearest_rank(run.histograms.get(name, []), q)
    return None if v is None else 1000.0 * v
