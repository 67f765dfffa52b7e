"""Driving the serve engine: warm-up, one recorded step, and the sample
of served requests that the reference checks.

Only public fields of the program's ``Request`` and ``ServeEngine`` are
read: ``prefill_done``, ``num_prefilled``, ``tokens``, ``log_beta``,
``prompt``, ``finish_time``, ``finish_reason``,
``first_token_time``, ``last_emit_time``; ``scheduler.running``,
``allocator``, ``metrics``, ``has_work``, ``submit`` and ``step``.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import numpy as np

import sizes
from harness import Round, RunRecord, Sent, now

# Round widths are padded to a multiple of this (the engine's jit key),
# so a chunk of ``c`` rows compiles ``ceil(c / 4)`` varlen programs.
ROUND_PAD = 4


class Client:
    """The engine with the harness's bookkeeping around each step."""

    def __init__(self, eng, rec: RunRecord) -> None:
        self.eng = eng
        self.rec = rec
        self.live: List = []          # submitted, not yet finished
        self.by_id: Dict[int, Sent] = {}
        self.recording = False

    def submit(self, s: Sent) -> None:
        s.req = self.eng.submit(s.prompt, s.max_new)
        s.sent = now()
        self.live.append(s.req)
        self.by_id[s.req.request_id] = s
        self.rec.sent.append(s)

    def step(self) -> List[Sent]:
        """One engine step; returns the requests it finished."""
        # A waiting request reads prefill_done True but decodes nothing.
        before = [(r, r.prefill_done and r.slot is not None,
                   r.num_prefilled, len(r.tokens)) for r in self.live]
        t0 = now()
        with jax.profiler.TraceAnnotation("bench.step"):
            done = self.eng.step()
        t1 = now()
        if self.recording:
            rows = ctx = kv = 0
            slots = []
            for r, pd0, np0, nt0 in before:
                n = r.num_prefilled - np0
                if n > 0:            # a prefill tile of n rows from np0
                    rows += n
                    ctx += n * np0 + n * (n + 1) // 2
                    kv += np0 + n
                    slots.append((np0 + n, n))
                if pd0 and len(r.tokens) > nt0:
                    c = len(r.prompt) + nt0   # one decode row
                    rows += 1
                    ctx += c
                    kv += c
                    slots.append((c, 1))
            alloc = self.eng.allocator
            self.rec.rounds.append(Round(
                t0=t0, t1=t1, running=len(self.eng.scheduler.running),
                pages_used=alloc.num_blocks - alloc.num_free,
                rows=rows, ctx_rows=ctx, kv_rows=kv, slots=tuple(slots)))
        finished = [self.by_id[t.request_id] for t in done]
        if finished:
            ids = {id(s.req) for s in finished}
            self.live = [r for r in self.live if id(r) not in ids]
        return finished

    def prefilling(self) -> bool:
        return any(not r.prefill_done for r in self.eng.scheduler.running) \
            or bool(self.eng.scheduler.waiting)


def warm_up(eng, prefill_chunk: int, vocab: int, seed: int) -> int:
    """Compile every program the window can use, and no other: one
    varlen round per padded width up to ``prefill_chunk`` and the decode
    step.  Each request runs alone, so its round has exactly its width.
    Returns the number of programs driven."""
    r = sizes.rng(seed, 99)
    widths = range(ROUND_PAD, -(-prefill_chunk // ROUND_PAD) * ROUND_PAD + 1,
                   ROUND_PAD)
    for width in widths:
        eng.submit(sizes.token_ids(r, width, vocab), 2)
        while eng.has_work:
            eng.step()
    return len(widths) + 1


def window_histogram(eng, name: str, start: int) -> List[float]:
    """Samples the program's histogram ``name`` took after ``start``."""
    h = eng.metrics.histogram(name)
    fresh = h.count - start
    return list(h.samples)[-fresh:] if fresh > 0 else []


def sample_served(sent: List[Sent], seed: int, k: int,
                  finished_only: bool) -> List[Sent]:
    """``k`` requests drawn from the seed among those that served at
    least one token, the one with most tokens always among them."""
    pool = [s for s in sent if s.req is not None and s.req.tokens
            and (not finished_only or s.req.finish_time is not None)]
    if not pool:
        return []
    longest = max(pool, key=lambda s: len(s.req.tokens))
    rest = [s for s in pool if s is not longest]
    r = sizes.rng(seed, 7)
    pick = list(r.choice(len(rest), size=min(k - 1, len(rest)),
                         replace=False)) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def check_logprobs(w, conf, picked: List[Sent], control: bool = False
                   ) -> Optional[float]:
    """Widest distance between the log-probability the engine reported
    for a served token (``log_beta``) and the reference's, over every
    served token of the picked requests.  With ``control``, the control's
    log-probabilities of the same tokens stand in for the engine's."""
    import reference

    widest = None
    for s in picked:
        ref = reference.served_logprobs(w, conf, s.prompt, s.req.tokens)
        got = (reference.served_logprobs(w, conf, s.prompt, s.req.tokens,
                                         bf16x3=True) if control
               else np.asarray(s.req.log_beta, np.float32))
        m = float(np.max(np.abs(got - ref)))
        widest = m if widest is None else max(widest, m)
    return widest
