"""Open loop: independent requests at a fixed mean rate, sent when due
whether or not earlier ones have finished.

A run of ``seconds`` at ``rate_per_s`` sends ``n = round(rate * seconds)``
requests.  Their gaps are the exponential's quantiles scaled to fill the
window, in an order the seed picks, so every seed sends the same set of
arrivals and lengths.  Mix keys: ``prompt_tokens``, ``output_tokens``
(lognormal: ``median``, ``sigma``, ``min``, ``max``).  Cell key:
``clients.rate_per_s``.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

import sizes
from harness import Sent, now

FINISHED_ONLY = True     # the sample is drawn from finished requests


def plan(mix: Dict[str, Any], cell: Dict[str, Any], seed: int, vocab: int,
         seconds: float) -> List[Sent]:
    n = max(int(round(cell["clients"]["rate_per_s"] * seconds)), 1)
    r = sizes.rng(seed, 1)
    gaps = r.permutation(sizes.exponential_gaps(n, seconds))
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    prompts = r.permutation(sizes.lognormal_set(mix["prompt_tokens"], n))
    outs = r.permutation(sizes.lognormal_set(mix["output_tokens"], n))
    r_ids = sizes.rng(seed, 2)
    return [Sent(due=float(offsets[i]),
                 prompt=sizes.token_ids(r_ids, int(prompts[i]), vocab),
                 max_new=int(outs[i]))
            for i in range(n)]


class Loop:
    def __init__(self, client, sends: List[Sent],
                 cell: Dict[str, Any]) -> None:
        self.client = client
        self.sends = sends        # due holds the offset until the window
        self.late: List[float] = []

    def prepare(self) -> None:
        """Nothing to set up: the engine starts empty, as after a lull."""

    def window(self, t_end: float) -> Dict[str, Any]:
        t0 = now()
        for s in self.sends:
            s.due += t0
        i, n = 0, len(self.sends)
        while True:
            t = now()
            if t >= t_end:
                break
            while i < n and self.sends[i].due <= t:
                self.client.submit(self.sends[i])
                self.late.append(self.sends[i].sent - self.sends[i].due)
                i += 1
            if self.client.eng.has_work:
                self.client.step()
            else:
                wake = self.sends[i].due if i < n else t_end
                time.sleep(max(0.0, min(wake, t_end) - now()))
        late = sorted(self.late)
        sched = self.client.eng.scheduler
        return {"requests_due": n, "requests_sent": i,
                "waiting_at_end": len(sched.waiting),
                "running_at_end": len(sched.running),
                "sender_late_p50_s": late[len(late) // 2] if late else 0.0,
                "sender_late_max_s": late[-1] if late else 0.0}
