"""Closed loop of prompt groups, as an RL rollout producer sends them.

A group is one prompt with ``completions_per_prompt`` completions, each
with its own output budget.  ``groups_in_flight`` groups are submitted
at set-up and prefilled there; in the window the engine steps, and when
every completion of a group has finished the next group of the stream is
submitted.  The ``n`` groups in flight are one fixed set, the same for
every seed: which prompt length goes with which output budgets is drawn
once, apart from the seed, since the engine's work in a round follows
the contexts of the slots it holds.  The seed picks the order in which
the groups are sent and draws the token ids.  The stream never runs dry:
group ``g`` takes the lengths of the ``g mod n``-th group of that order,
with fresh token ids.
Mix keys: ``completions_per_prompt``, ``prompt_tokens`` and
``output_tokens`` (lognormal: ``median``, ``sigma``, ``min``, ``max``).
Cell key: ``clients.groups_in_flight``.
"""
from __future__ import annotations

from typing import Any, Dict, List

import sizes
from harness import Sent, now

FINISHED_ONLY = False    # tokens served to in-flight requests are checked


class Stream:
    """Group ``g`` of the seed's endless stream, made when asked for."""

    def __init__(self, mix: Dict[str, Any], cell: Dict[str, Any],
                 seed: int, vocab: int) -> None:
        self.k = mix["completions_per_prompt"]
        self.n = cell["clients"]["groups_in_flight"]
        fixed = sizes.rng(0, 1)
        prompts = fixed.permutation(
            sizes.lognormal_set(mix["prompt_tokens"], self.n))
        outs = fixed.permutation(
            sizes.lognormal_set(mix["output_tokens"], self.n * self.k))
        order = sizes.rng(seed, 1).permutation(self.n)
        self.prompts = prompts[order]
        self.outs = outs.reshape(self.n, self.k)[order].reshape(-1)
        self.seed = seed
        self.vocab = vocab

    def group(self, g: int) -> List[Sent]:
        i = g % self.n
        ids = sizes.token_ids(sizes.rng(self.seed, 1000 + g),
                              int(self.prompts[i]), self.vocab)
        return [Sent(due=0.0, prompt=ids, max_new=int(m), group=g)
                for m in self.outs[i * self.k:(i + 1) * self.k]]


def plan(mix: Dict[str, Any], cell: Dict[str, Any], seed: int, vocab: int,
         seconds: float) -> Stream:
    return Stream(mix, cell, seed, vocab)


class Loop:
    def __init__(self, client, stream: Stream,
                 cell: Dict[str, Any]) -> None:
        self.client = client
        self.stream = stream
        self.next = 0
        self.left: Dict[int, int] = {}
        self.g_fly = cell["clients"]["groups_in_flight"]

    def _send_group(self) -> None:
        group = self.stream.group(self.next)
        self.next += 1
        self.left[group[0].group] = len(group)
        t = now()
        for s in group:
            s.due = t
            self.client.submit(s)

    def prepare(self) -> None:
        """Set-up: the first population, submitted and prefilled."""
        for _ in range(self.g_fly):
            self._send_group()
        while self.client.prefilling():
            self._after(self.client.step())

    def _after(self, finished: List[Sent]) -> None:
        for s in finished:
            self.left[s.group] -= 1
            if self.left[s.group] == 0:
                del self.left[s.group]
                self._send_group()

    def window(self, t_end: float) -> Dict[str, Any]:
        while now() < t_end:
            self._after(self.client.step())
        return {"groups_sent": self.next,
                "groups_finished": self.next - len(self.left)}
