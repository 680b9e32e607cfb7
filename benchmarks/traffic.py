"""The one general traffic generator; a mix is a data file it reads.

Three kinds: ``train_steps`` (seeded token batches), ``closed_loop``
(clients that each wait for a reply) and ``open_loop`` (arrivals on a
seeded schedule). Three rules make a serving cell repeat:

- *The seed permutes, it does not draw.* Lengths are dealt from the file's
  multisets in seeded shuffled rounds, in submission order, so any stretch
  of requests holds the same lengths whatever the seed.
- *Stationary start.* Each client's first request has its output cut to a
  residual, so the slots are de-phased when the window opens. The phases
  are evenly spaced within each output length; the seed only decides which
  client gets which phase.
- The plan is a fixed amount of work; the window counts tokens and gaps.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterator

import numpy as np

KINDS = ("train_steps", "closed_loop", "open_loop")


def seed32(seed: int, salt: int = 0) -> int:
    """Fold any whole number (the driver's seeds pass 2**31) into 32 bits."""
    return (int(seed) * 2654435761 + salt * 40503 + 12345) % (2 ** 32)


@dataclasses.dataclass
class PlannedRequest:
    """One request of a plan: who sends it, what it holds, when it is due."""

    index: int
    client: int
    prompt: list
    max_new: int
    drawn_new: int            # the dealt output length, before a residual
    due_s: float = 0.0        # open loop: seconds after the window opens


def deal(multiset: list, rng: np.random.Generator) -> Iterator[int]:
    """Deal from ``multiset`` in shuffled rounds: every round of
    ``len(multiset)`` consecutive draws is a permutation of it."""
    deck = [int(x) for x in multiset]
    while True:
        for i in rng.permutation(len(deck)):
            yield deck[i]


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> list:
    return rng.integers(0, vocab, size=n).tolist()


def stationary_residuals(drawn: list, rng: np.random.Generator) -> list:
    """Residual output lengths for the clients' first requests: within
    each drawn length the phases ``(j + 0.5) / n`` are evenly spaced, and
    the seed decides which client takes which."""
    by_len: dict = {}
    for client in rng.permutation(len(drawn)):
        by_len.setdefault(drawn[client], []).append(int(client))
    out = [0] * len(drawn)
    for length, clients in by_len.items():
        for j, client in enumerate(clients):
            out[client] = max(1, math.ceil(length * (j + 0.5) / len(clients)))
    return out


class ClosedLoop:
    """``clients`` callers, each sending its next request when the last
    one is answered. ``first()`` gives the stationary-start requests;
    ``next_for(client)`` the following ones, dealt in submission order."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, int(vocab)
        self.clients = int(mix["clients"])
        self._rng = np.random.default_rng(seed32(seed, 1))
        self._prompts = deal(mix["prompt_lengths"], self._rng)
        self._outputs = deal(mix["output_lengths"], self._rng)
        self._count = 0

    def _make(self, client: int, max_new=None) -> PlannedRequest:
        plen, drawn = next(self._prompts), next(self._outputs)
        req = PlannedRequest(self._count, client,
                             _tokens(self._rng, plen, self.vocab),
                             drawn if max_new is None else max_new, drawn)
        self._count += 1
        return req

    def first(self, prefill_chunk: int = 0) -> list:
        """The clients' first requests, in the order to submit them.

        With ``stationary_start`` each output is cut to a residual, so the
        slots are de-phased; and since the fill takes a tick for every
        prompt chunk of those still queued behind a request, while that
        request already decodes a token a tick, each residual is
        lengthened by the chunks behind it (``prefill_chunk`` tokens a
        chunk; 0 leaves that out). What is left when the last prompt is
        in is then the evenly spaced residual itself. A first-round
        request can so hold more tokens than the mix's longest prompt +
        longest output (its ``check.pad_to``), though never more than the
        engine's ``max_seq_len`` (tested): ``check.row_width`` follows."""
        reqs = [self._make(c) for c in range(self.clients)]
        order = [int(i) for i in self._rng.permutation(self.clients)]
        if self.mix.get("stationary_start", True):
            res = stationary_residuals([r.drawn_new for r in reqs], self._rng)
            behind = 0
            for i in reversed(order):
                reqs[i].max_new = res[i] + behind
                if prefill_chunk:
                    behind += math.ceil(len(reqs[i].prompt) / prefill_chunk)
        return [reqs[i] for i in order]

    def next_for(self, client: int) -> PlannedRequest:
        return self._make(client)


def open_loop_plan(mix: dict, seed: int, vocab: int, seconds: float) -> list:
    """Arrivals for ``seconds`` at ``rate_rps``: evenly spaced slots with a
    seeded jitter inside each slot (``burst`` requests share a slot), so
    every seed offers the same count and the same lengths."""
    rng = np.random.default_rng(seed32(seed, 2))
    prompts = deal(mix["prompt_lengths"], rng)
    outputs = deal(mix["output_lengths"], rng)
    burst = int(mix.get("burst", 1))
    slot = burst / float(mix["rate_rps"])
    plan = []
    for s in range(int(seconds / slot)):
        due = (s + float(rng.uniform(0.0, 1.0))) * slot
        for _ in range(burst):
            drawn = next(outputs)
            plan.append(PlannedRequest(len(plan), len(plan),
                                       _tokens(rng, next(prompts), vocab),
                                       drawn, drawn, due))
    plan.sort(key=lambda r: (r.due_s, r.index))
    return plan


def train_batches(mix: dict, seed: int, vocab: int, chips: int
                  ) -> Iterator[dict]:
    """Seeded GPT batches, every row different: ``sequences_per_chip`` x
    ``chips`` rows of ``seq_len`` tokens with next-token labels."""
    rng = np.random.default_rng(seed32(seed, 3))
    rows, seq = int(mix["sequences_per_chip"]) * chips, int(mix["seq_len"])
    pos = np.broadcast_to(np.arange(seq, dtype=np.int32), (rows, seq)).copy()
    mask = np.ones((rows, seq), np.float32)
    while True:
        tok = rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int32)
        yield {"tokens": tok[:, :-1].copy(), "position_ids": pos,
               "labels": tok[:, 1:].copy(), "loss_mask": mask}


def offered_work(mix: dict, n_requests: int, seed: int) -> dict:
    """The multiset of lengths the first ``n_requests`` of a serving mix
    hold (for the test that two seeds offer the same work)."""
    rng = np.random.default_rng(seed32(seed, 1))
    p, o = deal(mix["prompt_lengths"], rng), deal(mix["output_lengths"], rng)
    pl = sorted(next(p) for _ in range(n_requests))
    ol = sorted(next(o) for _ in range(n_requests))
    return {"prompts": pl, "outputs": ol, "tokens": sum(pl) + sum(ol)}
