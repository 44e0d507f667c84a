"""The one traffic generator: a mix file's parameters plus ``--seed``.

A mix (``traffic/<name>.json``) states the loop (``open`` at a fixed
Poisson rate, or ``closed`` with a number of clients and no think time),
the tenants with their priorities, and lognormal prompt and output
lengths clipped to a range.  Every seed gets the same set of lengths and
of inter-arrival gaps (quantiles of the stated distributions), in an
order drawn from the seed, and its own prompt token ids: the seed changes
which request is which, not how much work the window holds.  The order
is stratified: each run of ``STRATA`` consecutive requests holds one
value from each stratum of the sorted set, so that every part of the
window sees the same mix whatever the seed.  A closed loop has no end of
its own: its plan is drawn in rounds of ``clients * STRATA`` requests,
each the same set of lengths in a new order, and grows by a round
whenever a client needs a request past its end.  A mix with ``"order":
"fixed"`` takes, for every seed, the orders that the mix's ``order_seed``
(0 where it is not given) draws, so that the seed changes only the
prompts' tokens (and the weights): for traffic in which the order of
sizes decides how much work the engine does, such as which requests
preempt which.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from statistics import NormalDist
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the client will send it."""

    idx: int
    tenant: int
    priority: int
    prompt: np.ndarray
    max_new: int
    at: float = 0.0          # scheduled send time, seconds into the window


def _lognormal_set(spec: dict, n: int) -> np.ndarray:
    """n lengths: lognormal quantiles around ``median`` with ``sigma``,
    clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


STRATA = 16


def _stratified(values: np.ndarray, rng) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which each block of
    ``STRATA`` consecutive entries holds one value of each stratum of the
    sorted values (the last block takes what is left)."""
    v = np.sort(values)
    n = len(v)
    blocks = -(-n // STRATA)
    strata = [rng.permutation(s) for s in np.array_split(v, STRATA)]
    out = []
    for b in range(blocks):
        block = [s[b] for s in strata if b < len(s)]
        out.extend(rng.permutation(block))
    return np.asarray(out)


def _exponential_set(rate: float, n: int) -> np.ndarray:
    return np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)]) \
        / rate


def count(mix: dict, seconds: float) -> int:
    """How many requests one round of the plan holds: an open loop's
    window draws on one round, a closed loop's clients on as many as they
    need."""
    if mix["loop"] == "open":
        return int(math.ceil(mix["rate_rps"] * seconds * 1.25)) + 8
    return mix["clients"] * STRATA


class Plan(list):
    """The requests of one run, in sending order, grown a round at a time
    by :meth:`upto`."""

    def __init__(self, rounds: Iterator[List[Planned]]):
        super().__init__(next(rounds))
        self._rounds = rounds

    def upto(self, j: int) -> Planned:
        """Request ``j``, drawing further rounds until the plan holds it."""
        while j >= len(self):
            self.extend(next(self._rounds))
        return self[j]


def _rounds(mix: dict, seed: int, vocab: int, n: int
            ) -> Iterator[List[Planned]]:
    rng = np.random.default_rng(seed)
    order = (np.random.default_rng(mix.get("order_seed", 0))
             if mix.get("order") == "fixed" else rng)
    tenants = mix["tenants"]
    prompt_set = _lognormal_set(mix["prompt_tokens"], n)
    output_set = _lognormal_set(mix["output_tokens"], n)
    for r in itertools.count():
        prompts = _stratified(prompt_set, order)
        outputs = _stratified(output_set, order)
        reqs = []
        for i in range(r * n, (r + 1) * n):
            t = i % len(tenants)
            reqs.append(Planned(
                idx=i, tenant=t, priority=int(tenants[t]["priority"]),
                prompt=rng.integers(0, vocab, int(prompts[i - r * n]))
                .astype(np.int32),
                max_new=int(outputs[i - r * n])))
        if mix["loop"] == "open":
            gaps = _stratified(_exponential_set(mix["rate_rps"], n), order)
            at = np.cumsum(gaps) - gaps[0]
            for q, a in zip(reqs, at):
                q.at = float(a)
            yield reqs
            return
        yield reqs


def plan(mix: dict, seed: int, vocab: int, seconds: float) -> Plan:
    """The requests of one run, in sending order.

    Open loop: one round, and ``at`` is each request's scheduled arrival.
    Closed loop: request i belongs to client ``i % clients``; ``drive``
    sends a client's next request when its previous one completes."""
    return Plan(_rounds(mix, seed, vocab, count(mix, seconds)))
