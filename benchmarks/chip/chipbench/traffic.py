"""Seeded traffic from a parameter file.

Adapted from the program's open-loop trace generator (lognormal lengths
with a median and a shape, clipped; Poisson arrivals), extended with Zipf
client popularity, a closed loop, resident sessions and a context limit.

Arrivals are drawn afresh from each seed.  Lengths are not: every seed
gets the same multiset of prompt and output lengths (the stratified
quantiles of their lognormals) and of clients, in its own random order,
so the seed moves when the work comes and to whom, not how much there
is of it.

Parameters (``traffic/<name>.json``):

* ``loop``: ``"open"`` (requests due at scheduled times), ``"closed"``
  (``users`` coroutines, each sending its next request when its reply
  completes, first sends ``stagger_s`` apart) or ``"resident"``
  (``sessions`` requests admitted together before the window, whose
  prompts are the sessions' histories: the window sees them decode);
* ``arrival`` (open): ``"poisson"``, the only process implemented; any
  other value is an error;
* ``rate_per_s`` (open): mean arrival rate.  A window of ``seconds``
  holds round(rate * seconds) requests due at sorted uniform times in
  it: a Poisson process given its count;
* ``prompt_len`` / ``output_len``: ``median``, ``sigma``, ``min``, ``max``;
* ``clients`` and ``client_zipf_s`` (open, resident): requests spread
  over clients ``0..clients-1`` with popularity ``1 / (rank + 1) ** s``;
  in the closed loop every user is its own client;
* ``context_limit``: prompt plus output never exceeds it.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Item:
    """One request: who sends it, what it asks for, and (open loop) when
    it is due, in seconds from the start of the window."""
    client: int
    prompt: np.ndarray
    max_new_tokens: int
    due_s: float = 0.0


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one use of the run's seed."""
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def stratified_lengths(spec: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a lognormal with ``spec['median']``
    and shape ``spec['sigma']``, rounded and clipped to [min, max]."""
    nd = NormalDist()
    z = np.asarray([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_counts(n_clients: int, s: float, n: int) -> np.ndarray:
    """How many of n requests each client sends under Zipf popularity
    (largest-remainder rounding, so the counts sum to n)."""
    w = 1.0 / np.arange(1, n_clients + 1) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]
    counts[rest] += 1
    return counts


def _lengths(traffic: dict, n: int, rng: np.random.Generator):
    """The file's multiset of n prompt and n output lengths, each in an
    order drawn from ``rng``."""
    p = rng.permutation(stratified_lengths(traffic["prompt_len"], n))
    o = rng.permutation(stratified_lengths(traffic["output_len"], n))
    limit = traffic.get("context_limit")
    if limit is not None:
        o = np.minimum(o, limit - p)
        if (o < 1).any():
            raise ValueError("context_limit leaves a request no output")
    return p, o


def _prompts(lengths, vocab: int, rng: np.random.Generator):
    return [rng.integers(1, vocab, int(n)).astype(np.int32) for n in lengths]


ARRIVALS = ("poisson",)


def _clients(traffic: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation(np.repeat(
        np.arange(traffic["clients"]),
        zipf_counts(traffic["clients"], traffic.get("client_zipf_s", 0.0),
                    n)))


def open_loop(traffic: dict, seed: int, seconds: float,
              vocab: int) -> List[Item]:
    """Requests due in [0, seconds), sorted by due time: round(rate *
    seconds) of them at sorted uniform times, which is a Poisson process
    of that rate given its count."""
    if traffic.get("arrival") not in ARRIVALS:
        raise ValueError(f"arrival {traffic.get('arrival')!r} is not "
                         f"implemented (have {list(ARRIVALS)})")
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    rng = rng_for(seed, 1)
    due = np.sort(rng.uniform(0.0, seconds, n))
    p, o = _lengths(traffic, n, rng)
    clients = _clients(traffic, n, rng)
    prompts = _prompts(p, vocab, rng)
    return [Item(int(c), pr, int(b), float(t))
            for c, pr, b, t in zip(clients, prompts, o, due)]


def resident(traffic: dict, seed: int, vocab: int) -> List[Item]:
    """The ``sessions`` requests admitted before the window, all due at
    once."""
    n = traffic["sessions"]
    if n > traffic["slots"]:
        raise ValueError(f"{n} resident sessions do not fit "
                         f"{traffic['slots']} slots")
    rng = rng_for(seed, 4)
    p, o = _lengths(traffic, n, rng)
    clients = _clients(traffic, n, rng)
    return [Item(int(c), pr, int(b))
            for c, pr, b in zip(clients, _prompts(p, vocab, rng), o)]


def closed_loop(traffic: dict, seed: int, rounds: int,
                vocab: int) -> List[Item]:
    """The queue closed-loop users draw from, in order: ``rounds`` blocks
    of ``users`` requests, each block the same stratified multiset of
    lengths in its own order.  A user's request carries the user as its
    client; ``client`` is filled in when a user draws the item."""
    users = traffic["users"]
    rng = rng_for(seed, 2)
    items: List[Item] = []
    for _ in range(rounds):
        p, o = _lengths(traffic, users, rng)
        items += [Item(-1, pr, int(b)) for pr, b in
                  zip(_prompts(p, vocab, rng), o)]
    return items


def n_clients(traffic: dict) -> int:
    return traffic["users"] if traffic["loop"] == "closed" else \
        traffic["clients"]


def items(traffic: dict, seed: int, seconds: float,
          vocab: int) -> List[Item]:
    """The requests of one run of ``seconds``."""
    loop = traffic["loop"]
    if loop == "open":
        return open_loop(traffic, seed, seconds, vocab)
    if loop == "resident":
        return resident(traffic, seed, vocab)
    if loop == "closed":
        return closed_loop(traffic, seed, max(2, math.ceil(2 * seconds)),
                           vocab)
    raise ValueError(f"loop {loop!r} is not implemented")
