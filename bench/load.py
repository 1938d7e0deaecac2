"""The one traffic generator: turns a traffic file's parameters and a
seed into requests.

Every seed gets the same multiset of sizes and the same number of
arrivals in each phase; the seed only orders the sizes, places the
arrivals inside their phases and draws the token ids.  So two seeds do
the same amount of work in another order, and their runs can be compared.

A traffic file holds:

* ``loop``: ``"open"`` (requests sent on a schedule, whether or not
  earlier ones finished) or ``"closed"`` (``clients`` callers, each
  sending its next request when the last one completed);
* closed loop: ``rounds``, the length of its cycle.  The clients send
  round after round of ``clients`` requests, and every ``rounds`` rounds
  hold the same multiset of ``rounds * clients`` sizes, each round one
  size of each of ``clients`` strata of it (:func:`stratified_rounds`).
  A window reaches only its first few rounds, so the cycle is kept to
  about what it reaches: every window is then nearly the same work;
* ``prompt`` / ``output``: token-length distributions, ``{"median",
  "sigma", "min", "max"}`` of a lognormal, clipped;
* open loop: ``rate_rps`` and ``phases``, a repeated cycle of
  ``{"seconds", "rate_mult"}``; each phase sends
  ``rate_rps * rate_mult * seconds`` requests (carried over between
  phases so the total is exact), placed uniformly at random in it;
* ``trace_seconds``: how much of the window a ``--trace 1`` run records.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

@dataclass(frozen=True)
class Item:
    """One request to send: when (seconds after the window opens; closed
    loops leave it 0), its prompt ids and how many tokens to generate."""
    due: float
    prompt: tuple
    max_new: int


def length_multiset(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of the clipped lognormal:
    the same multiset for every seed."""
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    q = [(i + 0.5) / n for i in range(n)]
    vals = [math.exp(mu + sigma * nd.inv_cdf(x)) for x in q]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); seeds of any size."""
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])


def arrival_times(traffic: dict, seconds: float,
                  rng: np.random.Generator) -> list[float]:
    """Open-loop send times in ``[0, seconds)``: each phase of the cycle
    gets its fixed count of arrivals, placed uniformly in the phase."""
    rate = traffic["rate_rps"]
    phases = traffic["phases"]
    out, t, carry = [], 0.0, 0.0
    while t < seconds - 1e-9:
        for ph in phases:
            length = min(ph["seconds"], seconds - t)
            if length <= 0:
                break
            carry += rate * ph["rate_mult"] * length
            n = int(math.floor(carry + 1e-9))
            carry -= n
            out.extend(sorted(t + rng.uniform(0.0, length, n)))
            t += length
    return [float(x) for x in out]


def _sizes(traffic: dict, n: int, seed: int):
    """(prompt lengths, output lengths) of ``n`` requests: the fixed
    multisets, each in the seed's order."""
    prompts = length_multiset(traffic["prompt"], n)
    outputs = length_multiset(traffic["output"], n)
    return (prompts[rng_for(seed, 2).permutation(n)],
            outputs[rng_for(seed, 3).permutation(n)])


def _item(i: int, due: float, prompt_len: int, max_new: int, seed: int,
          vocab: int) -> Item:
    ids = rng_for(seed, 1_000_000 + i).integers(1, vocab, int(prompt_len))
    return Item(due, tuple(int(x) for x in ids), int(max_new))


def open_schedule(traffic: dict, seconds: float, seed: int,
                  vocab: int) -> list[Item]:
    """Every request of an open-loop window, in send order."""
    dues = arrival_times(traffic, seconds, rng_for(seed, 1))
    prompts, outputs = _sizes(traffic, len(dues), seed)
    return [_item(i, d, p, o, seed, vocab)
            for i, (d, p, o) in enumerate(zip(dues, prompts, outputs))]


def stratified_rounds(spec: dict, clients: int, rounds: int,
                      rng: np.random.Generator) -> np.ndarray:
    """One cycle: ``rounds * clients`` lengths, round after round.  The
    multiset of :func:`length_multiset` is cut into ``clients`` strata of
    ``rounds`` neighbouring quantiles, and each round takes one length of
    every stratum; the seed picks which member of a stratum a round takes
    and which client of the round gets it."""
    strata = length_multiset(spec, clients * rounds).reshape(clients, rounds)
    strata = rng.permuted(strata, axis=1)
    return rng.permuted(strata, axis=0).T.reshape(-1)


def client_items(traffic: dict, seed: int, vocab: int, c: int):
    """Closed loop: client ``c``'s requests, in order, without end.
    Client ``c`` takes items ``c``, ``c + clients``, ... of the cycles of
    stratified rounds, so the clients together walk them a round at a
    time."""
    clients, rounds = traffic["clients"], traffic["rounds"]
    n = clients * rounds
    rp, ro = rng_for(seed, 2), rng_for(seed, 3)
    for k in itertools.count():
        prompts = stratified_rounds(traffic["prompt"], clients, rounds, rp)
        outputs = stratified_rounds(traffic["output"], clients, rounds, ro)
        for i in range(c, n, clients):
            yield _item(k * n + i, 0.0, prompts[i], outputs[i], seed,
                        vocab)
