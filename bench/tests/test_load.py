"""The traffic generator: one seed, one schedule; every seed the same
work in another order."""
from __future__ import annotations

import json
import itertools
from collections import Counter

import load
from cell import BENCH

#: an open-loop mix: 4 s calm at 0.4 x the rate, then 1 s burst at 2 x
BURST = {"loop": "open", "rate_rps": 10.0,
         "phases": [{"seconds": 4, "rate_mult": 0.4},
                    {"seconds": 1, "rate_mult": 2.0}],
         "prompt": {"median": 160, "sigma": 0.8, "min": 16, "max": 1024},
         "output": {"median": 64, "sigma": 0.7, "min": 8, "max": 256}}
BATCH = json.loads((BENCH / "traffic" / "batch.json").read_text())
BIG = 2**31 + 12345


def test_open_schedule_repeats_for_a_seed():
    a = load.open_schedule(BURST, 30, BIG, 50288)
    b = load.open_schedule(BURST, 30, BIG, 50288)
    assert a == b and len(a) > 100


def test_open_schedule_same_work_for_every_seed():
    a = load.open_schedule(BURST, 30, 1, 50288)
    b = load.open_schedule(BURST, 30, BIG, 50288)
    assert a != b
    assert Counter(len(i.prompt) for i in a) == \
        Counter(len(i.prompt) for i in b)
    assert Counter(i.max_new for i in a) == Counter(i.max_new for i in b)

    def per_phase(s):       # arrivals in each 5 s cycle's calm and burst
        return Counter((int(i.due // 5), i.due % 5 >= 4) for i in s)
    assert per_phase(a) == per_phase(b)


def test_open_schedule_rate_and_bounds():
    s = load.open_schedule(BURST, 30, 3, 50288)
    mean = BURST["rate_rps"] * (4 * 0.4 + 1 * 2.0) / 5
    assert abs(len(s) - mean * 30) <= 1
    assert all(0 <= i.due < 30 for i in s)
    assert all(16 <= len(i.prompt) <= 1024 and 8 <= i.max_new <= 256
               for i in s)
    assert all(1 <= t < 50288 for i in s for t in i.prompt)


def test_closed_clients_repeat_for_a_seed():
    def first(seed):
        return [next(load.client_items(BATCH, seed, 32000, c))
                for c in range(BATCH["clients"])]
    assert first(BIG) == first(BIG)
    assert first(BIG) != first(5)


def _rounds(seed, cycles):
    """The first ``cycles`` cycles of the closed loop, round by round."""
    n, r = BATCH["clients"], BATCH["rounds"]
    items = [list(itertools.islice(load.client_items(BATCH, seed, 32000, c),
                                   cycles * r)) for c in range(n)]
    return [[items[c][k] for c in range(n)] for k in range(cycles * r)]


def test_closed_cycles_hold_the_same_sizes():
    """Every cycle of ``rounds`` rounds is the same multiset of sizes for
    every seed, in another order; each round takes one size of every
    stratum, so a part of a cycle is nearly the same work too."""
    n, r = BATCH["clients"], BATCH["rounds"]
    a, b = _rounds(1, 3), _rounds(BIG, 3)
    assert a[0] != b[0]
    for key, spec in ((lambda i: len(i.prompt), BATCH["prompt"]),
                      (lambda i: i.max_new, BATCH["output"])):
        whole = Counter(load.length_multiset(spec, n * r).tolist())
        for s in (a, b):
            for k in range(0, len(s), r):
                assert Counter(key(i) for rd in s[k:k + r]
                               for i in rd) == whole
        strata = load.length_multiset(spec, n * r).reshape(n, r)
        for rd in a + b:
            got = sorted(key(i) for i in rd)
            assert all(lo <= v <= hi for v, lo, hi in
                       zip(got, strata[:, 0], strata[:, -1]))
