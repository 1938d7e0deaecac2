"""End-to-end arithmetic over the clients' own records (host clock).

A :class:`Record` is what one client saw of one request: when it was
due, when it was sent, and when each token arrived.  All times are
``time.perf_counter()`` seconds.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class Record:
    rid: int
    due: float                  # absolute time the request was due
    prompt: tuple
    max_new: int
    sent: float | None = None   # when submit() was called
    times: list = field(default_factory=list)   # token arrival times
    tokens: list = field(default_factory=list)
    engine_out: list | None = None   # the engine's own record of the output
    error: str | None = None
    done: bool = False          # the stream ended
    cancelled: bool = False     # cancelled at the close (closed loop)


def p95(values) -> float:
    """95th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), 95))


def ttft_values(records, missing_at: float) -> list[float]:
    """First token time minus due time, per request.  A request that
    never got a token counts as missing: its value is ``missing_at`` minus
    its due time, a lower bound of a wait that never ended."""
    out = []
    for r in records:
        first = r.times[0] if r.times else missing_at
        out.append(first - r.due)
    return out


def itl_values(records, t_open: float, t_close: float) -> list[float]:
    """Every gap between consecutive tokens of a request whose later
    token arrived inside the window."""
    out = []
    for r in records:
        ts = r.times
        for a, b in zip(ts, ts[1:]):
            if t_open <= b <= t_close:
                out.append(b - a)
    return out


def tokens_in_window(records, t_open: float, t_close: float) -> int:
    return sum(1 for r in records for t in r.times if t_open <= t <= t_close)
