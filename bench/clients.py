"""The measured window: clients on threads of the benchmark's process
driving the engine's background loop (``engine.start()``, ``submit()``,
each handle's ``tokens()``, ``engine.stop()``) — the path a network
front end uses.  Every time here is ``time.perf_counter()``.
"""
from __future__ import annotations

import itertools
import threading
import time

from e2e import Record
from load import client_items

now = time.perf_counter


def _serve_one(engine, make_request, rec: Record, handles: dict,
               lock: threading.Lock):
    """Submit ``rec`` and drain its stream, stamping each token."""
    try:
        req = make_request(rec)
        rec.sent = now()
        h = engine.submit(req)
        with lock:
            handles[rec.rid] = h
        for tok in h.tokens():
            rec.times.append(now())
            rec.tokens.append(int(tok))
        rec.engine_out = list(req.out)
        rec.cancelled = h.cancelled
        rec.done = True
    except Exception as e:          # the record carries the failure
        rec.error = repr(e)
        rec.done = True


class Window:
    """What one window saw: records, its bounds and the generator's
    lateness (open loop)."""

    def __init__(self):
        self.records: list[Record] = []
        self.t_open = self.t_close = 0.0
        self.lateness: list[float] = []
        self.drain_deadline = 0.0


def run_open(engine, make_request, items, seconds: float,
             drain_s: float = 60.0, before_start=None, on_open=None,
             on_trace_end=None, trace_s: float = 0.0) -> Window:
    """Open loop: each item is sent at its due time on a thread of its
    own, whatever came before.  After the close, waits up to ``drain_s``
    for requests still running; those then cancelled count as failed."""
    w = Window()
    handles, lock, threads = {}, threading.Lock(), []
    if before_start is not None:
        before_start()
    engine.start()
    w.t_open = now() + 0.05
    w.t_close = w.t_open + seconds
    w.records = [Record(rid=i, due=w.t_open + it.due, prompt=it.prompt,
                        max_new=it.max_new) for i, it in enumerate(items)]
    _sleep_until(w.t_open)
    if on_open is not None:
        on_open()
    traced = on_trace_end is not None
    for rec in w.records:
        if traced and now() >= w.t_open + trace_s:
            on_trace_end()
            traced = False
        _sleep_until(rec.due)
        w.lateness.append(now() - rec.due)
        th = threading.Thread(target=_serve_one, daemon=True,
                              args=(engine, make_request, rec, handles, lock))
        th.start()
        threads.append(th)
    if traced:
        _sleep_until(w.t_open + trace_s)
        on_trace_end()
    _sleep_until(w.t_close)
    w.drain_deadline = w.t_close + drain_s
    for th in threads:
        th.join(max(0.0, w.drain_deadline - now()))
    with lock:
        late = [h for rid, h in handles.items()
                if not w.records[rid].done]
    for h in late:
        h.cancel()
    for th in threads:
        th.join(10.0)
    engine.stop()
    return w


def run_closed(engine, make_request, traffic: dict, seed: int, vocab: int,
               seconds: float, before_start=None, on_open=None,
               on_trace_end=None, trace_s: float = 0.0,
               fill_timeout_s: float = 300.0) -> Window:
    """Closed loop: ``traffic["clients"]`` clients, each sending its next
    request when the last one completed.  The window opens once every
    client has its first token (the slots are full); at the close the
    requests still running are cancelled (the run is over, not failed)."""
    w = Window()
    clients = traffic["clients"]
    handles, lock = {}, threading.Lock()
    rids = itertools.count()
    stop = threading.Event()
    per_client: list[list[Record]] = [[] for _ in range(clients)]

    def client(c):
        for it in client_items(traffic, seed, vocab, c):
            if stop.is_set():
                break
            with lock:
                rid = next(rids)
            rec = Record(rid=rid, due=now(), prompt=it.prompt,
                         max_new=it.max_new)
            per_client[c].append(rec)
            _serve_one(engine, make_request, rec, handles, lock)

    if before_start is not None:
        before_start()
    engine.start()
    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for th in threads:
        th.start()
    deadline = now() + fill_timeout_s
    while not all(rs and (rs[0].times or rs[0].done) for rs in per_client):
        if now() > deadline:
            raise RuntimeError("the closed loop's first requests got no "
                               f"token within {fill_timeout_s} s")
        time.sleep(0.001)
    w.t_open = now()
    w.t_close = w.t_open + seconds
    if on_open is not None:
        on_open()
    if on_trace_end is not None:
        _sleep_until(w.t_open + trace_s)
        on_trace_end()
    _sleep_until(w.t_close)
    stop.set()
    give_up = now() + 60.0
    while any(th.is_alive() for th in threads) and now() < give_up:
        # again and again: a client may send one more request as the
        # window closes, and engine.stop() would serve it to the end
        with lock:
            running = [h for h in handles.values() if not h.req.done]
        for h in running:
            h.cancel()
        for th in threads:
            th.join(0.1)
    engine.stop()
    w.drain_deadline = w.t_close
    w.records = sorted((r for rs in per_client for r in rs),
                       key=lambda r: r.rid)
    return w


def _sleep_until(t: float):
    while True:
        d = t - now()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))
