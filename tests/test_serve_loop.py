"""Background serve loop: threaded streaming pinned to the sync path.

The load-bearing pins:
  * loop-mode token streams (``start()`` + ``submit()`` +
    ``tokens()``-from-client-threads) are BYTE-IDENTICAL to the
    synchronous ``serve()`` path, for a mixed-family batch (attention,
    ssm, hybrid);
  * ``submit()`` is thread-safe: concurrent submits from many threads all
    finish with exactly the solo-reference output;
  * ``cancel()`` racing the final token never deadlocks and always
    terminates the stream;
  * ``stop(drain=True)`` finishes every in-flight request;
    ``stop(drain=False)`` leaves resumable state behind;
  * the injected clock is the single time base: a virtual clock makes
    deadline-miss accounting deterministic, and ``preempt()`` (cancel +
    requeue through the exact-accounting teardown) is greedy
    token-identical to an unpreempted run.
"""
import threading

import jax
import numpy as np
import pytest

from repro.models.registry import get_config, get_model
from repro.serve.config import EngineConfig
from repro.serve.engine import Engine, Request


class VirtualClock:
    """Hand-advanced monotonic clock (mirrors the load harness's)."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


def _setup(arch="yi-9b", **over):
    cfg = get_config(arch).reduced(dtype="float32", attn_impl="full", **over)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    return cfg, params


def _prompts(cfg, lens=(3, 9, 5, 12), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


def _consume_threaded(handles, timeout=120):
    """Drain every handle's token stream on its own client thread."""
    outs = [None] * len(handles)

    def consume(i):
        outs[i] = list(handles[i].tokens())

    threads = [threading.Thread(target=consume, args=(i,), daemon=True)
               for i in range(len(handles))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    assert all(not t.is_alive() for t in threads), "stream consumer hung"
    return outs


FAMILY_KNOBS = {
    "yi-9b": dict(paged=True, block_size=8),
    "mamba2-1.3b": dict(),
    "zamba2-1.2b": dict(paged=True, block_size=8),
}


@pytest.mark.parametrize("arch", sorted(FAMILY_KNOBS))
def test_loop_stream_identical_to_sync_mixed_family(arch):
    """Acceptance pin: background-loop token streams are byte-identical to
    the synchronous serve() path, for a mixed-length batch on every
    family (attention/paged, ssm, hybrid split-substrate)."""
    cfg, params = _setup(arch)
    prompts = _prompts(cfg)
    knobs = dict(max_batch=2, max_seq=48, **FAMILY_KNOBS[arch])

    sync = Engine(cfg, params, EngineConfig(**knobs))
    sync_reqs = [Request(rid=i, prompt=list(p), max_new=5)
                 for i, p in enumerate(prompts)]
    assert sync.serve(sync_reqs)["done"]
    ref = [list(r.out) for r in sync_reqs]

    loop = Engine(cfg, params, EngineConfig(**knobs)).start()
    try:
        loop_reqs = [Request(rid=i, prompt=list(p), max_new=5)
                     for i, p in enumerate(prompts)]
        handles = [loop.submit(r) for r in loop_reqs]
        outs = _consume_threaded(handles)
    finally:
        assert loop.stop(timeout=120)
    assert outs == ref
    assert [r.out for r in loop_reqs] == ref


def test_concurrent_submit_from_many_threads():
    """submit() is safe from concurrent client threads: every request
    finishes and matches its solo-reference output (2 slots, 8 requests
    from 4 threads — forces queueing through the loop-mode scheduler
    fallback)."""
    cfg, params = _setup()
    prompts = _prompts(cfg, lens=(3, 9, 5, 12, 4, 7, 6, 10))
    refs = []
    for i, p in enumerate(prompts):
        eng = Engine(cfg, params, EngineConfig(max_batch=1, max_seq=48))
        req = Request(rid=i, prompt=list(p), max_new=4)
        assert eng.serve([req])["done"]
        refs.append(list(req.out))

    loop = Engine(cfg, params,
                  EngineConfig(max_batch=2, max_seq=48)).start()
    reqs = [Request(rid=i, prompt=list(p), max_new=4)
            for i, p in enumerate(prompts)]
    outs = [None] * len(reqs)
    try:
        def client(idx):
            for i in range(idx, len(reqs), 4):
                h = loop.submit(reqs[i])
                outs[i] = list(h.tokens())

        threads = [threading.Thread(target=client, args=(k,), daemon=True)
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        assert all(not t.is_alive() for t in threads), "client thread hung"
    finally:
        assert loop.stop(timeout=120)
    assert outs == refs


def test_tokens_blocks_on_queue_while_loop_runs():
    """A tokens() consumer never ticks the engine itself in loop mode: the
    stream completes while the caller only blocks, and equals req.out."""
    cfg, params = _setup()
    loop = Engine(cfg, params,
                  EngineConfig(max_batch=2, max_seq=48)).start()
    try:
        req = Request(rid=0, prompt=_prompts(cfg)[1], max_new=6)
        handle = loop.submit(req)
        ticks_before = loop.metrics.ticks
        stream = list(handle.tokens())     # this thread never calls step()
        assert loop.metrics.ticks > ticks_before
        assert stream == req.out and len(stream) == 6 and req.done
    finally:
        assert loop.stop(timeout=120)


def test_cancel_races_final_token():
    """cancel() fired from another thread mid-stream: the generator always
    terminates (token count <= max_new), nothing deadlocks, and the
    request ends done — whether the cancel won or the final token did."""
    cfg, params = _setup()
    loop = Engine(cfg, params,
                  EngineConfig(max_batch=2, max_seq=48)).start()
    try:
        for attempt, cancel_after in enumerate((1, 2, 3)):
            req = Request(rid=attempt, prompt=_prompts(cfg)[3], max_new=8)
            handle = loop.submit(req)
            got = []
            canceller = None
            for tok in handle.tokens():
                got.append(tok)
                if len(got) == cancel_after:
                    canceller = threading.Thread(target=handle.cancel,
                                                 daemon=True)
                    canceller.start()
            if canceller is not None:
                canceller.join(60)
                assert not canceller.is_alive()
            assert req.done
            assert cancel_after <= len(got) <= 8
            assert got == req.out[:len(got)]
    finally:
        assert loop.stop(timeout=120)


def test_stop_drains_inflight_requests():
    """stop(drain=True) keeps ticking until every queued + active request
    finished — no submitted token is lost."""
    cfg, params = _setup()
    loop = Engine(cfg, params,
                  EngineConfig(max_batch=2, max_seq=48)).start()
    reqs = [Request(rid=i, prompt=list(p), max_new=4)
            for i, p in enumerate(_prompts(cfg))]
    handles = [loop.submit(r) for r in reqs]
    assert loop.stop(drain=True, timeout=180)
    assert not loop.running and loop.idle
    assert all(r.done and len(r.out) == 4 for r in reqs)
    # streams subscribed after the stop still replay the full backlog
    assert [list(h.tokens()) for h in handles] == [r.out for r in reqs]


def test_stop_without_drain_is_resumable():
    """stop(drain=False) exits at a tick boundary; the survivors stay
    queued/active and a sync serve() finishes them with the exact
    reference output (state is never torn down off-thread)."""
    cfg, params = _setup()
    prompts = _prompts(cfg)
    ref_eng = Engine(cfg, params, EngineConfig(max_batch=1, max_seq=48))
    ref = Request(rid=3, prompt=list(prompts[3]), max_new=6)
    assert ref_eng.serve([ref])["done"]

    loop = Engine(cfg, params, EngineConfig(max_batch=1, max_seq=48))
    req = Request(rid=3, prompt=list(prompts[3]), max_new=6)
    loop.serve([req], max_ticks=0)          # enqueue without ticking
    loop.start()
    assert loop.stop(drain=False, timeout=120)
    assert loop.serve([])["done"] or req.done   # drain the survivor
    assert req.done and req.out == ref.out


def test_virtual_clock_deadline_accounting():
    """The injected clock is the single time base: deadlines stamped in
    virtual seconds account hits/misses deterministically."""
    cfg, params = _setup()
    vc = VirtualClock()
    eng = Engine(cfg, params, EngineConfig(max_batch=2, max_seq=48),
                 clock=vc)
    prompts = _prompts(cfg)
    hit = Request(rid=0, prompt=prompts[0], max_new=2, deadline=1e9)
    miss = Request(rid=1, prompt=prompts[1], max_new=2, deadline=0.5)
    vc.advance(1.0)                  # past miss's deadline before admission
    assert eng.serve([hit, miss])["done"]
    assert eng.metrics.deadline_hits == 1
    assert eng.metrics.deadline_misses == 1
    assert hit.token_ts and hit.token_ts[0] == vc.now == 1.0
    assert hit.submit_ts == 1.0      # stamped on the same clock


def test_preempt_requeue_is_greedy_identical():
    """preempt() mid-decode (slot + reservation released through the
    cancel-path accounting, emitted tokens folded into the prompt,
    request requeued) continues the greedy stream token-identically to a
    run that was never preempted."""
    cfg, params = _setup()
    prompts = _prompts(cfg)
    knobs = EngineConfig(max_batch=2, max_seq=48, paged=True, block_size=8)

    ref_eng = Engine(cfg, params, knobs)
    ref = Request(rid=7, prompt=list(prompts[1]), max_new=8)
    assert ref_eng.serve([ref])["done"]

    eng = Engine(cfg, params, knobs)
    req = Request(rid=7, prompt=list(prompts[1]), max_new=8)
    eng.serve([req], max_ticks=0)
    for _ in range(4):
        eng.step()
    assert 0 < len(req.out) < 8 and not req.done
    free_before = eng.backend.free_capacity
    assert eng.preempt(req)
    assert eng.backend.free_capacity > free_before  # blocks really freed
    assert eng.metrics.preemptions == 1
    while not req.done:
        eng.step()
    assert req.out == ref.out
    # preempting a non-active (queued/finished) request is a no-op
    assert not eng.preempt(req)


def test_submit_backpressure_queues_under_loop():
    """Loop-mode contract shift: a backpressured submit() returns a falsy
    handle but the request is QUEUED — the loop admits it when capacity
    frees and the stream still completes."""
    cfg, params = _setup()
    loop = Engine(cfg, params,
                  EngineConfig(max_batch=1, max_seq=48)).start()
    try:
        reqs = [Request(rid=i, prompt=list(p), max_new=4)
                for i, p in enumerate(_prompts(cfg, lens=(6, 6, 6)))]
        # hold the engine lock across the submits so the loop cannot retire
        # the first request before the others arrive (on a loaded host it
        # otherwise can, and nothing backpressures)
        with loop._lock:
            handles = [loop.submit(r) for r in reqs]
        assert not all(handles), "3 requests on 1 slot must backpressure"
        outs = _consume_threaded(handles)
        assert all(len(o) == 4 for o in outs)
        assert outs == [r.out for r in reqs]
    finally:
        assert loop.stop(timeout=120)
