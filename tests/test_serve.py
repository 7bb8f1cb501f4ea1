"""Serving engine tests (tpunet/serve/): continuous batching over the
KV-slot pool on a tiny CPU LM — slot reuse, mid-flight admission token
parity with solo greedy decode, backpressure, deadlines, cancellation,
drain."""

import time

import jax
import numpy as np
import pytest

from tpunet.config import ModelConfig, ServeConfig
from tpunet.models import create_model, init_variables
from tpunet.models.lm import generate
from tpunet.serve import (Engine, GenerateRequest, PromptTooLongError,
                          QueueFullError, RequestQueue)
from tpunet.serve.scheduler import DrainingError

TINY = ModelConfig(name="lm", vit_hidden=32, vit_depth=2, vit_heads=2,
                   dropout_rate=0.0, dtype="float32", vocab_size=31,
                   max_seq_len=48)


@pytest.fixture(scope="module")
def tiny_lm():
    model = create_model(TINY)
    variables = init_variables(model, jax.random.PRNGKey(0), seq_len=8)
    return model, variables


def make_engine(tiny_lm, **cfg_kw):
    model, variables = tiny_lm
    cfg_kw.setdefault("slots", 4)
    cfg_kw.setdefault("queue_max", 8)
    cfg_kw.setdefault("prefill_buckets", (8, 16))
    cfg_kw.setdefault("default_max_new_tokens", 6)
    cfg_kw.setdefault("emit_every_s", 0.0)
    return Engine(model, variables, ServeConfig(**cfg_kw))


def prompts(n, rng_seed=0, lo=2, hi=9):
    rng = np.random.default_rng(rng_seed)
    return [rng.integers(0, TINY.vocab_size,
                         size=int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


def solo_greedy(tiny_lm, prompt, n_new):
    model, variables = tiny_lm
    out = generate(model, variables, np.asarray(prompt)[None],
                   n_new=n_new)
    return np.asarray(out)[0, len(prompt):].tolist()


# ---------------------------------------------------------------------------
# continuous batching correctness
# ---------------------------------------------------------------------------

def test_mid_flight_admission_matches_solo_greedy(tiny_lm):
    """The acceptance bar: 8 concurrent requests, admitted in waves so
    later ones join while earlier ones are mid-decode (2 slots force
    both queueing and slot REUSE), each return exactly the tokens solo
    greedy decode produces — per-slot masking means co-residents never
    contaminate each other."""
    eng = make_engine(tiny_lm, slots=2).start()
    try:
        ps = prompts(8)
        reqs = []
        for i, p in enumerate(ps):
            reqs.append(eng.submit(p, max_new_tokens=5))
            if i % 3 == 2:
                time.sleep(0.02)   # stagger admission mid-flight
        outs = [r.result(timeout=120) for r in reqs]
        for p, out, req in zip(ps, outs, reqs):
            assert out == solo_greedy(tiny_lm, p, 5), \
                f"request {req.id} diverged from solo decode"
            assert req.finish_reason == "length"
        # 8 requests through 2 slots: slots were reused.
        snap = eng.registry.snapshot()
        assert snap["serve_requests_completed"] == 8
        assert snap["serve_ttft_s_count"] == 8
        assert eng.active_slots() == 0
    finally:
        eng.stop()


def test_slot_reuse_across_staggered_requests(tiny_lm):
    """One slot, requests submitted strictly after the previous
    finished: every request runs in the SAME cache row and must not see
    the previous occupant's K/V (active-mask freeze + prefill
    overwrite)."""
    eng = make_engine(tiny_lm, slots=1).start()
    try:
        for seed in range(3):
            p = prompts(1, rng_seed=seed)[0]
            out = eng.submit(p, max_new_tokens=4).result(timeout=60)
            assert out == solo_greedy(tiny_lm, p, 4)
    finally:
        eng.stop()


def test_streamed_events_arrive_in_order(tiny_lm):
    eng = make_engine(tiny_lm).start()
    try:
        p = prompts(1)[0]
        req = eng.submit(p, max_new_tokens=4)
        events = list(req.events(timeout=60))
        kinds = [k for k, _ in events]
        assert kinds == ["token"] * 4 + ["done"]
        assert [v for k, v in events if k == "token"] == \
            solo_greedy(tiny_lm, p, 4)
        assert events[-1][1] == "length"
    finally:
        eng.stop()


def test_sampled_generation_deterministic_per_seed(tiny_lm):
    """Sampling keys are per request, folded from its seed: the
    same seed reproduces the same tokens, a different seed (almost
    surely) differs, and all tokens stay in-vocab."""
    eng = make_engine(tiny_lm).start()
    try:
        p = prompts(1)[0]
        kw = dict(max_new_tokens=8, temperature=1.0, top_k=10,
                  top_p=0.9)
        a = eng.submit(p, seed=7, **kw).result(timeout=60)
        b = eng.submit(p, seed=7, **kw).result(timeout=60)
        c = eng.submit(p, seed=8, **kw).result(timeout=60)
        assert a == b
        assert all(0 <= t < TINY.vocab_size for t in a)
        assert a != c or len(a) == 0  # vanishing collision odds
    finally:
        eng.stop()


def test_stop_token_finishes_early(tiny_lm):
    """A request whose stop_token is the model's first greedy token
    finishes with reason 'stop' after exactly one token."""
    p = prompts(1)[0]
    first = solo_greedy(tiny_lm, p, 1)[0]
    eng = make_engine(tiny_lm).start()
    try:
        req = eng.submit(p, max_new_tokens=6, stop_token=int(first))
        out = req.result(timeout=60)
        assert out == [first]
        assert req.finish_reason == "stop"
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# admission control / backpressure
# ---------------------------------------------------------------------------

def test_queue_full_rejection():
    q = RequestQueue(queue_max=2)
    q.submit(GenerateRequest([1], max_new_tokens=1))
    q.submit(GenerateRequest([1], max_new_tokens=1))
    with pytest.raises(QueueFullError):
        q.submit(GenerateRequest([1], max_new_tokens=1))
    assert q.depth() == 2


def test_engine_rejects_when_queue_bound_hit(tiny_lm):
    """Backpressure end-to-end: a stopped engine never drains its
    queue, so submits beyond queue_max must raise QueueFullError
    (frontend: 429) instead of growing the queue."""
    eng = make_engine(tiny_lm, slots=1, queue_max=2)  # NOT started
    eng.submit([1, 2], max_new_tokens=2)
    eng.submit([1, 2], max_new_tokens=2)
    with pytest.raises(QueueFullError):
        eng.submit([1, 2], max_new_tokens=2)
    snap = eng.registry.snapshot()
    assert snap["serve_requests_rejected"] == 1
    assert snap["serve_requests_total"] == 2


def test_prompt_too_long_rejected(tiny_lm):
    eng = make_engine(tiny_lm)   # buckets (8, 16), max_seq_len 48
    with pytest.raises(PromptTooLongError):
        eng.submit(np.zeros(17, np.int32))
    # fits the bucket but leaves no room to generate
    eng2 = make_engine(tiny_lm, prefill_buckets=(48,))
    with pytest.raises(PromptTooLongError):
        eng2.submit(np.zeros(48, np.int32))


def test_max_new_tokens_clamped_to_kv_length(tiny_lm):
    """A budget that would overflow the KV length is clamped, not
    rejected: prompt 40 + budget 100 against max_seq_len 48 yields
    exactly 8 tokens."""
    eng = make_engine(tiny_lm, prefill_buckets=(48,)).start()
    try:
        req = eng.submit(np.ones(40, np.int32), max_new_tokens=100)
        out = req.result(timeout=60)
        assert len(out) == 8
        assert req.finish_reason == "length"
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# deadlines / cancellation / drain / failure
# ---------------------------------------------------------------------------

def test_deadline_cancellation_frees_the_slot(tiny_lm):
    """A request with an already-tiny deadline is cancelled at an
    iteration boundary with reason 'deadline', its slot frees, and the
    NEXT request still decodes correctly in the freed slot."""
    eng = make_engine(tiny_lm, slots=1,
                      default_max_new_tokens=40).start()
    try:
        p = prompts(1)[0]
        doomed = eng.submit(p, max_new_tokens=40, deadline_s=0.001)
        doomed.result(timeout=60)
        assert doomed.finish_reason == "deadline"
        assert len(doomed.tokens) < 40
        # slot is reusable and clean
        out = eng.submit(p, max_new_tokens=4).result(timeout=60)
        assert out == solo_greedy(tiny_lm, p, 4)
        assert eng.registry.snapshot()["serve_finished_deadline"] == 1
    finally:
        eng.stop()


def test_client_cancel_frees_the_slot(tiny_lm):
    eng = make_engine(tiny_lm, slots=1,
                      default_max_new_tokens=40).start()
    try:
        p = prompts(1)[0]
        req = eng.submit(p, max_new_tokens=40)
        # wait for the first token so it is mid-decode, then cancel
        next(iter(req.events(timeout=60)))
        req.cancel()
        req.result(timeout=60)
        assert req.finish_reason == "cancelled"
        assert eng.active_slots() == 0
    finally:
        eng.stop()


def test_graceful_drain_finishes_in_flight(tiny_lm):
    """drain(): already-admitted AND already-queued requests finish
    with their exact tokens; submits during/after the drain are
    rejected."""
    eng = make_engine(tiny_lm, slots=1).start()
    try:
        ps = prompts(3)
        reqs = [eng.submit(p, max_new_tokens=4) for p in ps]
        assert eng.drain(timeout=120.0)
        for p, req in zip(ps, reqs):
            assert req.finish_reason == "length"
            assert list(req.tokens) == solo_greedy(tiny_lm, p, 4)
        with pytest.raises(DrainingError):
            eng.submit(ps[0])
    finally:
        eng.stop()


def test_drain_timeout_finishes_survivors_with_drain_reason(tiny_lm):
    """When the drain budget expires, BOTH the in-flight request and
    the still-queued one finish with reason 'drain' (not 'cancelled' —
    the shutdown took them, not a client) and the counter ticks for
    each."""
    eng = make_engine(tiny_lm, slots=1, default_max_new_tokens=500,
                      max_new_tokens_cap=2048)
    real_step = eng._step

    def slow_step(*a, **k):
        time.sleep(0.05)
        return real_step(*a, **k)

    eng._step = slow_step
    eng.start()
    inflight = eng.submit(prompts(1)[0], max_new_tokens=40)
    queued = eng.submit(prompts(1, rng_seed=1)[0], max_new_tokens=40)
    next(iter(inflight.events(timeout=60)))   # mid-decode for sure
    assert not eng.drain(timeout=0.05)        # budget too small
    inflight.result(timeout=30)
    queued.result(timeout=30)
    assert inflight.finish_reason == "drain"
    assert queued.finish_reason == "drain"
    assert eng.registry.snapshot()["serve_finished_drain"] == 2
    assert eng.active_slots() == 0


def test_stop_unblocks_waiting_clients(tiny_lm):
    """stop() must FINISH in-flight requests, not just cancel them — a
    client blocked in result() unblocks immediately instead of at its
    own timeout."""
    eng = make_engine(tiny_lm, slots=1, default_max_new_tokens=500,
                      max_new_tokens_cap=2048)
    real_step = eng._step

    def slow_step(*a, **k):
        time.sleep(0.05)
        return real_step(*a, **k)

    eng._step = slow_step
    eng.start()
    req = eng.submit(prompts(1)[0], max_new_tokens=40)
    next(iter(req.events(timeout=60)))        # mid-decode
    t0 = time.perf_counter()
    eng.stop()
    req.result(timeout=5)                     # must not need 5s
    assert time.perf_counter() - t0 < 15
    assert req.done and req.finish_reason == "cancelled"


def test_drain_never_started_engine_returns_fast(tiny_lm):
    """drain() on an engine whose thread never ran must not sit out
    the whole budget — there is no loop to finish the work."""
    eng = make_engine(tiny_lm, slots=1)       # NOT started
    queued = eng.submit(prompts(1)[0], max_new_tokens=4)
    t0 = time.perf_counter()
    assert not eng.drain(timeout=30.0)        # work was left behind
    assert time.perf_counter() - t0 < 5
    assert queued.done and queued.finish_reason == "drain"
    assert eng.registry.snapshot()["serve_finished_drain"] == 1
    # and an idle never-started engine drains clean
    eng2 = make_engine(tiny_lm, slots=1)
    assert eng2.drain(timeout=30.0)


def test_queued_cancel_and_deadline_are_accounted(tiny_lm):
    """Requests finished while still QUEUED (cancelled / expired
    before reaching a slot) must tick the same serve_finished_*
    counters as slot-finishes: requests_total reconciles with
    rejected + finished."""
    eng = make_engine(tiny_lm, slots=1, default_max_new_tokens=500,
                      max_new_tokens_cap=2048)
    real_step = eng._step

    def slow_step(*a, **k):
        time.sleep(0.05)
        return real_step(*a, **k)

    eng._step = slow_step
    eng.start()
    try:
        hog = eng.submit(prompts(1)[0], max_new_tokens=40)
        victim = eng.submit(prompts(1, rng_seed=1)[0],
                            max_new_tokens=4)
        expired = eng.submit(prompts(1, rng_seed=2)[0],
                             max_new_tokens=4, deadline_s=0.01)
        victim.cancel()
        # queued finishes are detected when the hog frees the slot
        victim.result(timeout=60)
        expired.result(timeout=60)
        hog.result(timeout=60)
        assert victim.finish_reason == "cancelled"
        assert expired.finish_reason == "deadline"
        assert hog.finish_reason == "length"
        snap = eng.registry.snapshot()
        assert snap["serve_finished_cancelled"] == 1
        assert snap["serve_finished_deadline"] == 1
        assert snap["serve_finished_length"] == 1
        assert snap["serve_requests_total"] == 3
        # reconciliation: total == rejected + sum(finished_*)
        finished = sum(v for k, v in snap.items()
                       if k.startswith("serve_finished_"))
        assert finished + snap.get("serve_requests_rejected", 0) == 3
    finally:
        eng.stop()


def test_engine_failure_fails_requests_and_health(tiny_lm):
    """An engine-thread crash must fail in-flight and queued requests
    fast (finish_reason 'error') and flip healthy False — the /healthz
    503 path — instead of hanging clients."""
    eng = make_engine(tiny_lm, slots=1, default_max_new_tokens=40)

    def boom(*a, **k):
        raise RuntimeError("device fell over")

    eng._step = boom
    eng.start()
    try:
        # the submit may lose the race with the engine dying
        req = eng.submit(prompts(1)[0])
    except DrainingError:
        req = None
    if req is not None:
        req.result(timeout=60)
        assert req.finish_reason == "error"
        assert "device fell over" in (req.error or "")
    deadline = time.perf_counter() + 30
    while eng.healthy and time.perf_counter() < deadline:
        time.sleep(0.01)
    assert not eng.healthy
    assert "device fell over" in (eng.error or "")
    with pytest.raises(DrainingError):
        eng.submit(prompts(1)[0])


@pytest.mark.parametrize("surface,name", [
    ("config", "paged_kv"), ("config", "device_sampling"),
    ("cli", "--no-paged-kv"), ("cli", "--no-device-sampling")])
def test_cache_layout_and_sampler_are_not_options(surface, name, capsys):
    """An engine holds a paged pool and samples on the device, always:
    the dataclass and argparse reject the two options that used to
    select otherwise by themselves (no shim, nothing silently
    ignored)."""
    if surface == "config":
        with pytest.raises(TypeError, match=name):
            ServeConfig(**{name: True})
        return
    from tpunet.serve.__main__ import main
    with pytest.raises(SystemExit) as exit_info:
        main([name])
    assert exit_info.value.code == 2
    assert f"unrecognized arguments: {name}" in capsys.readouterr().err
