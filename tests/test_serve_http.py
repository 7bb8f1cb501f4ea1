"""HTTP frontend tests: end-to-end smoke against an ephemeral port
(generate, streaming ndjson, classify micro-batching, healthz/metrics,
backpressure status codes, obs_serve records in metrics.jsonl) and the
slow-marked continuous-vs-sequential throughput regression."""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from tpunet.config import DataConfig, ModelConfig, ServeConfig
from tpunet.models import create_model, init_variables
from tpunet.serve import ClassifyBatcher, Engine, ServeServer

TINY = ModelConfig(name="lm", vit_hidden=32, vit_depth=2, vit_heads=2,
                   dropout_rate=0.0, dtype="float32", vocab_size=256,
                   max_seq_len=64)


def make_server(tmp_path=None, *, with_classifier=False, **cfg_kw):
    cfg_kw.setdefault("slots", 2)
    cfg_kw.setdefault("queue_max", 4)
    cfg_kw.setdefault("prefill_buckets", (16,))
    cfg_kw.setdefault("default_max_new_tokens", 8)
    cfg_kw.setdefault("emit_every_s", 0.0)
    cfg = ServeConfig(**cfg_kw)
    model = create_model(TINY)
    variables = init_variables(model, jax.random.PRNGKey(0), seq_len=8)
    engine = Engine(model, variables, cfg)
    metrics_logger = None
    if tmp_path is not None:
        from tpunet.obs.registry import JsonlSink
        from tpunet.utils.logging import MetricsLogger
        metrics_logger = MetricsLogger(str(tmp_path))
        engine.registry.add_sink(JsonlSink(metrics_logger))
    batcher = None
    if with_classifier:
        from tpunet.infer.predict import Predictor
        pred = Predictor(
            model_cfg=ModelConfig(dtype="float32", width_mult=0.5,
                                  dropout_rate=0.0),
            data_cfg=DataConfig(image_size=32))
        batcher = ClassifyBatcher(pred, batch_max=4, window_ms=5.0,
                                  registry=engine.registry)
    return ServeServer(engine, classify_batcher=batcher, port=0,
                       metrics_logger=metrics_logger).start()


def post(base, path, obj, timeout=120):
    req = urllib.request.Request(
        base + path, json.dumps(obj).encode(),
        {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def get(base, path, timeout=30):
    try:
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_http_end_to_end(tmp_path):
    """One server, the whole surface: healthz, token + text generate,
    parity with solo decode, streaming, classify 503 (none configured),
    bad-request 400s, metrics, drain -> healthz 503 + obs_serve record
    in metrics.jsonl."""
    srv = make_server(tmp_path)
    base = f"http://127.0.0.1:{srv.port}"
    try:
        code, health = get(base, "/healthz")
        assert code == 200 and health["status"] == "ok"
        assert health["slots"] == 2

        code, out = post(base, "/v1/generate",
                         {"prompt": "hello", "max_new_tokens": 5})
        assert code == 200
        assert len(out["tokens"]) == 5
        assert out["finish_reason"] == "length"
        assert isinstance(out["text"], str)
        assert out["ttft_ms"] > 0 and out["e2e_ms"] >= out["ttft_ms"]

        # token-id prompts hit the same engine path
        code, out2 = post(base, "/v1/generate",
                          {"tokens": [104, 101, 108, 108, 111],
                           "max_new_tokens": 5})
        assert code == 200 and out2["tokens"] == out["tokens"]

        # streaming: ndjson token events, then the done frame
        req = urllib.request.Request(
            base + "/v1/generate",
            json.dumps({"prompt": "hi", "max_new_tokens": 4,
                        "stream": True}).encode(),
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200
            assert "ndjson" in r.headers["Content-Type"]
            lines = [json.loads(line) for line in
                     r.read().decode().strip().splitlines()]
        assert len(lines) == 5
        assert all("token" in ev for ev in lines[:4])
        assert lines[-1] == {**lines[-1], "done": True,
                             "finish_reason": "length", "n_tokens": 4}

        # error surface
        assert post(base, "/v1/generate", {})[0] == 400
        assert post(base, "/v1/generate", {"tokens": []})[0] == 400
        assert post(base, "/v1/generate",
                    {"tokens": [999]})[0] == 400     # out of vocab
        assert post(base, "/v1/generate",
                    {"tokens": [1] * 40})[0] == 413  # > largest bucket
        assert post(base, "/v1/classify", {"image": [[0]]})[0] == 503
        assert get(base, "/nope")[0] == 404

        code, snap = get(base, "/metrics")
        assert code == 200
        assert snap["serve_requests_total"] >= 3
        assert snap["serve_tokens_total"] >= 14
        assert "serve_ttft_s_p50" in snap

    finally:
        srv.drain(timeout=30.0)
    # after drain the listener is down; the obs_serve record flushed
    recs = [json.loads(line) for line in
            (tmp_path / "metrics.jsonl").read_text().splitlines()]
    serve_recs = [r for r in recs if r.get("kind") == "obs_serve"]
    assert serve_recs, "drain must flush a final obs_serve record"
    final = serve_recs[-1]
    assert final["final"] and final["requests_total"] >= 3
    assert final["queue_depth"] == 0 and final["active_slots"] == 0


def _eight_way_outputs(srv):
    """8 concurrent POSTs through 2 slots; returns the token lists."""
    base = f"http://127.0.0.1:{srv.port}"
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, size=int(n)).astype(int).tolist()
               for n in rng.integers(2, 10, size=8)]
    results = [None] * 8

    def worker(i):
        results[i] = post(base, "/v1/generate",
                          {"tokens": prompts[i], "max_new_tokens": 6})

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    outs = []
    for res in results:
        assert res is not None, "worker timed out"
        code, out = res
        assert code == 200, out
        outs.append(out["tokens"])
    return prompts, outs


def test_http_concurrent_parity_eight_requests():
    """The ISSUE acceptance check: 8 concurrent POSTs through 2 slots
    return token-identical output to solo greedy decode."""
    from tpunet.models.lm import generate

    srv = make_server(queue_max=8)
    model = srv.engine.model
    variables = srv.engine.variables
    try:
        prompts, outs = _eight_way_outputs(srv)
        for p, out in zip(prompts, outs):
            solo = np.asarray(generate(
                model, variables,
                np.asarray(p, np.int32)[None], n_new=6))[0, len(p):]
            assert out == solo.tolist()
    finally:
        srv.drain(timeout=10.0)


def test_http_response_reports_effective_budget():
    """The clamp satellite over the wire: a budget clamped at
    admission (operator cap / KV length) surfaces as max_new_tokens +
    requested_max_new_tokens in the response metadata instead of a
    silently short token list."""
    srv = make_server(max_new_tokens_cap=4)
    base = f"http://127.0.0.1:{srv.port}"
    try:
        code, out = post(base, "/v1/generate",
                         {"prompt": "hi", "max_new_tokens": 50})
        assert code == 200
        assert len(out["tokens"]) == 4
        assert out["max_new_tokens"] == 4
        assert out["requested_max_new_tokens"] == 50
        # an unclamped request reports its effective budget only
        code, out2 = post(base, "/v1/generate",
                          {"prompt": "hi", "max_new_tokens": 3})
        assert code == 200
        assert out2["max_new_tokens"] == 3
        assert "requested_max_new_tokens" not in out2
    finally:
        srv.drain(timeout=10.0)


def test_http_queue_full_returns_429():
    """Backpressure over the wire: slots busy + queue at bound -> 429
    queue_full, and the rejected counter ticks."""
    srv = make_server(slots=1, queue_max=1,
                      default_max_new_tokens=60)
    base = f"http://127.0.0.1:{srv.port}"
    try:
        slow = []

        def bg():
            slow.append(post(base, "/v1/generate",
                             {"prompt": "a", "max_new_tokens": 60}))

        threads = [threading.Thread(target=bg) for _ in range(3)]
        for t in threads:
            t.start()
            time.sleep(0.15)   # let each land: slot, queue, reject
        got_429 = None
        deadline = time.perf_counter() + 30
        while got_429 is None and time.perf_counter() < deadline:
            code, out = post(base, "/v1/generate",
                             {"prompt": "b", "max_new_tokens": 60})
            if code == 429:
                got_429 = out
            else:
                time.sleep(0.05)
        assert got_429 is not None, "never saw a 429 under overload"
        assert got_429["error"] == "queue_full"
        for t in threads:
            t.join(timeout=300)
        code, snap = get(base, "/metrics")
        assert snap["serve_requests_rejected"] >= 1
    finally:
        srv.drain(timeout=10.0)


def test_http_classify_micro_batched():
    """Concurrent /v1/classify requests coalesce into shared batched
    forwards and return the Predictor's exact probabilities."""
    srv = make_server(with_classifier=True)
    base = f"http://127.0.0.1:{srv.port}"
    try:
        rng = np.random.default_rng(0)
        imgs = [rng.integers(0, 256, (32, 32, 3)).astype(int).tolist()
                for _ in range(6)]
        results = [None] * 6

        def worker(i):
            results[i] = post(base, "/v1/classify",
                              {"image": imgs[i], "topk": 3})

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        pred = srv.classify.predictor
        for img, res in zip(imgs, results):
            assert res is not None
            code, out = res
            assert code == 200, out
            assert len(out["topk"]) == 3
            ref = pred.predict_probs(np.asarray(img, np.uint8))
            got = np.asarray([out["probs"][n]
                              for n in pred.class_names])
            np.testing.assert_allclose(got, ref, atol=2e-5)
        code, snap = get(base, "/metrics")
        assert snap["serve_classify_requests_total"] == 6
        # coalescing happened: fewer batches than requests
        assert snap["serve_classify_batches_total"] < 6
    finally:
        srv.drain(timeout=10.0)


def test_healthz_unhealthy_after_engine_crash():
    srv = make_server()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        def boom(*a, **k):
            raise RuntimeError("step exploded")

        srv.engine._step = boom
        try:
            post(base, "/v1/generate", {"prompt": "x"}, timeout=60)
        except Exception:
            pass
        deadline = time.perf_counter() + 30
        code = 200
        while code == 200 and time.perf_counter() < deadline:
            code, health = get(base, "/healthz")
            time.sleep(0.05)
        assert code == 503
        assert health["status"] == "unhealthy"
        assert "step exploded" in health["error"]
    finally:
        srv.drain(timeout=10.0)


def test_drain_under_load_finishes_stream_and_503s_new_requests():
    """Satellite: an in-flight ndjson stream COMPLETES (finish_reason
    length, not drain) while drain() runs, and requests arriving
    during the drain get 503 (Retry-After semantics pinned
    deterministically in the sibling test below)."""
    big = ModelConfig(name="lm", vit_hidden=32, vit_depth=2,
                      vit_heads=2, dropout_rate=0.0, dtype="float32",
                      vocab_size=256, max_seq_len=512)
    cfg = ServeConfig(slots=1, queue_max=4, prefill_buckets=(16,),
                      default_max_new_tokens=300, emit_every_s=0.0,
                      drain_timeout_s=60.0)
    model = create_model(big)
    variables = init_variables(model, jax.random.PRNGKey(0), seq_len=8)
    srv = ServeServer(Engine(model, variables, cfg), port=0).start()
    base = f"http://127.0.0.1:{srv.port}"
    req = urllib.request.Request(
        base + "/v1/generate",
        json.dumps({"prompt": "hi", "max_new_tokens": 300,
                    "stream": True}).encode(),
        {"Content-Type": "application/json"})
    resp = urllib.request.urlopen(req, timeout=120)
    first = json.loads(resp.readline())
    assert "token" in first

    drained = []
    t = threading.Thread(target=lambda: drained.append(
        srv.drain(timeout=60.0)))
    t.start()
    # While draining: new admissions are rejected 503, never queued.
    saw_503 = False
    deadline = time.perf_counter() + 30
    while not saw_503 and time.perf_counter() < deadline:
        try:
            code, out = post(base, "/v1/generate",
                             {"prompt": "x", "max_new_tokens": 2},
                             timeout=30)
        except (urllib.error.URLError, OSError):
            break              # listener already closed: drain done
        if code == 503:
            saw_503 = True
            assert out["error"] == "draining"
        else:
            time.sleep(0.005)
    # The in-flight stream ran to completion through the drain.
    lines = [json.loads(line) for line in resp]
    resp.close()
    done = ([first] + lines)[-1]
    assert done.get("done") and done["finish_reason"] == "length", done
    assert done["n_tokens"] == 300
    t.join(timeout=90)
    assert drained and drained[0], "drain did not finish clean"
    assert saw_503, "never observed a mid-drain 503 rejection"


def test_draining_503_carries_retry_after_header():
    """The Retry-After contract, deterministically: queue closed =>
    both /healthz and /v1/generate answer 503 with Retry-After."""
    srv = make_server(drain_timeout_s=45.0)
    base = f"http://127.0.0.1:{srv.port}"
    try:
        srv.engine._draining.set()
        srv.engine.queue.close()
        try:
            urllib.request.urlopen(base + "/healthz", timeout=10)
            assert False, "expected 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["status"] == "draining"
            assert int(e.headers["Retry-After"]) == 45
        try:
            req = urllib.request.Request(
                base + "/v1/generate",
                json.dumps({"prompt": "x"}).encode(),
                {"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=10)
            assert False, "expected 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert int(e.headers["Retry-After"]) == 45
    finally:
        srv.drain(timeout=10.0)


def test_healthz_carries_run_id():
    """The router matches webhook pages to replicas by the run_id the
    health probe returns."""
    srv = make_server()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        code, health = get(base, "/healthz")
        assert code == 200
        assert health["run_id"].startswith("serve-")
    finally:
        srv.drain(timeout=10.0)



def test_engine_step_error_is_an_error_answer_not_an_empty_one():
    """A step that raises kills the engine thread. The request must end
    with finish_reason 'error' and the cause, the HTTP frontend must
    answer non-200, and /healthz must fail — never a 200 with an empty
    token list (which is what a dead engine's ``result()`` returns)."""
    srv = make_server()
    base = f"http://127.0.0.1:{srv.port}"

    def boom(*a, **kw):
        raise RuntimeError("injected step failure")

    srv.engine._dispatch_step = boom
    try:
        code, out = post(base, "/v1/generate",
                         {"tokens": [1, 2, 3], "max_new_tokens": 4})
        assert code == 500, (code, out)
        assert out["finish_reason"] == "error"
        assert "injected step failure" in out["error"]
        assert out["tokens"] == []
        assert "injected step failure" in srv.engine.error
        # The dead engine admits nothing more: 503, not an empty 200.
        code, out = post(base, "/v1/generate",
                         {"tokens": [1, 2, 3], "max_new_tokens": 4})
        assert code == 503, (code, out)
        code, health = get(base, "/healthz")
        assert code != 200, health
    finally:
        srv.drain(timeout=10.0)


def _answer(engine, prompt, new_tokens):
    """Greedy tokens of one request — which must not have ended in
    error: ``result()`` returns the tokens whatever the finish reason,
    so a dead engine reads as an empty answer."""
    req = engine.submit(prompt, max_new_tokens=new_tokens)
    tokens = req.result(timeout=120)
    assert req.finish_reason in ("length", "stop"), \
        (req.finish_reason, req.error, engine.error)
    assert engine.error is None and not req.error
    assert len(tokens) == new_tokens
    return tokens


def test_engine_aot_store_roundtrip(tmp_path):
    """AOT warm-start parity: a second engine boot deserializes every
    program ('loaded') and produces token-identical greedy output."""
    from tpunet.serve.engine import build_aot_store

    cfg = ServeConfig(slots=2, queue_max=4, prefill_buckets=(16,),
                      default_max_new_tokens=8, emit_every_s=0.0)
    model = create_model(TINY)
    variables = init_variables(model, jax.random.PRNGKey(0), seq_len=8)
    store = build_aot_store(str(tmp_path), TINY, cfg)
    prompt = np.arange(5, dtype=np.int32)

    eng = Engine(model, variables, cfg, aot_store=store).start()
    try:
        toks1 = _answer(eng, prompt, 5)
    finally:
        eng.stop()
    assert all(v.startswith("compiled")
               for v in eng.aot_status.values())
    assert any(p.name.endswith(".aotx") for p in tmp_path.iterdir())

    eng2 = Engine(model, variables, cfg, aot_store=store).start()
    try:
        toks2 = _answer(eng2, prompt, 5)
    finally:
        eng2.stop()
    assert eng2.aot_status == {"w1": "loaded", "k4w16": "loaded"}
    assert toks2 == toks1

    # jit fallback (no store) agrees too.
    eng3 = Engine(model, variables, cfg).start()
    try:
        toks3 = _answer(eng3, prompt, 5)
    finally:
        eng3.stop()
    assert toks3 == toks1
    # A different pool shape is a clean store MISS, never a wrong
    # program.
    cfg4 = ServeConfig(slots=3, queue_max=4, prefill_buckets=(16,),
                       default_max_new_tokens=8, emit_every_s=0.0)
    store4 = build_aot_store(str(tmp_path), TINY, cfg4)
    eng4 = Engine(model, variables, cfg4, aot_store=store4).start()
    try:
        _answer(eng4, prompt, 2)
    finally:
        eng4.stop()
    assert all(v.startswith("compiled")
               for v in eng4.aot_status.values())


def test_serve_cli_argparser_roundtrip():
    """The module entry point's arg surface builds a coherent config
    (no server start — just the parse + bucket plumbing)."""
    from tpunet.serve.__main__ import build_argparser

    args = build_argparser().parse_args(
        ["--checkpoint-dir", "ck", "--slots", "3", "--queue-max", "5",
         "--prefill-buckets", "8,32", "--port", "0",
         "--vit-hidden", "32", "--vit-depth", "2", "--vit-heads", "2",
         "--max-seq-len", "64"])
    assert args.slots == 3 and args.queue_max == 5
    assert args.prefill_buckets == "8,32"
    assert args.vit_hidden == 32


@pytest.mark.slow
def test_continuous_batching_beats_sequential():
    """The regression the subsystem exists for: at concurrency >= 4,
    continuous batching through the slot pool must deliver >= 2x the
    total tokens/s of one-request-at-a-time generation of the same
    work (ISSUE acceptance bar; scripts/bench_serve.py measures the
    same thing off-CI)."""
    from tpunet.models.lm import generate

    model = create_model(TINY)
    variables = init_variables(model, jax.random.PRNGKey(0), seq_len=8)
    n_req, n_new = 6, 24
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, size=6).astype(np.int32)
               for _ in range(n_req)]

    # sequential: one compiled single-token program, one request at a
    # time (the tpunet/infer/generate.py serving shape) — warm up the
    # compile first so both sides race steady-state.
    generate(model, variables, prompts[0][None], n_new=2)
    t0 = time.perf_counter()
    for p in prompts:
        generate(model, variables, p[None], n_new=n_new)
    seq_s = time.perf_counter() - t0

    cfg = ServeConfig(slots=n_req, queue_max=n_req,
                      prefill_buckets=(8,), emit_every_s=0.0)
    eng = Engine(model, variables, cfg).start()
    try:
        # warm both engine programs (prefill bucket + decode step)
        eng.submit(prompts[0], max_new_tokens=2).result(timeout=120)
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
        for r in reqs:
            r.result(timeout=300)
        batched_s = time.perf_counter() - t0
    finally:
        eng.stop()
    speedup = seq_s / batched_s
    assert speedup >= 2.0, (
        f"continuous batching {n_req * n_new / batched_s:.0f} tok/s vs "
        f"sequential {n_req * n_new / seq_s:.0f} tok/s "
        f"(speedup {speedup:.2f}x < 2x)")
