"""Mid-stream request failover + serve-tier chaos harness.

Three layers, cheapest first: pure-logic units (chaos grammar +
hooks, the request journal, resume-request semantics, supervisor
chaos forwarding, the flock-deduped AOT store), stub-replica
integration (duplicate-at-the-seam suppression, journal-cap
degradation, deadline propagation, drain-during-failover), and THE
acceptance test: two real ``python -m tpunet.serve`` children behind
an in-process router with ``--chaos kill@tokens=N:replica=0`` — a
real SIGKILL of the serving replica after first bytes reached the
client, with the completed stream asserted bitwise against solo
generate (greedy) and against an uninterrupted engine (sampled).
"""

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from tpunet.config import RouterConfig, ServeConfig
from tpunet.router.journal import JournalEntry, RequestJournal
from tpunet.serve.chaos import (ServeChaos, ServeChaosError,
                                split_by_replica, spec_for_replica)
from tpunet.serve.scheduler import GenerateRequest

SCRIPTS = os.path.join(os.path.dirname(__file__), "..", "scripts")


def _smoke():
    sys.path.insert(0, SCRIPTS)
    try:
        return __import__("serve_chaos_smoke")
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# chaos grammar + hooks (no processes, injected kill/sleep)
# ---------------------------------------------------------------------------


def test_chaos_parse_good_and_bad():
    ch = ServeChaos.parse("kill@tokens=5;stall@tokens=3:ms=100;"
                          "drop-probe@prob=0.5:seed=7;"
                          "slow-stream@ms=2;kill@prefill")
    assert len(ch.events) == 5
    assert ch.render().startswith("kill@tokens=5")
    for bad in ("boom@tokens=1", "kill@step=1", "kill@tokens",
                "stall@tokens=3", "drop-probe@prob=0.5",
                "drop-probe@prob=2:seed=1", "kill@tokens=x",
                "kill@tokens=1:wat=2", ""):
        with pytest.raises(ServeChaosError):
            ServeChaos.parse(bad)


def test_chaos_replica_scoping():
    spec = "kill@tokens=5:replica=0;slow-stream@ms=10;" \
           "stall@tokens=2:ms=50:replica=1"
    assert split_by_replica(spec) == {
        0: "kill@tokens=5", None: "slow-stream@ms=10",
        1: "stall@tokens=2:ms=50"}
    assert spec_for_replica(spec, 0) == \
        "kill@tokens=5;slow-stream@ms=10"
    assert spec_for_replica(spec, 1) == \
        "slow-stream@ms=10;stall@tokens=2:ms=50"
    assert spec_for_replica(spec, 2) == "slow-stream@ms=10"
    assert spec_for_replica("", 0) == ""
    with pytest.raises(ServeChaosError):
        split_by_replica("kill@tokens=bad:replica=0")


def test_chaos_hooks_fire_deterministically():
    kills = []
    sleeps = []
    ch = ServeChaos.parse(
        "kill@tokens=3;kill@prefill=2;stall@tokens=2:ms=40",
        kill=lambda pid, sig: kills.append((pid, sig)),
        sleep=sleeps.append)
    ch.on_token()                      # 1: nothing
    assert not kills and not ch.stalled
    ch.on_token()                      # 2: stall arms
    assert ch.stalled and ch.stall_ms == 40.0
    ch.maybe_stall()
    assert sleeps == [0.04]
    ch.on_token()                      # 3: kill fires ONCE
    ch.on_token()
    assert len(kills) == 1
    ch.on_prefill()                    # ordinal 1: below the =2 mark
    assert len(kills) == 1
    ch.on_prefill()                    # ordinal 2: fires
    assert len(kills) == 2
    # drop-probe: same seed => same afflicted probes.
    runs = []
    for _ in range(2):
        probe = ServeChaos.parse("drop-probe@prob=0.5:seed=11",
                                 kill=lambda *a: None,
                                 sleep=lambda s: None)
        runs.append([probe.on_probe() for _ in range(16)])
    assert runs[0] == runs[1] and any(runs[0]) and not all(runs[0])


# ---------------------------------------------------------------------------
# request journal
# ---------------------------------------------------------------------------


def test_journal_roundtrip_cap_and_failover_accounting():
    journal = RequestJournal(max_tokens=3)
    entry = journal.open({"tokens": [1], "max_new_tokens": 8},
                         deadline_t=None)
    assert journal.active() == 1 and journal.active_failovers() == 0
    assert journal.note_token(entry, 5)
    assert journal.note_token(entry, 6)
    assert journal.note_token(entry, 7)
    assert not entry.over_cap
    assert not journal.note_token(entry, 8)   # cap: NOT recorded
    assert entry.over_cap and entry.tokens == [5, 6, 7]
    body = entry.resume_body()
    assert body["resume_tokens"] == [5, 6, 7] and body["stream"]
    assert entry.body.get("resume_tokens") is None  # original intact
    journal.begin_failover(entry)
    assert entry.failover_count == 1
    assert journal.active_failovers() == 1
    journal.end_failover(entry)
    assert journal.active_failovers() == 0
    journal.close(entry)
    assert journal.active() == 0
    journal.close(entry)                      # idempotent
    with pytest.raises(ValueError):
        RequestJournal(max_tokens=0)


def test_journal_entry_deadline_budget():
    entry = JournalEntry({}, deadline_t=time.monotonic() + 1.0)
    remaining = entry.remaining_ms()
    assert 0 < remaining <= 1000
    assert JournalEntry({}).remaining_ms() is None
    expired = JournalEntry({}, deadline_t=time.monotonic() - 0.1)
    assert expired.remaining_ms() <= 0


# ---------------------------------------------------------------------------
# resume-request semantics (no engine)
# ---------------------------------------------------------------------------


def test_generate_request_resume_tokens():
    req = GenerateRequest([1, 2], max_new_tokens=8,
                          resume_tokens=[7, 9, 11])
    assert req.tokens == [7, 9, 11] and req.resume_offset == 3
    # Journaled tokens are NOT re-emitted as events; a new push is.
    req.push_token(13)
    req.finish("length")
    events = list(req.events(timeout=1.0))
    assert events == [("token", 13), ("done", "length")]
    assert req.tokens == [7, 9, 11, 13]
    # A journal larger than the budget is a client error, not a hang.
    with pytest.raises(ValueError):
        GenerateRequest([1], max_new_tokens=2,
                        resume_tokens=[1, 2, 3])
    plain = GenerateRequest([1], max_new_tokens=2)
    assert plain.resume_offset == 0


def test_supervisor_forwards_scoped_chaos():
    from tpunet.router.supervisor import Supervisor
    sup = Supervisor(["--slots", "2"],
                     chaos="kill@tokens=5:replica=0;slow-stream@ms=9")
    argv0 = sup.child_argv(0, 8001, "r-0")
    argv1 = sup.child_argv(1, 8002, "r-1")
    assert argv0[argv0.index("--chaos") + 1] == \
        "kill@tokens=5;slow-stream@ms=9"
    assert argv1[argv1.index("--chaos") + 1] == "slow-stream@ms=9"
    # Caller-pinned --chaos in serve_args wins (not duplicated).
    sup2 = Supervisor(["--chaos", "kill@prefill"],
                      chaos="kill@tokens=5")
    assert sup2.child_argv(0, 1, "x").count("--chaos") == 1
    # Unscoped-empty: no flag at all.
    sup3 = Supervisor([], chaos="kill@tokens=5:replica=3")
    assert "--chaos" not in sup3.child_argv(0, 1, "x")


# ---------------------------------------------------------------------------
# AOT store: shared-filesystem dedup (flock-guarded commit)
# ---------------------------------------------------------------------------


def test_aot_store_concurrent_writers_dedup(tmp_path):
    """N concurrent writers of one entry key (the multi-host fleet
    sharing one --aot-cache dir): exactly one committed file, no tmp
    litter, every save reports success, and the committed entry
    load-verifies."""
    import jax
    import jax.numpy as jnp

    from tpunet.utils.cache import AotProgramStore, \
        serializable_compile

    with serializable_compile():
        compiled = jax.jit(lambda x: x + 1).lower(
            jax.ShapeDtypeStruct((4,), jnp.float32)).compile()
    store = AotProgramStore(str(tmp_path), "dedup-test")
    results = [None] * 6
    threads = [threading.Thread(
        target=lambda i=i: results.__setitem__(
            i, store.save("prog", "w1", compiled)))
        for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert all(results), results
    entries = [f for f in os.listdir(tmp_path)
               if f.endswith(AotProgramStore.SUFFIX)]
    assert len(entries) == 1, entries
    assert not [f for f in os.listdir(tmp_path)
                if ".tmp" in f], "tmp litter left behind"
    loaded = store.load("prog", "w1")
    assert loaded is not None
    out = np.asarray(loaded(jnp.zeros((4,), jnp.float32)))
    np.testing.assert_array_equal(out, np.ones(4, np.float32))
    # A later save of a committed key is a dedup no-op, not a rewrite.
    path = os.path.join(tmp_path, entries[0])
    before = os.stat(path).st_mtime_ns
    assert store.save("prog", "w1", compiled)
    assert os.stat(path).st_mtime_ns == before


# ---------------------------------------------------------------------------
# stub-replica integration (stdlib stubs, no engine)
# ---------------------------------------------------------------------------


def _stub_fleet(behaviors, **cfg_kw):
    smoke = _smoke()
    stubs = [smoke.StubReplica(f"fs{i}", b)
             for i, b in enumerate(behaviors)]
    router, server = smoke.make_router([s.url for s in stubs],
                                       **cfg_kw)
    smoke.wait_for(lambda: router.healthy_count() == len(stubs),
                   what="stubs healthy")
    return smoke, stubs, router, server


def test_duplicate_token_seam_suppressed():
    """The dying replica re-emits its last token at the seam AND the
    resumed stream is index-stamped: the client sees every index
    exactly once, greedy-identical to an uninterrupted stream."""
    smoke, stubs, router, server = _stub_fleet(
        [{"die_after_tokens": 4, "dup_at_seam": True}, {}])
    try:
        lines = smoke.read_stream(
            f"http://127.0.0.1:{server.port}",
            {"tokens": [10], "max_new_tokens": 10, "stream": True})
        toks = [ev["token"] for ev in lines if "token" in ev]
        idxs = [ev["i"] for ev in lines if "token" in ev]
        assert toks == smoke.expected_tokens(10, 10)
        assert idxs == list(range(10)), "indices not exactly-once"
        done = lines[-1]
        assert done["finish_reason"] == "length" \
            and "error" not in done
        assert done["failover_count"] == 1
    finally:
        server.drain()
        for s in stubs:
            s.close()


def test_journal_cap_honest_error_frame():
    """Past the cap, replica death degrades to the HONEST error frame
    (documented), never a silent truncation or a wrong resume."""
    smoke, stubs, router, server = _stub_fleet(
        [{"die_after_tokens": 6}, {}], failover_journal_tokens=3)
    try:
        lines = smoke.read_stream(
            f"http://127.0.0.1:{server.port}",
            {"tokens": [9], "max_new_tokens": 12, "stream": True})
        done = lines[-1]
        assert done["finish_reason"] == "error"
        assert "journal cap" in done["error"]
        assert done["n_tokens"] == 3     # what the journal still holds
        assert stubs[1].resumes == 0
    finally:
        server.drain()
        for s in stubs:
            s.close()


def test_deadline_header_propagates_and_expires():
    """X-Deadline-Ms: forwarded to the replica with the REMAINING
    budget (never more than the client sent), and an expired budget
    is a 504 carrying the partial token count."""
    smoke, stubs, router, server = _stub_fleet([{}, {}])
    base = f"http://127.0.0.1:{server.port}"
    try:
        lines = smoke.read_stream(
            base, {"tokens": [5], "max_new_tokens": 4,
                   "stream": True},
            headers=[("X-Deadline-Ms", "30000")])
        assert lines[-1]["finish_reason"] == "length"
        seen = [h for s in stubs for h in s.headers_seen
                if "X-Deadline-Ms" in h]
        assert seen, "deadline header not forwarded"
        assert all(0 < float(h["X-Deadline-Ms"]) <= 30000
                   for h in seen)
        # Pre-expired budget: 504 + partial count, replica untouched.
        before = sum(s.requests for s in stubs)
        req = urllib.request.Request(
            base + "/v1/generate",
            json.dumps({"tokens": [5], "stream": True}).encode(),
            {"Content-Type": "application/json",
             "X-Deadline-Ms": "0.001"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 504
        payload = json.loads(exc.value.read())
        assert payload == {"error": "deadline", "n_tokens": 0}
        assert sum(s.requests for s in stubs) == before
        # Garbage header: loud 400.
        req = urllib.request.Request(
            base + "/v1/generate",
            json.dumps({"tokens": [5]}).encode(),
            {"Content-Type": "application/json",
             "X-Deadline-Ms": "soon"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 400
    finally:
        server.drain()
        for s in stubs:
            s.close()


def test_drain_waits_for_inflight_failover():
    """A drain issued while a failover is in flight must not orphan
    the journaled request: drain blocks (against the shared grace
    budget) until the resume is re-homed, and the client stream still
    completes with no error frame."""
    smoke, stubs, router, server = _stub_fleet(
        [{"die_after_tokens": 2},
         {"resume_delay_s": 1.0, "line_delay_s": 0.05}],
        drain_grace_s=15.0)
    result = {}

    def client():
        try:
            result["lines"] = smoke.read_stream(
                f"http://127.0.0.1:{server.port}",
                {"tokens": [3], "max_new_tokens": 8, "stream": True},
                timeout=30)
        except Exception as e:  # noqa: BLE001
            result["error"] = e

    t = threading.Thread(target=client)
    t.start()
    try:
        smoke.wait_for(lambda: router.journal.active_failovers() > 0,
                       timeout=10, what="failover to begin")
        server.drain()                  # must block past the window
        assert router.journal.active_failovers() == 0
        t.join(timeout=30)
        assert not t.is_alive()
        assert "error" not in result, result.get("error")
        lines = result["lines"]
        toks = [ev["token"] for ev in lines if "token" in ev]
        assert toks == smoke.expected_tokens(3, 8)
        done = lines[-1]
        assert done["finish_reason"] == "length" \
            and "error" not in done
        assert done["failover_count"] == 1
    finally:
        for s in stubs:
            s.close()


# ---------------------------------------------------------------------------
# serve-side: X-Deadline-Ms through a real engine
# ---------------------------------------------------------------------------


def test_serve_honors_deadline_header(tmp_path):
    """The serve frontend maps X-Deadline-Ms into the engine
    scheduler's deadline: an exhausted budget finishes 'deadline'
    with the partial tokens it produced."""
    sys.path.insert(0, os.path.dirname(__file__))
    try:
        http_helpers = __import__("test_serve_http")
    finally:
        sys.path.pop(0)
    srv = http_helpers.make_server(default_max_new_tokens=64)
    base = f"http://127.0.0.1:{srv.port}"
    try:
        req = urllib.request.Request(
            base + "/v1/generate",
            json.dumps({"tokens": [1, 2, 3]}).encode(),
            {"Content-Type": "application/json",
             "X-Deadline-Ms": "1"})
        with urllib.request.urlopen(req, timeout=120) as r:
            out = json.loads(r.read())
        assert out["finish_reason"] == "deadline"
        assert len(out["tokens"]) < 64
        # The tighter of header and body wins.
        req = urllib.request.Request(
            base + "/v1/generate",
            json.dumps({"tokens": [1, 2, 3], "max_new_tokens": 4,
                        "deadline_s": 600.0}).encode(),
            {"Content-Type": "application/json",
             "X-Deadline-Ms": "1"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert json.loads(r.read())["finish_reason"] == "deadline"
    finally:
        srv.drain(5.0)


def _post(base, path, obj, timeout=120):
    req = urllib.request.Request(
        base + path, json.dumps(obj).encode(),
        {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_resume_stop_token_and_budget_guards(tmp_path):
    """Resume seams the engine must close: a journal already ending
    in the stop token finishes 'stop' immediately (never generates
    past the stop an uninterrupted run honored), one that meets the
    budget finishes 'length'; greedy and sampled resumes alike
    continue to the total budget (the sampler's keys are counted per
    (seed, step), so any replica can pick a stream up)."""
    sys.path.insert(0, os.path.dirname(__file__))
    try:
        http_helpers = __import__("test_serve_http")
    finally:
        sys.path.pop(0)
    srv = http_helpers.make_server()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        # Journal ends in the stop token -> immediate 'stop', no
        # generation.
        code, out = _post(base, "/v1/generate",
                          {"tokens": [1, 2], "max_new_tokens": 8,
                           "stop_token": 42,
                           "resume_tokens": [7, 42]})
        assert code == 200 and out["finish_reason"] == "stop"
        assert out["tokens"] == [7, 42]
        # A greedy resume continues to the total budget.
        code, out = _post(base, "/v1/generate",
                          {"tokens": [1, 2], "max_new_tokens": 6,
                           "resume_tokens": [7, 9]})
        assert code == 200 and out["finish_reason"] == "length"
        assert len(out["tokens"]) == 6 and out["tokens"][:2] == [7, 9]
        # Journal already meets the budget -> immediate 'length'.
        code, out = _post(base, "/v1/generate",
                          {"tokens": [1, 2], "max_new_tokens": 2,
                           "resume_tokens": [7, 9]})
        assert code == 200 and out["finish_reason"] == "length"
        assert out["tokens"] == [7, 9]
        code, out = _post(base, "/v1/generate",
                          {"tokens": [1, 2], "max_new_tokens": 8,
                           "temperature": 0.9, "seed": 3,
                           "resume_tokens": [7, 9]})
        assert code == 200 and out["finish_reason"] == "length"
        assert len(out["tokens"]) == 8 and out["tokens"][:2] == [7, 9]
    finally:
        srv.drain(5.0)


# ---------------------------------------------------------------------------
# THE acceptance test: real SIGKILL mid-stream through real HTTP
# ---------------------------------------------------------------------------

TINY_ARGS = ["--vit-hidden", "32", "--vit-depth", "2",
             "--vit-heads", "2", "--vocab-size", "256",
             "--max-seq-len", "256"]


def _pin_session_to(name: str) -> str:
    """A session string whose rendezvous-preferred replica (over the
    supervised fleet's stable names r0/r1) is ``name`` — routes the
    test stream onto the chaos-armed child deterministically."""
    from tpunet.router.balance import preferred_replica
    from tpunet.router.replica import ReplicaHandle
    fakes = [ReplicaHandle("r0", "http://x"),
             ReplicaHandle("r1", "http://x")]
    return next(s for s in (f"sess{i}" for i in range(256))
                if preferred_replica(fakes, f"s:{s}").name == name)


def _stream(base, body, timeout=240, headers=()):
    req = urllib.request.Request(
        base + "/v1/generate", json.dumps(body).encode(),
        {"Content-Type": "application/json", **dict(headers)})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return [json.loads(line) for line in resp]


def test_midstream_sigkill_failover_real_http(tmp_path):
    """SIGKILL of the serving replica mid-stream (after first bytes
    reached the client) produces a COMPLETE client stream with no
    error frame — greedy token-identical to an uninterrupted solo
    run, and a sampled stream deterministic across the failover
    (the (seed, step) counter-based sampling keys)."""
    import jax

    from tpunet.config import ModelConfig
    from tpunet.models import create_model, init_variables
    from tpunet.models.lm import generate
    from tpunet.router.__main__ import build_argparser, build_server
    from tpunet.serve.engine import Engine

    argv = ["--spawn", "2", "--port", "0",
            "--probe-interval-s", "0.2", "--probe-timeout-s", "2",
            "--unhealthy-after", "2", "--boot-timeout-s", "240",
            "--respawn-backoff-s", "0.2", "--emit-every-s", "0.5",
            "--min-replicas", "2", "--max-replicas", "2",
            "--metrics-dir", str(tmp_path),
            "--aot-cache", str(tmp_path / "aot"),
            "--chaos", "kill@tokens=12:replica=0", "--",
            "--checkpoint-dir", "", "--slots", "2",
            "--prefill-buckets", "64", "--queue-max", "16",
            "--max-new-tokens", "64"] + TINY_ARGS
    server = build_server(build_argparser().parse_args(argv)).start()
    router = server.router
    base = f"http://127.0.0.1:{server.port}"
    session = _pin_session_to("r0")
    try:
        _wait(lambda: router.healthy_count() == 2, timeout=240,
              what="both replicas healthy (cold boot)")

        # -- greedy: bitwise parity with an uninterrupted solo run ----
        model_cfg = ModelConfig(name="lm", vit_hidden=32, vit_depth=2,
                                vit_heads=2, vocab_size=256,
                                max_seq_len=256, dropout_rate=0.0)
        model = create_model(model_cfg)
        variables = init_variables(model, jax.random.PRNGKey(0),
                                   seq_len=16)
        prompt = np.asarray([17, 5, 211, 42, 9], np.int32)
        trace_id = "abad1deafee1900d"   # client-supplied: always sampled
        lines = _stream(base, {"tokens": prompt.tolist(),
                               "max_new_tokens": 24, "stream": True,
                               "session": session},
                        headers=[("X-Trace-Id", trace_id)])
        done = lines[-1]
        toks = [ev["token"] for ev in lines if "token" in ev]
        assert done.get("done") and done["finish_reason"] == "length", \
            f"stream must end cleanly across the SIGKILL: {done}"
        assert "error" not in done, done
        assert done.get("failover_count", 0) >= 1, \
            f"the kill never triggered a failover: {done}"
        solo = np.asarray(generate(model, variables, prompt[None],
                                   n_new=24))[0, prompt.size:]
        assert toks == solo.tolist(), \
            "failover stream diverged from uninterrupted solo generate"
        assert [ev["i"] for ev in lines if "token" in ev] \
            == list(range(24)), "token indices not exactly-once"

        # -- sampled: deterministic continuation across the failover --
        _wait(lambda: router.healthy_count() == 2, timeout=240,
              what="victim respawned healthy (AOT warm boot)")
        ref_engine = Engine(model, variables, ServeConfig(
            slots=2, prefill_buckets=(64,), emit_every_s=0.0)).start()
        try:
            ref = ref_engine.submit(prompt, max_new_tokens=24,
                                    temperature=0.9, seed=1234)
            ref_tokens = ref.result(timeout=120)
        finally:
            ref_engine.stop()
        lines = _stream(base, {"tokens": prompt.tolist(),
                               "max_new_tokens": 24, "stream": True,
                               "temperature": 0.9, "seed": 1234,
                               "session": session})
        done = lines[-1]
        toks = [ev["token"] for ev in lines if "token" in ev]
        assert done.get("done") and done["finish_reason"] == "length", \
            done
        assert "error" not in done, done
        assert done.get("failover_count", 0) >= 1, \
            "respawned replica's re-armed chaos never fired"
        assert toks == ref_tokens, \
            "sampled continuation diverged across the failover"

        snap = json.loads(urllib.request.urlopen(
            base + "/metrics", timeout=10).read())
        assert snap["router_failovers_total"] >= 2
    finally:
        server.drain()

    # -- failover events + counters in metrics.jsonl -------------------
    recs = [json.loads(line) for line in
            (tmp_path / "metrics.jsonl").read_text().splitlines()]
    events = [r for r in recs if r.get("kind") == "obs_router"
              and r.get("event") == "failover"]
    assert len(events) >= 2
    assert all(e["cause"] == "replica_failed_mid_stream"
               for e in events)
    windows = [r for r in recs if r.get("kind") == "obs_router"
               and not r.get("event")]
    assert windows[-1]["failovers_total"] >= 2

    # -- ONE trace_id spans both replicas, seam recorded ---------------
    # Router-role span: the client-supplied id, closed with the
    # failover seam accounting (docs/metrics_schema.md "obs_trace").
    spans = [r for r in recs if r.get("kind") == "obs_trace"
             and r.get("trace_id") == trace_id]
    assert len(spans) == 1 and spans[0]["role"] == "router", spans
    assert spans[0]["hop"] == 0
    assert spans[0]["finish_reason"] == "length"
    assert spans[0]["tokens"] == 24
    assert spans[0]["failover_count"] >= 1
    assert spans[0].get("tokens_relayed") is not None
    # Replica-role span: the SIGKILLed first hop never finishes (its
    # breadcrumbs survive in the crash-durable ring); the survivor's
    # resumed hop emits its span with the resume offset.
    rep_spans = []
    for rep_dir in sorted(tmp_path.glob("replica-*")):
        mfile = rep_dir / "metrics.jsonl"
        if not mfile.exists():
            continue
        rep_spans += [json.loads(line) for line
                      in mfile.read_text().splitlines()
                      if '"obs_trace"' in line]
    rep_spans = [r for r in rep_spans if r.get("trace_id") == trace_id]
    assert rep_spans, "no surviving replica emitted the resumed span"
    resumed = next(r for r in rep_spans if r.get("resume_offset"))
    assert resumed["role"] == "replica" and resumed["hop"] >= 2
    assert resumed["resume_offset"] + resumed["tokens"] == 24
    assert resumed["finish_reason"] == "length"

    # -- the timeline join renders one causal track --------------------
    from tpunet.obs.history.timeline import build_timeline
    trace = build_timeline(
        [str(tmp_path)] + [str(d) for d
                           in sorted(tmp_path.glob("replica-*"))])
    joined = [e for e in trace["traceEvents"]
              if e.get("args", {}).get("trace_id") == trace_id
              and e["pid"] == 1]
    names = {e["name"] for e in joined}
    assert "relay" in names, "router relay span missing from the join"
    assert "seam" in names, "failover seam missing from the join"
    # The dying hop's orphaned lifecycle is force-closed at the seam.
    assert any(e.get("args", {}).get("force_closed") == "failover_seam"
               for e in joined), "first hop never force-closed"
    # The track spans BOTH replicas: the router's open crumbs name a
    # different serving replica per hop (the victim's own ring was
    # recycled by its respawn — the router's record is what survives).
    reps = {e["args"]["replica"] for e in joined
            if e.get("args", {}).get("replica")}
    assert len(reps) >= 2, \
        f"trace does not span both replicas: {reps}"
    # The survivor's own breadcrumbs joined the track too.
    assert any(e.get("args", {}).get("process") for e in joined), \
        "no replica-side crumbs joined the track"


def _wait(pred, timeout=20.0, what=""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out: {what}")


# ---------------------------------------------------------------------------
# speculative decoding x failover: verified-only journal resume
# ---------------------------------------------------------------------------


def test_spec_midverify_stop_resumes_from_verified_journal():
    """A spec replica that stops mid-verify-window leaves a journal of
    VERIFIED tokens only (the engine never push_token()s a draft), so
    a survivor seeded with that journal continues the exact canonical
    stream. Modeled in-process: replica A's budget cuts its last burst
    in the middle of an accepted verify window (a full-accept
    self-speculating drafter guarantees the window overshoots), then
    replica B resumes with ``resume_tokens`` — greedy and sampled, the
    stitched stream must be bitwise an uninterrupted spec-off run."""
    import jax

    from tpunet.config import ModelConfig
    from tpunet.models import create_model, init_variables
    from tpunet.serve import Engine

    cfg = ModelConfig(name="lm", vit_hidden=32, vit_depth=2,
                      vit_heads=2, dropout_rate=0.0, dtype="float32",
                      vocab_size=31, max_seq_len=48)
    model = create_model(cfg)
    variables = init_variables(model, jax.random.PRNGKey(0), seq_len=8)

    def make(spec):
        return Engine(model, variables, ServeConfig(
            slots=2, queue_max=8, prefill_buckets=(8, 16),
            emit_every_s=0.0, spec_decode=spec, spec_k=3,
            spec_draft_width_mult=1.0)).start()

    rng = np.random.default_rng(31)
    prompt = rng.integers(0, 31, size=6).astype(np.int32)
    # Sampling params are per-request, so ONE spec-off and ONE spec-on
    # engine serve both the greedy and the sampled arm (compile once).
    eng_off, eng_on = make(False), make(True)
    try:
        for samp in (dict(),
                     dict(temperature=0.9, top_k=5, seed=77)):
            canonical = eng_off.submit(
                prompt, max_new_tokens=10, **samp).result(timeout=120)
            # Replica A: K=3 self-spec emits 4 verified tokens per
            # cycle; a budget of 6 stops it 2 tokens INTO the second
            # verify window. Its stream is the journal.
            journal = eng_on.submit(
                prompt, max_new_tokens=6, **samp).result(timeout=120)
            assert journal == canonical[:6], \
                f"journal is not a verified-only prefix ({samp})"
            # Replica B: resume from the journal, finish the budget
            # (counter-based keys make the resumed rows land on the
            # same (seed, step) stream the canonical run sampled).
            resumed = eng_on.submit(
                prompt, max_new_tokens=10, resume_tokens=journal,
                **samp).result(timeout=120)
            assert resumed == canonical, \
                f"survivor diverged after mid-verify resume ({samp})"
    finally:
        eng_off.stop()
        eng_on.stop()
