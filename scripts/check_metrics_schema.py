#!/usr/bin/env python
"""Schema-conformance check: no record field leaves the code
undocumented.

docs/metrics_schema.md is the contract between the trainer/server/
aggregator and every consumer — but nothing used to enforce it, and
fields drifted in silently (the PR-3 obs_serve kind shipped fields the
doc didn't know). This script closes the loop from the emitting side:
it drives every obs / serve / agg record-emission path against an
in-memory sink (no run, no devices — CPU jax only), then asserts that
every emitted ``kind`` and every top-level field is documented in the
schema file. The check is one-directional on purpose: the doc may
describe more than one run emits (fields are often conditional), but
the code may never emit what the doc doesn't describe.

Run standalone (exit 1 on drift, listing the offenders), or through
the non-slow ``tests/test_schema_conformance.py``.
"""

from __future__ import annotations

import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SCHEMA_PATH = os.path.join(os.path.dirname(__file__), "..",
                           "docs", "metrics_schema.md")

# The kind assigned to records with no "kind" field (plain epoch rows).
PLAIN = "<plain>"


# ---------------------------------------------------------------------------
# doc side: parse documented kinds and field names
# ---------------------------------------------------------------------------


def _expand_braces(text: str):
    """``ttft_{p50,p90}_s`` -> ttft_p50_s, ttft_p90_s (one level)."""
    m = re.search(r"\{([^{}]*)\}", text)
    if not m:
        yield text
        return
    for alt in m.group(1).split(","):
        yield from _expand_braces(text[:m.start()] + alt.strip()
                                  + text[m.end():])


def _span_tokens(span: str):
    """Field-name tokens inside one backticked span."""
    for expanded in _expand_braces(span):
        for tok in re.findall(r"[A-Za-z_][A-Za-z0-9_]*", expanded):
            yield tok


def parse_schema(path: str = SCHEMA_PATH):
    """-> (kinds, fields_by_kind, global_fields). Field sets are the
    union of identifier tokens in the section's code spans — a
    deliberate superset (prose code spans add stray tokens), since the
    check only runs emitted ⊆ documented."""
    with open(path) as f:
        lines = f.read().splitlines()
    kinds: set = set()
    fields: dict = {}
    global_fields: set = set()
    current = None          # a kind, "GLOBAL", or None
    for line in lines:
        if line.startswith("## "):
            current = None
            m = re.match(r"##\s+`([a-z_]+)`", line)
            if m:
                current = m.group(1)
                kinds.add(current)
                fields.setdefault(current, set())
            elif "Plain epoch record" in line:
                current = PLAIN
                kinds.add(PLAIN)
                fields.setdefault(PLAIN, set())
            elif "Run identity" in line:
                # Identity fields are stamped on EVERY kind.
                current = "GLOBAL"
            continue
        if current is None:
            continue
        dest = global_fields if current == "GLOBAL" else fields[current]
        for span in re.findall(r"`([^`]+)`", line):
            dest.update(_span_tokens(span))
    return kinds, fields, global_fields


# ---------------------------------------------------------------------------
# code side: drive every emission path into a MemorySink
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def collect_obs_records(tmpdir: str) -> list:
    """obs_epoch / obs_step / obs_alert (every watchdog reason) via
    the real Observability facade."""
    import dataclasses

    from tpunet.config import ObsConfig
    from tpunet.obs import Observability
    from tpunet.obs.health import Watchdog
    from tpunet.obs.registry import MemorySink

    cfg = ObsConfig(step_records_every=1)
    obs = Observability(cfg, checkpoint_dir=tmpdir)
    sink = MemorySink()
    obs.add_sink(sink)
    obs.set_flops_per_unit(1e6)
    obs.begin_epoch(1)
    for step in range(1, 4):
        obs.observe_data_wait(0.002)
        obs.observe_step(step, 0.01 + 0.001 * step)
        obs.observe_loss(step, 1.0)
    obs.registry.counter("ckpt_saves").inc()
    obs.registry.counter("ckpt_wait_s").inc(0.5)
    obs.end_epoch(epoch=1, step=3, units=300.0, train_seconds=0.05,
                  eval_seconds=0.01, partial=True)
    obs.close()

    # Watchdog: drive every alert reason with an injected clock.
    clock = _FakeClock()
    wcfg = dataclasses.replace(
        cfg, stall_factor=2.0, stall_min_s=0.0, loss_spike_factor=2.0,
        heartbeat_timeout_s=10.0, alert_cooldown_steps=0,
        gauge_rules=("some_gauge > 1", "some_gauge + 0.1/s"))
    from tpunet.obs.registry import Registry
    reg = Registry()
    reg.set_identity(run_id="check", process_index=0, host="h")
    reg.add_sink(sink)
    wd = Watchdog(wcfg, reg, expected_processes=2, clock=clock)
    for i in range(Watchdog.MIN_BASELINE):
        wd.observe_step(i, 0.01)
    wd.observe_step(20, 1.0)                      # step_stall
    wd.observe_loss(21, float("nan"))             # nan_loss
    for i in range(Watchdog.MIN_LOSS_OBS + 1):
        wd.observe_loss(22 + i, 1.0)
    wd.observe_loss(40, 100.0)                    # loss_spike
    clock.t += 100.0
    wd.check_heartbeat(step=41)                   # stale_heartbeat
    wd.observe_heartbeat(live=1, step=42)         # missing_processes
    reg.gauge("some_gauge").set(5.0)
    wd.check_gauges(43, reg.snapshot())           # threshold rule
    for i in range(4):                            # growth rule
        reg.gauge("some_gauge").set(5.0 + i)
        clock.t += 1.0
        wd.check_gauges(44 + i, reg.snapshot())
    # thread_stalled: a registered host thread busy past its budget.
    from tpunet.obs.flightrec.threads import THREADS
    handle = THREADS.register("schema-check", stall_after_s=1.0,
                              clock=clock)
    try:
        handle.beat("busy")
        clock.t += 10.0
        wd.check_threads(50)
    finally:
        THREADS.unregister("schema-check")
    return sink.records


def collect_crash_records(tmpdir: str) -> list:
    """obs_crash via the real path: a flightrec artifact dir is
    assembled into a report, detected as a prior crash, and emitted."""
    from tpunet.obs import flightrec
    from tpunet.obs.flightrec import report as frreport
    from tpunet.obs.registry import MemorySink, Registry

    rec = flightrec.FlightRecorder(tmpdir, watcher=False, native=False)
    rec.install()
    rec.record("span", "step 1")
    rec.refresh_threads()
    frreport.write_report(rec.directory)
    rep, path = flightrec.prior_crash_report(tmpdir)
    # Close NOW (restores faulthandler, releases the stacks file):
    # the recorder must not outlive the tmpdir it points into.
    rec.close()
    assert rep is not None
    reg = Registry()
    reg.set_identity(run_id="crash-check", process_index=0, host="h")
    sink = MemorySink()
    reg.add_sink(sink)
    reg.emit("obs_crash", flightrec.crash_record(rep, path))
    return sink.records


def collect_serve_records() -> list:
    """obs_serve via the factored record builder (no engine/model
    needed — the builder IS the record shape). The prefix-KV-cache
    instruments are driven through the REAL host-side cache (lookup
    miss -> insert -> hit -> pin/unpin -> evict), not hand-set, so a
    renamed instrument fails here before it drifts from the doc."""
    from tpunet.obs.registry import MemorySink, Registry
    from tpunet.serve.engine import build_serve_record
    from tpunet.serve.prefixcache import PrefixCache, chain_digests

    reg = Registry()
    reg.set_identity(run_id="serve-check", process_index=0, host="h")
    sink = MemorySink()
    reg.add_sink(sink)
    for name in ("serve_requests_total", "serve_requests_completed",
                 "serve_requests_rejected", "serve_tokens_total",
                 "serve_decode_steps_total", "serve_prefills_total"):
        reg.counter(name).inc(3)
    for name in ("serve_ttft_s", "serve_token_s", "serve_e2e_s",
                 "serve_prefill_s"):
        for i in range(5):
            reg.histogram(name).observe(0.01 * (i + 1))
    cache = PrefixCache(page_tokens=4, capacity=4, registry=reg)
    toks = list(range(8))
    assert cache.lookup(toks, 2) == []            # miss
    d0, d1 = chain_digests(toks, 4, 2)
    n0 = cache.insert(d0, None, 0, 1)
    n1 = cache.insert(d1, n0, 1, 2)
    chain = cache.lookup(toks, 2)                 # hit, 2 pages
    assert [n.page for n in chain] == [1, 2]
    cache.pin(chain)
    cache.unpin(chain)
    assert cache.evict_one() == 2                 # leaf-first
    # engine-side counters of the same family (COW copies, shared-FS
    # spill/warm-start) — incremented exactly as the engine does
    for name in ("serve_prefix_cow_total", "serve_prefix_spills_total",
                 "serve_prefix_warm_loads_total"):
        reg.counter(name).inc()
    # the engine thread's phases, as ``Engine._emit_record`` sets them
    for phase, seconds in (("prefix_adopt", 1.1), ("decode_wait", 7.5)):
        reg.gauge("serve_host_s_" + phase).set(seconds)
        reg.gauge("serve_host_max_s_" + phase).set(seconds / 10)
    record = build_serve_record(
        reg, queue_depth=1, active_slots=2, slots=4,
        uptime_s=12.0, window_s=3.0, final=True)
    assert record["prefix_hit_rate"] > 0
    assert record["host_s"] == {"prefix_adopt": 1.1, "decode_wait": 7.5}
    reg.emit("obs_serve", record)
    return sink.records


def collect_spec_serve_records() -> list:
    """obs_serve from a REAL speculative-decoding engine: the
    serve_spec_* instruments only exist when the drafter path runs,
    so a tiny spec engine (2 slots, K=2 self-speculation) decodes one
    request end-to-end and its registry builds the record — a renamed
    spec instrument fails here before it drifts from the doc."""
    import jax
    import numpy as np

    from tpunet.config import ModelConfig, ServeConfig
    from tpunet.models import create_model, init_variables
    from tpunet.obs.registry import MemorySink, Registry
    from tpunet.serve import Engine
    from tpunet.serve.engine import build_serve_record

    cfg = ModelConfig(name="lm", vit_hidden=16, vit_depth=1,
                      vit_heads=2, dropout_rate=0.0, dtype="float32",
                      vocab_size=17, max_seq_len=32)
    model = create_model(cfg)
    variables = init_variables(model, jax.random.PRNGKey(0),
                               seq_len=8)
    reg = Registry()
    reg.set_identity(run_id="spec-check", process_index=0, host="h")
    sink = MemorySink()
    reg.add_sink(sink)
    eng = Engine(model, variables, ServeConfig(
        slots=2, queue_max=4, prefill_buckets=(8,), emit_every_s=0.0,
        spec_decode=True, spec_k=2, spec_draft_width_mult=1.0),
        registry=reg).start()
    try:
        eng.submit(np.arange(4, dtype=np.int32),
                   max_new_tokens=6).result(timeout=120)
    finally:
        eng.stop()
    record = build_serve_record(
        reg, queue_depth=0, active_slots=0, slots=2,
        uptime_s=1.0, window_s=1.0, final=True)
    assert record["spec_draft_tokens_total"] > 0
    assert record["spec_verify_steps_total"] > 0
    assert record["spec_acceptance_rate"] == 1.0  # self-speculation
    reg.emit("obs_serve", record)
    return sink.records


def collect_regression_records() -> list:
    """obs_regression via the real path: two synthetic record streams
    summarized by the history store and compared (quantile rows with
    DKW bounds, scalar rows with tolerance, alert/crash carryover)."""
    from tpunet.obs.history import (compare_summaries, emit_regression,
                                    summarize_run)
    from tpunet.obs.registry import MemorySink, Registry

    def stream(run_id, base, thr):
        records = []
        for ep in range(1, 4):
            records.append({
                "kind": "obs_epoch", "run_id": run_id,
                "config_fingerprint": "fp0", "host": "h", "epoch": ep,
                "step": 10 * ep, "steps": 10,
                "step_time_mean_s": base, "step_time_p50_s": base,
                "step_time_sample": [base + 0.0001 * i
                                     for i in range(16)],
                "tokens_per_sec": thr, "mfu": 0.4,
                "live_processes": 1,
            })
        records.append({
            "kind": "obs_serve", "run_id": run_id,
            "config_fingerprint": "fp0", "uptime_s": 9.0,
            "window_s": 3.0, "queue_depth": 0, "active_slots": 1,
            "slots": 4, "requests_total": 8, "ttft_count": 8,
            "ttft_sample": [base + 0.001 * i for i in range(8)],
            "e2e_count": 8,
            "e2e_sample": [base * 10 + 0.01 * i for i in range(8)],
        })
        records.append({"kind": "obs_alert", "run_id": run_id,
                        "reason": "step_stall", "step": 5,
                        "severity": "warn"})
        return records

    a = summarize_run(stream("run-a", 0.010, 100.0))
    b = summarize_run(stream("run-b", 0.030, 60.0))
    comparison = compare_summaries(a, b)
    reg = Registry()
    reg.set_identity(run_id="compare-check", process_index=0, host="h")
    sink = MemorySink()
    reg.add_sink(sink)
    emit_regression(reg, comparison)
    return sink.records


def collect_elastic_records(tmpdir: str) -> list:
    """obs_elastic via both real emission paths: the agent-side
    append (identity from the run dir, one jsonl line) and the
    trainer-side registry emit — plus the checkpointer's
    ckpt_io_retry obs_alert."""
    import os

    from tpunet.ckpt.orbax_io import emit_io_retry_alert
    from tpunet.elastic import events
    from tpunet.obs.registry import MemorySink, Registry
    from tpunet.utils.logging import MetricsLogger

    run_dir = os.path.join(tmpdir, "run")
    os.makedirs(run_dir, exist_ok=True)
    with open(os.path.join(run_dir, "run_id"), "w") as f:
        f.write("elastic-check\n")
    records = []
    records.append(events.append_elastic_record(
        run_dir, events.build_elastic_record(
            "shrink", cause="host_lost", generation=3, old_world=2,
            new_world=1, hosts=["h0"], lost=["h1"], step=40,
            recovery_s=2.345)))
    records.append(events.append_elastic_record(
        run_dir, events.build_elastic_record(
            "quorum_failed", cause="0 hosts announced", generation=4,
            old_world=1)))
    # The agent-side lines really are metrics.jsonl lines.
    assert MetricsLogger.read_records(
        os.path.join(run_dir, "metrics.jsonl"))
    reg = Registry()
    reg.set_identity(run_id="elastic-check", process_index=0, host="h")
    sink = MemorySink()
    reg.add_sink(sink)
    reg.emit("obs_elastic", events.build_elastic_record(
        "recovered", generation=3, new_world=1,
        old_mesh={"data": 2, "seq": 1, "pipe": 1, "model": 1},
        new_mesh={"data": 1, "seq": 1, "pipe": 1, "model": 1},
        epoch=2, step=40))
    reg.emit("obs_elastic", events.build_elastic_record(
        "evict_requested", cause="step_stall", epoch=2, step=37,
        detail={"reason": "step_stall", "step_time_s": 1.2}))
    emit_io_retry_alert(reg, what="save",
                        error="chaos: injected transient save IO "
                              "error", max_retries=3, backoff_s=0.1)
    return records + sink.records


def collect_router_records() -> list:
    """obs_router via the factored builders (no replicas needed — the
    builders ARE the record shapes): one window record with live
    counters/histograms + per-replica rows, plus every event flavor
    the control loop emits."""
    from tpunet.obs.registry import MemorySink, Registry
    from tpunet.router.records import (build_router_event,
                                       build_router_record)

    reg = Registry()
    reg.set_identity(run_id="router-check", process_index=0, host="h")
    sink = MemorySink()
    reg.add_sink(sink)
    for name in ("requests", "rerouted", "rejected", "affinity_hits",
                 "failovers", "evictions", "respawns", "scale_ups",
                 "scale_downs", "probe_failures"):
        reg.counter(f"router_{name}_total").inc(2)
    for i in range(5):
        reg.histogram("router_e2e_s").observe(0.02 * (i + 1))
    replicas = [
        {"name": "r0", "url": "http://127.0.0.1:8000",
         "state": "healthy", "run_id": "router-replica-0", "slots": 8,
         "queue_depth": 1, "active_slots": 2,
         "serve_requests_total": 9, "requests_routed": 5,
         "requests_failed": 0, "fail_streak": 0},
        {"name": "r1", "url": "http://127.0.0.1:8001",
         "state": "dead", "run_id": "router-replica-1", "slots": 8,
         "queue_depth": 0, "active_slots": 0,
         "serve_requests_total": 4, "requests_routed": 4,
         "requests_failed": 1, "fail_streak": 3},
    ]
    record = build_router_record(
        reg, replicas=replicas, uptime_s=30.0, window_s=10.0,
        scale_decision="scale_up", ttft_slo_burn=1.25, final=True)
    reg.emit("obs_router", record)
    reg.emit("obs_router", build_router_event(
        "evict", replica="r1", url="http://127.0.0.1:8001",
        cause="webhook:straggler",
        detail={"kind": "obs_alert", "reason": "straggler"}))
    reg.emit("obs_router", build_router_event(
        "respawn", replica="r1", url="http://127.0.0.1:8002",
        cause="evicted"))
    reg.emit("obs_router", build_router_event(
        "scale_up", cause="policy", old_replicas=2, new_replicas=3))
    reg.emit("obs_router", build_router_event(
        "scale_down", replica="r0", cause="policy", old_replicas=3,
        new_replicas=2))
    reg.emit("obs_router", build_router_event(
        "failover", replica="r0", url="http://127.0.0.1:8000",
        cause="replica_failed_mid_stream",
        detail={"tokens_relayed": 5}))
    return sink.records


def collect_trace_records() -> list:
    """obs_trace via the factored builder (no router/engine needed —
    the builder IS the record shape): one router-role span with the
    failover seam fields and one replica-role span with the full
    phase decomposition, both fed through ``observe_trace`` so the
    ``trace_*`` instruments exercise their real names."""
    from tpunet.obs.registry import MemorySink, Registry
    from tpunet.obs.tracing import build_trace_record, observe_trace

    reg = Registry()
    reg.set_identity(run_id="trace-check", process_index=0, host="h")
    sink = MemorySink()
    reg.add_sink(sink)
    router_rec = build_trace_record(
        trace_id="0123456789abcdef", hop=0, role="router",
        finish_reason="length", tokens=24, failover_count=1,
        tokens_relayed=12, e2e_s=0.9, error="")
    replica_rec = build_trace_record(
        trace_id="0123456789abcdef", hop=2, role="replica",
        finish_reason="length", queue_s=0.01, prefill_s=0.04,
        prefill_bucket=64, first_decode_s=0.002, tokens=12,
        preemptions=1, preempt_wall_s=0.05, resume_offset=12,
        ttft_s=0.06, e2e_s=0.5,
        error="replica failed mid-stream")
    for rec in (router_rec, replica_rec):
        observe_trace(reg, rec)
        reg.emit("obs_trace", rec)
    return sink.records


def collect_slo_records() -> list:
    """obs_slo + the slo_fast_burn / slo_slow_burn obs_alert flavors
    via the real engine (tpunet/obs/slo.py): the default policy is
    loaded, the availability stream is burned hard enough to fire the
    fast-burn page, a probe mismatch carries a trace id into the
    correctness page, and ``evaluate()`` records are emitted exactly
    the way the router control loop emits them."""
    from tpunet.obs.registry import MemorySink, Registry
    from tpunet.obs.slo import SloEngine, load_policy

    clock = _FakeClock()
    reg = Registry()
    reg.set_identity(run_id="slo-check", process_index=0, host="h")
    sink = MemorySink()
    reg.add_sink(sink)
    engine = SloEngine(load_policy(), registry=reg, clock=clock)
    for i in range(40):                     # healthy baseline
        engine.note_request(True)
        engine.note_latency("ttft", 0.01)
        engine.note_latency("e2e", 0.1)
        clock.t += 1.0
    for _ in range(40):                     # sustained burn -> page
        engine.note_request(False)
        clock.t += 1.0
        engine.evaluate()
    engine.note_probe(ok=True, mismatch=True, ttft_s=0.02, e2e_s=0.2,
                      trace_id="0123456789abcdef")   # correctness page
    engine.evaluate()
    for rec in engine.evaluate():           # the control-loop emission
        reg.emit("obs_slo", rec)
    return sink.records


def collect_agg_records() -> list:
    """obs_fleet + every fleet obs_alert reason via a two-stream
    aggregator (one straggling, one leaking, both serving)."""
    from tpunet.obs.agg import Aggregator
    from tpunet.obs.registry import MemorySink

    clock = _FakeClock()
    agg = Aggregator(clock=clock, straggler_factor=1.5,
                     stream_stale_s=5.0,
                     mem_growth_bytes_per_epoch=1.0,
                     rules=("serve_queue_depth > 0",
                            "step_time_p50_s + 1e-9/s"))
    sink = MemorySink()
    agg.registry.add_sink(sink)
    for name, base in (("a", 0.01), ("b", 0.05)):
        for ep in range(1, 5):
            sample = [base + 0.0001 * i for i in range(16)]
            agg.ingest({
                "kind": "obs_epoch", "run_id": name,
                "process_index": 0, "host": name, "epoch": ep,
                "step": 10 * ep, "steps": 16,
                "step_time_mean_s": base, "step_time_p50_s": base,
                "step_time_sample": sample, "tokens_per_sec": 100.0,
                "mfu": 0.4, "live_processes": 1,
                "device_memory": [
                    {"device": 0,
                     "peak_bytes_in_use": 2 ** 20 + ep * 100}],
            })
            for s in range(10 * ep - 2, 10 * ep):
                agg.ingest({"kind": "obs_step", "run_id": name,
                            "process_index": 0, "step": s,
                            "step_time_s": base})
        agg.ingest({
            "kind": "obs_serve", "run_id": f"serve-{name}",
            "process_index": 0, "host": name, "uptime_s": 9.0,
            "window_s": 3.0, "queue_depth": 2, "active_slots": 1,
            "slots": 4, "requests_total": 10, "requests_completed": 8,
            "requests_rejected": 1, "tokens_total": 100,
            "ttft_count": 8, "ttft_p50_s": 0.05,
            "ttft_sample": [0.04 + 0.001 * i for i in range(8)],
            "e2e_count": 8, "e2e_p99_s": 0.9,
            "e2e_sample": [0.8 + 0.01 * i for i in range(8)],
        })
        agg.ingest({"kind": "obs_alert", "run_id": name,
                    "process_index": 0, "reason": "step_stall",
                    "step": 5, "severity": "warn"})
    agg.ingest({"kind": "obs_crash", "run_id": "a",
                "process_index": 0, "cause": "SIGSEGV", "signal": 11,
                "report_path": "/tmp/x.json", "crashed_pid": 1,
                "events": 3, "stack_threads": 2, "native_ops": 5,
                "assembled_t": 1.0})      # crash alert + crashes_total
    agg.ingest({"kind": "obs_elastic", "run_id": "a",
                "process_index": 0, "event": "shrink",
                "severity": "warn", "cause": "host_lost",
                "generation": 2, "old_world": 2, "new_world": 1,
                "time": 1234.5})          # elastic_* rollup fields
    agg.ingest({"kind": "obs_router", "run_id": "router-a",
                "process_index": 0, "uptime_s": 30.0, "window_s": 10.0,
                "replicas": 2, "replicas_healthy": 1,
                "replicas_draining": 0, "replicas_dead": 1,
                "fleet_queue_depth": 3, "fleet_active_slots": 2,
                "fleet_slots": 16, "requests_total": 9,
                "rerouted_total": 1, "rejected_total": 0,
                "affinity_hits_total": 4, "evictions_total": 1,
                "respawns_total": 1, "scale_ups_total": 0,
                "scale_downs_total": 0, "probe_failures_total": 3,
                "scale_decision": "hold",
                "per_replica": []})       # router_* rollup fields
    agg.ingest({"kind": "obs_router", "run_id": "router-a",
                "process_index": 0, "event": "evict", "replica": "r1",
                "severity": "warn", "cause": "probe_failures",
                "time": 1234.6})          # router_last_event
    agg.ingest({"kind": "obs_trace", "run_id": "router-a",
                "process_index": 0, "trace_id": "0123456789abcdef",
                "hop": 0, "role": "router", "finish_reason": "length",
                "tokens": 24, "failover_count": 1,
                "tokens_relayed": 12, "e2e_s": 0.9})
    agg.ingest({"kind": "obs_trace", "run_id": "serve-a",
                "process_index": 0, "trace_id": "0123456789abcdef",
                "hop": 1, "role": "replica", "finish_reason": "length",
                "queue_s": 0.01, "prefill_s": 0.04, "prefill_bucket": 64,
                "first_decode_s": 0.002, "tokens": 12, "ttft_s": 0.06,
                "e2e_s": 0.5})            # trace_* rollup fields
    agg.ingest({"kind": "obs_slo", "run_id": "router-a",
                "process_index": 0, "name": "availability",
                "sli": "availability", "objective": 0.999,
                "compliance_window_s": 3600.0, "events": 120,
                "bad": 3, "error_rate": 0.025,
                "budget_remaining": 0.4, "page_burn_long": 25.0,
                "page_burn_short": 30.0, "page_burn_threshold": 14.4,
                "page_window_long_s": 300.0,
                "page_window_short_s": 36.0, "page_firing": 1,
                "ticket_burn_long": 25.0, "ticket_burn_short": 25.0,
                "ticket_burn_threshold": 3.0,
                "ticket_window_long_s": 3600.0,
                "ticket_window_short_s": 300.0, "pages_total": 1,
                "tickets_total": 1, "probe_requests": 40,
                "probe_failures": 3, "probe_mismatches": 1,
                "last_failed_trace": "0123456789abcdef"
                })                        # fleet_slo_* rollup fields
    agg.emit_rollup()           # straggler + mem_growth + rules + crash
    clock.t += 100.0
    agg.emit_rollup()           # stream_stale for every stream
    return sink.records


# ---------------------------------------------------------------------------


def undocumented(records, kinds, fields, global_fields) -> list:
    bad = set()
    for r in records:
        kind = r.get("kind", PLAIN)
        if kind not in kinds:
            bad.add((kind, "<kind undocumented>"))
            continue
        allowed = fields[kind] | global_fields | {"kind"}
        for f in r:
            if f not in allowed:
                bad.add((kind, f))
    return sorted(bad)


def main() -> int:
    import tempfile

    kinds, fields, global_fields = parse_schema()
    records = []
    with tempfile.TemporaryDirectory() as tmp:
        records += collect_obs_records(tmp)
    with tempfile.TemporaryDirectory() as tmp:
        records += collect_crash_records(tmp)
    records += collect_serve_records()
    records += collect_spec_serve_records()
    records += collect_router_records()
    records += collect_trace_records()
    records += collect_slo_records()
    records += collect_agg_records()
    records += collect_regression_records()
    with tempfile.TemporaryDirectory() as tmp:
        records += collect_elastic_records(tmp)
    emitted_kinds = sorted({r.get("kind", PLAIN) for r in records})
    bad = undocumented(records, kinds, fields, global_fields)
    if bad:
        print("schema drift: emitted but not documented in "
              "docs/metrics_schema.md:", file=sys.stderr)
        for kind, field in bad:
            print(f"  kind={kind!r:<14} field={field!r}",
                  file=sys.stderr)
        return 1
    print(f"schema OK: {len(records)} records across kinds "
          f"{emitted_kinds} all documented")
    return 0


if __name__ == "__main__":
    sys.exit(main())
