#!/usr/bin/env python
"""Closed-loop load generator for the serving engine.

Drives the in-process ``tpunet.serve.Engine`` (no HTTP overhead in the
measurement; ``--http`` targets a running server instead) with N
concurrent closed-loop clients — each client keeps exactly one request
in flight, so offered load is the concurrency level — and reports
total throughput (tok/s), TTFT / end-to-end latency percentiles, and
queue depth per concurrency level, plus the sequential
one-request-at-a-time baseline the continuous-batching speedup is
measured against (the ISSUE acceptance bar: >= 2x at concurrency 4).

    python scripts/bench_serve.py                 # synthetic weights
    python scripts/bench_serve.py --checkpoint-dir ckpt --vit-hidden 192
    python scripts/bench_serve.py --http http://HOST:PORT --prompt-len 64
    python scripts/bench_serve.py --enforce-budget  # + absolute floor gate

``--enforce-budget`` checks ``tokens_per_s_per_slot`` (peak engine
tok/s over the offered-load sweep, divided by the KV slot count)
against the checked-in floor in docs/serve_budget.json — the
bytes-budget mechanism pointed at serving capacity (exit 3 on a
drop past tolerance; scripts/check_serve_budget.py is the standalone
form). The >=2x-vs-sequential RELATIVE test lives in tests/test_serve;
the absolute floor catches both paths slowing down together.

``--prefix-frac`` switches to the shared-prompt workload that
measures the prefix KV cache: that fraction of requests share the
same ``--prefix-tokens``-long page-aligned prompt prefix (the system-
prompt traffic shape), and the SAME workload runs cache-on and
cache-off. The record reports ``prefill_tokens_per_request`` for both
(the cache-on number must drop toward the suffix length),
``prefix_hit_rate`` from the engine's own counters, and shared-prefix
TTFT percentiles — ``shared_prefix_ttft_p99_ms`` is the budget-gated
ceiling:

    python scripts/bench_serve.py --prefix-frac 0.75 --prompt-len 64
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _answer(req, timeout: float = 600.0):
    """The finished request's tokens. A request that ended in error
    raises: ``Request.result`` returns the tokens whatever the finish
    reason, so a dead engine would otherwise read as an (empty) answer
    and a fast one."""
    tokens = req.result(timeout=timeout)
    if req.finish_reason == "error" or req.error:
        raise RuntimeError(f"request {req.id} finished "
                           f"{req.finish_reason!r}: {req.error}")
    return tokens


def pct(xs, q):
    if not xs:
        return None
    from tpunet.obs.registry import percentile_of_sorted
    return percentile_of_sorted(sorted(xs), q)


def ms(xs, q):
    """Percentile in milliseconds, or None on no samples — an
    all-errors run must still report its 'errors' list instead of
    crashing on round(None)."""
    p = pct(xs, q)
    return None if p is None else round(1e3 * p, 2)


def _p99_exemplar(samples):
    """trace_id of the request at the p99 e2e rank — the slow-request
    lookup key for the joined timeline (scripts/obs_timeline.py)."""
    if not samples:
        return None
    ordered = sorted(samples)
    return ordered[min(len(ordered) - 1, int(0.99 * len(ordered)))][1]


def run_level(engine, concurrency, *, prompt_len, new_tokens,
              requests_per_client, vocab, seed=0):
    """Closed loop: each of ``concurrency`` clients fires
    ``requests_per_client`` requests back-to-back."""
    from tpunet.obs import tracing
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, size=prompt_len).astype(np.int32)
               for _ in range(concurrency)]
    ttfts, e2es, depths = [], [], []
    queues, prefills = [], []
    exemplars = []  # (e2e_s, trace_id) — p99 slow-request lookup key
    errors = []
    done_tokens = [0] * concurrency

    def client(i):
        try:
            for _ in range(requests_per_client):
                tid = tracing.mint_trace_id()
                req = engine.submit(prompts[i],
                                    max_new_tokens=new_tokens,
                                    trace_id=tid)
                _answer(req)
                ttfts.append(req.ttft_s)
                e2es.append(req.e2e_s)
                if req.queue_s is not None:
                    queues.append(req.queue_s)
                if req.prefill_s is not None:
                    prefills.append(req.prefill_s)
                if req.e2e_s is not None:
                    exemplars.append((req.e2e_s, tid))
                done_tokens[i] += len(req.tokens)
                depths.append(engine.queue.depth())
        except Exception as e:  # noqa: BLE001 — report, don't hang
            errors.append(f"client {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    total_tokens = sum(done_tokens)
    return {
        "concurrency": concurrency,
        "requests": concurrency * requests_per_client,
        "errors": errors,
        "total_tokens": total_tokens,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_tokens / wall, 1),
        "ttft_p50_ms": ms(ttfts, 50),
        "ttft_p90_ms": ms(ttfts, 90),
        "ttft_p99_ms": ms(ttfts, 99),
        # TTFT decomposition from the scheduler's phase stamps:
        # queue-wait (submit -> prefill launch) vs prefill compute.
        "ttft_queue_p50_ms": ms(queues, 50),
        "ttft_queue_p99_ms": ms(queues, 99),
        "ttft_prefill_p50_ms": ms(prefills, 50),
        "ttft_prefill_p99_ms": ms(prefills, 99),
        "e2e_p50_ms": ms(e2es, 50),
        "e2e_p99_ms": ms(e2es, 99),
        "p99_exemplar_trace_id": _p99_exemplar(exemplars),
        "queue_depth_mean": round(float(np.mean(depths)), 2)
        if depths else 0.0,
        "queue_depth_max": int(max(depths)) if depths else 0,
    }


def run_http_level(base, concurrency, *, prompt_len, new_tokens,
                   requests_per_client, vocab, seed=0):
    """Same closed loop against a live server's /v1/generate."""
    import urllib.request
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, size=prompt_len).tolist()
               for _ in range(concurrency)]
    ttfts, e2es = [], []
    tokens = [0] * concurrency
    errors = []

    def client(i):
        for _ in range(requests_per_client):
            body = json.dumps({"tokens": prompts[i],
                               "max_new_tokens": new_tokens}).encode()
            req = urllib.request.Request(
                base + "/v1/generate", body,
                {"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=600) as r:
                    out = json.loads(r.read())
                tokens[i] += len(out["tokens"])
                ttfts.append(out["ttft_ms"] / 1e3)
                e2es.append(out["e2e_ms"] / 1e3)
            except Exception as e:  # noqa: BLE001
                errors.append(f"client {i}: {type(e).__name__}: {e}")
                return

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    total = sum(tokens)
    return {
        "concurrency": concurrency,
        "requests": concurrency * requests_per_client,
        "errors": errors,
        "total_tokens": total,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total / wall, 1),
        "ttft_p50_ms": ms(ttfts, 50),
        "ttft_p99_ms": ms(ttfts, 99),
        "e2e_p50_ms": ms(e2es, 50),
        "e2e_p99_ms": ms(e2es, 99),
    }


def run_cold_start_child(args) -> None:
    """Hidden mode (--_cold-start-child): build the engine in THIS
    fresh process and print cold_start_to_first_token_s — the wall
    time from engine construction (weights already initialized; that
    cost is variant-independent) to the first generated token. The
    parent controls what is warm: JAX_COMPILATION_CACHE_DIR in the
    environment, the AOT store via --_aot-dir."""
    import jax

    from tpunet.config import ModelConfig, ServeConfig
    from tpunet.models import create_model, init_variables
    from tpunet.serve.engine import Engine, build_aot_store
    from tpunet.utils.cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    model_cfg = ModelConfig(
        name="lm", vit_hidden=args.vit_hidden, vit_depth=args.vit_depth,
        vit_heads=args.vit_heads, vocab_size=args.vocab_size,
        max_seq_len=args.max_seq_len, dropout_rate=0.0, dtype="float32")
    bucket = 1 << max(4, (args.prompt_len - 1).bit_length())
    cfg = ServeConfig(slots=args.slots, queue_max=64,
                      prefill_buckets=(min(bucket, args.max_seq_len),),
                      emit_every_s=0.0)
    model = create_model(model_cfg)
    variables = init_variables(model, jax.random.PRNGKey(0), seq_len=16)
    store = None
    if args._aot_dir:
        store = build_aot_store(args._aot_dir, model_cfg, cfg)
    t0 = time.perf_counter()
    engine = Engine(model, variables, cfg, aot_store=store).start()
    try:
        req = engine.submit(np.zeros(args.prompt_len, np.int32),
                            max_new_tokens=1)
        _answer(req)
        cold_start = req.first_token_t - t0
    finally:
        engine.stop()
    print(json.dumps({
        "cold_start_to_first_token_s": round(cold_start, 3),
        "aot_status": engine.aot_status,
        "device": jax.devices()[0].device_kind}))


def _cold_start_variant(argv_base, *, cache_dir, aot_dir=""):
    """One fresh-process boot measurement."""
    import subprocess
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir)
    argv = argv_base + ["--_cold-start-child"]
    if aot_dir:
        argv += ["--_aot-dir", aot_dir]
    out = subprocess.run(argv, env=env, capture_output=True, text=True,
                         timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"cold-start child failed (rc "
                           f"{out.returncode}):\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_cold_start_bench(args) -> dict:
    """Measure cold_start_to_first_token_s for the three boot modes
    (fresh process each — an in-process A/B would hit jax's live jit
    caches):

    - ``cold``       — empty persistent compilation cache, no AOT;
    - ``persistent`` — the compilation cache the cold boot populated;
    - ``aot``        — deserialized AOT executables against an EMPTY
      compilation cache, so only the AOT store contributes.

    The acceptance bar (and the serve-budget gate): aot < cold, and
    aot under the checked-in ceiling."""
    import tempfile

    base = [sys.executable, os.path.abspath(__file__),
            "--vit-hidden", str(args.vit_hidden),
            "--vit-depth", str(args.vit_depth),
            "--vit-heads", str(args.vit_heads),
            "--vocab-size", str(args.vocab_size),
            "--max-seq-len", str(args.max_seq_len),
            "--slots", str(args.slots),
            "--prompt-len", str(args.prompt_len)]
    with tempfile.TemporaryDirectory() as tmp:
        cache1 = os.path.join(tmp, "cache1")
        cache2 = os.path.join(tmp, "cache2")
        aot = os.path.join(tmp, "aot")
        os.makedirs(cache1)
        os.makedirs(cache2)
        cold = _cold_start_variant(base, cache_dir=cache1)
        persistent = _cold_start_variant(base, cache_dir=cache1)
        # Prepare the AOT store (timing discarded), then boot from it
        # with a cache dir that has never seen these programs.
        _cold_start_variant(base, cache_dir=cache1, aot_dir=aot)
        aot_boot = _cold_start_variant(base, cache_dir=cache2,
                                       aot_dir=aot)
    assert all(v == "loaded" for v in aot_boot["aot_status"].values()), \
        f"AOT boot did not deserialize: {aot_boot['aot_status']}"
    record = {
        "mode": "cold_start",
        "device": cold["device"],
        "slots": args.slots,
        "prompt_len": args.prompt_len,
        "cold_start_to_first_token_s": {
            "cold": cold["cold_start_to_first_token_s"],
            "persistent": persistent["cold_start_to_first_token_s"],
            "aot": aot_boot["cold_start_to_first_token_s"],
        },
    }
    if record["cold_start_to_first_token_s"]["aot"] > 0:
        record["aot_speedup_vs_cold"] = round(
            record["cold_start_to_first_token_s"]["cold"]
            / record["cold_start_to_first_token_s"]["aot"], 2)
    return record


def _lever_overrides(args) -> dict:
    """ServeConfig overrides from the paged-KV lever flags."""
    return {"kv_pages": args.kv_pages,
            "kv_page_tokens": args.kv_page_tokens,
            "kv_dtype": args.kv_dtype}


def run_slots_sweep(args, model, variables) -> dict:
    """Fixed-KV-pool-bytes capacity sweep (the paging acceptance
    measurement): take as the budget what ``--slots`` slots pin when
    each holds ``max_seq_len`` tokens at the compute dtype (the
    auto-sized pool, ``kv_pages=0``, less its garbage page: the
    record's ``dense_*`` fields), size a paged (+ optionally int8)
    pool to AT MOST those bytes, then drive ascending offered
    concurrency through it and report tokens/s + the admitted-slot
    high-water mark per level. ``slot_capacity`` is the analytic
    concurrent-request capacity at the sweep workload's length
    (prompt + new tokens); the engine's slot count is capped at
    4x the dense baseline so the jitted batch stays benchable."""
    from tpunet.config import ServeConfig
    from tpunet.serve import Engine

    bucket = 1 << max(4, (args.prompt_len - 1).bit_length())
    bucket = min(bucket, args.max_seq_len)
    pt = args.kv_page_tokens
    full = Engine(model, variables, ServeConfig(
        slots=args.slots, queue_max=1, prefill_buckets=(bucket,),
        emit_every_s=0.0, kv_pages=0, kv_page_tokens=pt))
    pool_budget = (full.kv_pool_bytes() * full.kv_pages_usable
                   / (full.kv_pages_usable + 1))
    dense_bytes_per_slot = pool_budget / args.slots
    del full

    # Probe the per-page byte cost at --kv-dtype (pool bytes are linear
    # in pages+garbage), then size the pool to that budget.
    kv_dtype = args.kv_dtype
    probe = Engine(model, variables, ServeConfig(
        slots=1, queue_max=1, prefill_buckets=(bucket,),
        emit_every_s=0.0, kv_pages=1, kv_page_tokens=pt,
        kv_dtype=kv_dtype))
    bytes_per_page = probe.kv_pool_bytes() / 2     # 1 usable + garbage
    del probe
    usable = max(1, int(pool_budget // bytes_per_page) - 1)
    req_tokens = args.prompt_len + args.new_tokens
    pages_per_req = -(-req_tokens // pt)
    slot_capacity = max(1, usable // pages_per_req)
    sweep_slots = min(slot_capacity, 4 * args.slots)
    cfg = ServeConfig(slots=sweep_slots, queue_max=4096,
                      prefill_buckets=(bucket,), emit_every_s=0.0,
                      kv_pages=usable, kv_page_tokens=pt,
                      kv_dtype=kv_dtype)
    engine = Engine(model, variables, cfg).start()
    levels = sorted({max(1, sweep_slots // 4), sweep_slots // 2,
                     sweep_slots} - {0})
    rows = []
    try:
        _answer(engine.submit(np.zeros(args.prompt_len, np.int32),
                      max_new_tokens=2))
        for c in levels:
            engine.peak_active_slots = 0
            r = run_level(engine, c, prompt_len=args.prompt_len,
                          new_tokens=args.new_tokens,
                          requests_per_client=args.requests_per_client,
                          vocab=args.vocab_size)
            r["admitted_slots_peak"] = engine.peak_active_slots
            rows.append(r)
        paged_pool = engine.kv_pool_bytes()
        bytes_per_token = engine.kv_bytes_per_token()
    finally:
        engine.stop()
    import jax
    peak = max((r["admitted_slots_peak"] for r in rows), default=0)
    return {
        "mode": "slots_sweep",
        "device": jax.devices()[0].device_kind,
        "kv_dtype": kv_dtype,
        "kv_page_tokens": pt,
        "prompt_len": args.prompt_len,
        "new_tokens": args.new_tokens,
        "fixed_pool_bytes": int(pool_budget),
        "dense_slots": args.slots,
        "dense_kv_hbm_bytes_per_slot": round(dense_bytes_per_slot, 1),
        "paged_pool_bytes": int(paged_pool),
        "paged_kv_pages": usable,
        "kv_bytes_per_token": round(bytes_per_token, 2),
        "slot_capacity": slot_capacity,
        "slot_capacity_vs_dense": round(slot_capacity / args.slots, 2),
        "admitted_slots_peak": peak,
        "admitted_vs_dense": round(peak / args.slots, 2),
        "levels": rows,
    }


def _prefix_workload(concurrency, *, prompt_len, shared_len,
                     prefix_frac, requests_per_client, vocab, seed=0):
    """Per-client request plans for the shared-prompt workload —
    built ONCE so the cache-on and cache-off engines serve the exact
    same token streams. Each plan entry is (is_shared, prompt):
    shared requests start with the common ``shared_len`` prefix and
    differ only in the suffix."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, size=shared_len).astype(np.int32)
    plans = []
    for i in range(concurrency):
        crng = np.random.default_rng(seed + 1000 + i)
        plan = []
        for _ in range(requests_per_client):
            if crng.random() < prefix_frac:
                sfx = crng.integers(
                    0, vocab,
                    size=prompt_len - shared_len).astype(np.int32)
                plan.append((True, np.concatenate([shared, sfx])))
            else:
                plan.append((False, crng.integers(
                    0, vocab, size=prompt_len).astype(np.int32)))
        plans.append(plan)
    return shared, plans


def _run_prefix_variant(engine, shared, plans, *, new_tokens):
    """Drive one engine through the shared-prompt plans (closed loop,
    one client per plan) and report the prefix-relevant numbers from
    the engine's OWN counters — the bench reads the same instruments
    operators dashboard, not a shadow accounting."""
    # Warm: compile programs and (when the cache is on) adopt the
    # shared prefix, so the measurement sees steady-state hits rather
    # than the one-time cold miss.
    warm = np.concatenate([shared, np.zeros(1, np.int32)])
    _answer(engine.submit(warm, max_new_tokens=2))
    base = engine.registry.snapshot()
    ttfts, shared_ttfts, e2es = [], [], []
    errors = []
    done_tokens = [0] * len(plans)

    def client(i):
        try:
            for is_shared, p in plans[i]:
                req = engine.submit(p, max_new_tokens=new_tokens)
                _answer(req)
                ttfts.append(req.ttft_s)
                if is_shared:
                    shared_ttfts.append(req.ttft_s)
                e2es.append(req.e2e_s)
                done_tokens[i] += len(req.tokens)
        except Exception as e:  # noqa: BLE001 — report, don't hang
            errors.append(f"client {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(plans))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    snap = engine.registry.snapshot()
    n_requests = sum(len(p) for p in plans)
    prefill = (snap.get("serve_prefill_tokens_total", 0)
               - base.get("serve_prefill_tokens_total", 0))
    lookups = (snap.get("serve_prefix_lookups_total", 0)
               - base.get("serve_prefix_lookups_total", 0))
    hits = (snap.get("serve_prefix_hits_total", 0)
            - base.get("serve_prefix_hits_total", 0))
    hit_tokens = (snap.get("serve_prefix_hit_tokens_total", 0)
                  - base.get("serve_prefix_hit_tokens_total", 0))
    total_tokens = sum(done_tokens)
    return {
        "requests": n_requests,
        "errors": errors,
        "total_tokens": total_tokens,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_tokens / wall, 1) if wall else 0.0,
        "prefill_tokens_per_request": round(prefill / n_requests, 2)
        if n_requests else None,
        "prefix_hit_rate": round(hits / lookups, 4) if lookups else 0.0,
        "prefix_hit_tokens": int(hit_tokens),
        "ttft_p50_ms": ms(ttfts, 50),
        "ttft_p99_ms": ms(ttfts, 99),
        "shared_ttft_p50_ms": ms(shared_ttfts, 50),
        "shared_ttft_p99_ms": ms(shared_ttfts, 99),
        "e2e_p99_ms": ms(e2es, 99),
    }


def run_prefix_bench(args, model, variables, concurrency) -> dict:
    """Shared-prompt A/B: the same workload (``--prefix-frac`` of
    requests share a ``--prefix-tokens`` page-aligned prefix) through
    a cache-on and a cache-off engine. The acceptance claim is in the
    delta: cache-on ``prefill_tokens_per_request`` collapses toward
    the suffix length while greedy output is identical math (the
    parity tests own that half); ``shared_prefix_ttft_p99_ms`` is the
    budget-gated latency ceiling."""
    from tpunet.config import ServeConfig
    from tpunet.serve import Engine

    pt = args.kv_page_tokens
    shared_len = args.prefix_tokens
    if shared_len <= 0:
        shared_len = (3 * args.prompt_len // 4) // pt * pt
    if not 0 < shared_len < args.prompt_len:
        print(f"--prompt-len {args.prompt_len} leaves no room for a "
              f"page-aligned shared prefix at --kv-page-tokens {pt}; "
              "raise --prompt-len or set --prefix-tokens explicitly",
              file=sys.stderr)
        sys.exit(2)
    shared, plans = _prefix_workload(
        concurrency, prompt_len=args.prompt_len, shared_len=shared_len,
        prefix_frac=args.prefix_frac,
        requests_per_client=args.requests_per_client,
        vocab=args.vocab_size)
    bucket = 1 << max(4, (args.prompt_len - 1).bit_length())
    bucket = min(bucket, args.max_seq_len)
    variants = {}
    for label, on in (("cache_on", True), ("cache_off", False)):
        cfg = ServeConfig(slots=args.slots,
                          queue_max=max(64, 4 * args.slots),
                          prefill_buckets=(bucket,), emit_every_s=0.0,
                          prefix_cache=on, **_lever_overrides(args))
        engine = Engine(model, variables, cfg).start()
        try:
            variants[label] = _run_prefix_variant(
                engine, shared, plans, new_tokens=args.new_tokens)
        finally:
            engine.stop()
    import jax
    on, off = variants["cache_on"], variants["cache_off"]
    out = {
        "mode": "prefix",
        "device": jax.devices()[0].device_kind,
        "slots": args.slots,
        "prompt_len": args.prompt_len,
        "prefix_tokens": shared_len,
        "prefix_frac": args.prefix_frac,
        "new_tokens": args.new_tokens,
        "kv_page_tokens": pt,
        "concurrency": concurrency,
        "cache_on": on,
        "cache_off": off,
        # headline numbers mirrored at top level for dashboards
        "prefix_hit_rate": on["prefix_hit_rate"],
        "prefill_tokens_per_request": on["prefill_tokens_per_request"],
        "shared_prefix_ttft_p99_ms": on["shared_ttft_p99_ms"],
    }
    if on["prefill_tokens_per_request"] \
            and off["prefill_tokens_per_request"]:
        out["prefill_reduction_vs_cache_off"] = round(
            off["prefill_tokens_per_request"]
            / on["prefill_tokens_per_request"], 2)
    return out


def _spec_workload(concurrency, *, prompt_len, requests_per_client,
                   vocab, seed=0):
    """Per-client prompt plans for the speculative-decoding A/B —
    built ONCE so the spec-on and spec-off engines serve the exact
    same token streams (greedy: bitwise-identical output is pinned by
    tests/test_serve_paged.py; the bench only measures speed)."""
    plans = []
    for i in range(concurrency):
        crng = np.random.default_rng(seed + 2000 + i)
        plans.append([
            crng.integers(0, vocab, size=prompt_len).astype(np.int32)
            for _ in range(requests_per_client)])
    return plans


def _run_spec_variant(engine, plans, *, new_tokens):
    """Drive one engine through the plans (closed loop, one client per
    plan) and report throughput plus the spec counters from the
    engine's OWN registry — ``accepted_tokens_per_verify`` is the
    number the speedup stands on."""
    # The warm request must cover the same position range as the
    # measured run: the burst/verify programs are compiled per
    # attention-window bucket, so a short warm request would leave
    # the deeper buckets to compile inside the measured window — a
    # deployed replica deserializes the full closed set from the AOT
    # store at boot instead.
    warm = np.zeros(max(4, int(plans[0][0].size)), np.int32)
    warm_new = 2
    if getattr(engine, "spec_decode", False):
        warm_new = new_tokens
    _answer(engine.submit(warm, max_new_tokens=warm_new))
    base = engine.registry.snapshot()
    ttfts, e2es, errors = [], [], []
    done_tokens = [0] * len(plans)

    def client(i):
        try:
            for p in plans[i]:
                req = engine.submit(p, max_new_tokens=new_tokens)
                _answer(req)
                ttfts.append(req.ttft_s)
                e2es.append(req.e2e_s)
                done_tokens[i] += len(req.tokens)
        except Exception as e:  # noqa: BLE001 — report, don't hang
            errors.append(f"client {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(plans))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    snap = engine.registry.snapshot()

    def delta(name):
        return snap.get(name, 0) - base.get(name, 0)

    drafted = delta("serve_spec_draft_tokens_total")
    accepted = delta("serve_spec_accepted_tokens_total")
    verifies = delta("serve_spec_verify_steps_total")
    total_tokens = sum(done_tokens)
    slots = engine.slots
    return {
        "requests": sum(len(p) for p in plans),
        "errors": errors,
        "total_tokens": total_tokens,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_tokens / wall, 1) if wall else 0.0,
        "tokens_per_s_per_slot": round(total_tokens / wall / slots, 1)
        if wall else 0.0,
        "decode_steps": int(delta("serve_decode_steps_total")),
        "draft_tokens": int(drafted),
        "accepted_tokens": int(accepted),
        "verify_steps": int(verifies),
        "spec_acceptance_rate": round(accepted / drafted, 4)
        if drafted else 0.0,
        "accepted_tokens_per_verify": round(accepted / verifies, 2)
        if verifies else 0.0,
        "drafter_pool_bytes": engine.drafter_pool_bytes(),
        "ttft_p50_ms": ms(ttfts, 50),
        "ttft_p99_ms": ms(ttfts, 99),
        "e2e_p99_ms": ms(e2es, 99),
    }


def run_spec_bench(args, model_cfg, model, variables,
                   concurrency) -> dict:
    """Speculative-decoding A/B: the identical workload through a
    spec-off and a spec-on engine at the same pool geometry. The
    drafter is FITTED to the bench workload first
    (tpunet.serve.spec.fit_drafter distills a width-mult drafter onto
    the serving model's own greedy trajectories) — the same flow an
    operator uses against logged traffic, scaled down; an unfitted
    drafter drafts noise and spec-on would honestly lose. The
    acceptance claim is ``spec_on.tokens_per_s > spec_off
    .tokens_per_s`` on the same streams (gated unconditionally by
    check_serve_budget.py), with ``accepted_tokens_per_verify`` and
    the drafter pool's extra bytes reported alongside."""
    import jax

    from tpunet.config import ServeConfig
    from tpunet.serve import Engine
    from tpunet.serve import spec as serve_spec

    plans = _spec_workload(
        concurrency, prompt_len=args.prompt_len,
        requests_per_client=args.requests_per_client,
        vocab=args.vocab_size)
    drafter_cfg = serve_spec.drafter_model_config(
        model_cfg, args.spec_width_mult)
    from tpunet.models import create_model, init_variables
    dmodel = create_model(drafter_cfg)
    dparams = init_variables(dmodel, jax.random.PRNGKey(0),
                             seq_len=16)["params"]
    fit_prompts = np.stack([p for plan in plans for p in plan])
    t_fit = time.perf_counter()
    dparams = serve_spec.fit_drafter(
        model, variables["params"], dmodel, dparams, fit_prompts,
        gen_tokens=args.new_tokens, steps=args.spec_fit_steps,
        log=lambda m: print(f"# {m}", file=sys.stderr, flush=True))
    fit_s = time.perf_counter() - t_fit
    bucket = 1 << max(4, (args.prompt_len - 1).bit_length())
    bucket = min(bucket, args.max_seq_len)
    variants = {}
    for label, on in (("spec_off", False), ("spec_on", True)):
        cfg = ServeConfig(slots=args.slots,
                          queue_max=max(64, 4 * args.slots),
                          prefill_buckets=(bucket,), emit_every_s=0.0,
                          spec_decode=on, spec_k=args.spec_k,
                          spec_draft_width_mult=args.spec_width_mult,
                          **_lever_overrides(args))
        engine = Engine(model, variables, cfg,
                        drafter_params=dparams if on else None).start()
        try:
            variants[label] = _run_spec_variant(
                engine, plans, new_tokens=args.new_tokens)
        finally:
            engine.stop()
    on, off = variants["spec_on"], variants["spec_off"]
    out = {
        "mode": "spec",
        "device": jax.devices()[0].device_kind,
        "slots": args.slots,
        "prompt_len": args.prompt_len,
        "new_tokens": args.new_tokens,
        "spec_k": args.spec_k,
        "spec_width_mult": args.spec_width_mult,
        "spec_fit_steps": args.spec_fit_steps,
        "fit_wall_s": round(fit_s, 1),
        "concurrency": concurrency,
        "spec_on": on,
        "spec_off": off,
        # headline numbers mirrored at top level for dashboards
        "tokens_per_s_per_slot": on["tokens_per_s_per_slot"],
        "spec_acceptance_rate": on["spec_acceptance_rate"],
        "accepted_tokens_per_verify": on["accepted_tokens_per_verify"],
        "drafter_pool_bytes": on["drafter_pool_bytes"],
    }
    if off["tokens_per_s"]:
        out["spec_speedup"] = round(
            on["tokens_per_s"] / off["tokens_per_s"], 3)
    return out


def _get_json(url, timeout=10):
    import urllib.request
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def run_router_bench(args) -> dict:
    """Closed-loop load through a spawned router + replica fleet with
    one replica killed mid-run: fleet tok/s, re-route latency (kill
    -> next completed request), dropped-request count (client-visible
    failures — MUST be 0 for --kill-mode drain; bounded by the
    route-retry budget for sigkill), and respawn recovery."""
    import signal as _signal
    import socket
    import subprocess

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    import tempfile
    workdir = tempfile.mkdtemp(prefix="router-bench-")
    argv = [sys.executable, "-m", "tpunet.router",
            "--spawn", str(args.replicas), "--port", str(port),
            "--probe-interval-s", "0.25", "--unhealthy-after", "2",
            "--respawn-backoff-s", "0.5", "--emit-every-s", "2",
            "--min-replicas", str(args.replicas),
            "--metrics-dir", workdir,
            "--aot-cache", os.path.join(workdir, "aot"), "--",
            "--checkpoint-dir", "",
            "--vit-hidden", str(args.vit_hidden),
            "--vit-depth", str(args.vit_depth),
            "--vit-heads", str(args.vit_heads),
            "--vocab-size", str(args.vocab_size),
            "--max-seq-len", str(args.max_seq_len),
            "--slots", str(args.slots),
            "--prefill-buckets", str(min(
                1 << max(4, (args.prompt_len - 1).bit_length()),
                args.max_seq_len))]
    router = subprocess.Popen(argv, stdout=subprocess.DEVNULL,
                              stderr=subprocess.STDOUT)
    out = {"mode": "router", "replicas": args.replicas,
           "kill_mode": args.kill_mode, "workdir": workdir,
           "errors": []}
    try:
        deadline = time.time() + 300
        while time.time() < deadline:
            try:
                h = _get_json(base + "/healthz", timeout=2)
                if h.get("routable", 0) >= args.replicas:
                    break
            except Exception:  # noqa: BLE001
                pass
            time.sleep(0.5)
        else:
            out["errors"].append("fleet never became routable")
            return out

        rng = np.random.default_rng(0)
        concurrency = max(4, args.replicas * 2)
        n_requests = concurrency * max(4, args.requests_per_client)
        prompts = [rng.integers(0, args.vocab_size,
                                size=args.prompt_len).tolist()
                   for _ in range(concurrency)]
        results = []           # (t_done, ok, tokens)
        lock = threading.Lock()
        kill_at = n_requests // 2
        killed = {"t": None, "pid": None}

        def kill_one():
            rows = _get_json(base + "/replicas")["replicas"]
            victim = next((r for r in rows
                           if r.get("alive") and r.get("pid")), None)
            if victim is None:
                out["errors"].append("no live replica to kill")
                return
            killed["pid"] = victim["pid"]
            killed["t"] = time.perf_counter()
            sig = (_signal.SIGKILL if args.kill_mode == "sigkill"
                   else _signal.SIGTERM)
            os.kill(victim["pid"], sig)

        import urllib.request
        counter = {"n": 0}

        def client(i):
            while True:
                with lock:
                    if counter["n"] >= n_requests:
                        return
                    counter["n"] += 1
                    seq = counter["n"]
                if seq == kill_at and args.kill_mode != "none":
                    kill_one()
                body = json.dumps(
                    {"tokens": prompts[i],
                     "max_new_tokens": args.new_tokens}).encode()
                req = urllib.request.Request(
                    base + "/v1/generate", body,
                    {"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req, timeout=600) as r:
                        payload = json.loads(r.read())
                    with lock:
                        results.append((time.perf_counter(), True,
                                        len(payload["tokens"])))
                except Exception:  # noqa: BLE001 — a failed request
                    with lock:     # is the measurement, not a crash
                        results.append((time.perf_counter(), False, 0))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(concurrency)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        ok = [r for r in results if r[1]]
        dropped = len(results) - len(ok)
        total_tokens = sum(r[2] for r in ok)
        out.update({
            "requests": len(results),
            "dropped_requests": dropped,
            "total_tokens": total_tokens,
            "wall_s": round(wall, 3),
            "fleet_tokens_per_s": round(total_tokens / wall, 1),
        })
        if killed["t"] is not None:
            after = [t for t, good, _ in results
                     if good and t > killed["t"]]
            if after:
                out["reroute_latency_s"] = round(
                    min(after) - killed["t"], 3)
            # Respawn recovery: every replica routable again.
            deadline = time.time() + 180
            while time.time() < deadline:
                try:
                    h = _get_json(base + "/healthz", timeout=2)
                    if h.get("routable", 0) >= args.replicas:
                        out["respawn_recovery_s"] = round(
                            time.perf_counter() - killed["t"], 3)
                        break
                except Exception:  # noqa: BLE001
                    pass
                time.sleep(0.5)
            else:
                out["errors"].append("killed replica never respawned")
        try:
            snap = _get_json(base + "/metrics")
            for key in ("router_requests_total", "router_rerouted_total",
                        "router_rejected_total",
                        "router_failovers_total",
                        "router_evictions_total",
                        "router_respawns_total"):
                if key in snap:
                    out[key] = int(snap[key])
        except Exception:  # noqa: BLE001
            pass
        if args.kill_mode == "drain" and dropped:
            out["errors"].append(
                f"drain kill dropped {dropped} request(s); drain must "
                "drop zero")
    finally:
        router.send_signal(_signal.SIGTERM)
        try:
            router.wait(timeout=90)
        except subprocess.TimeoutExpired:
            router.kill()
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--http", default="",
                    help="bench a RUNNING server at this base URL "
                         "instead of an in-process engine")
    ap.add_argument("--cold-start", action="store_true",
                    help="measure cold_start_to_first_token_s for "
                         "cold / persistent-cache / AOT-deserialized "
                         "replica boots (fresh subprocess each)")
    ap.add_argument("--_cold-start-child", action="store_true",
                    dest="_cold_start_child", help=argparse.SUPPRESS)
    ap.add_argument("--_aot-dir", default="", dest="_aot_dir",
                    help=argparse.SUPPRESS)
    ap.add_argument("--router", action="store_true",
                    help="closed-loop load against a spawned router + "
                         "replica fleet with a mid-run replica kill "
                         "(fleet tok/s, re-route latency, dropped "
                         "requests)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="--router: replica children to spawn")
    ap.add_argument("--kill-mode", default="sigkill",
                    choices=("sigkill", "drain", "none"),
                    help="--router: how the mid-run replica dies "
                         "(drain = SIGTERM graceful; dropped "
                         "requests must be 0 for drain, bounded for "
                         "sigkill)")
    ap.add_argument("--slots-sweep", action="store_true",
                    help="fixed-KV-pool-bytes capacity sweep: size a "
                         "pool to the bytes --slots slots pin at full "
                         "length, then "
                         "report tokens/s and admitted-slot count vs "
                         "offered concurrency — the concurrent-slot "
                         "multiplier paging buys at constant HBM")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="usable KV pages (0 = every slot at full "
                         "length)")
    ap.add_argument("--kv-page-tokens", type=int, default=16,
                    help="tokens per KV page")
    ap.add_argument("--kv-dtype", default="auto",
                    choices=("auto", "bf16", "int8"),
                    help="KV page payload dtype (int8 = quantized "
                         "pages, per-row scale)")
    ap.add_argument("--prefix-frac", type=float, default=0.0,
                    help="shared-prompt workload: this fraction of "
                         "requests share one prompt prefix; > 0 "
                         "switches to the prefix-cache A/B bench "
                         "(cache-on vs cache-off over the SAME "
                         "workload)")
    ap.add_argument("--prefix-tokens", type=int, default=0,
                    help="length of the shared prompt prefix (0 = "
                         "largest page multiple <= 3/4 of "
                         "--prompt-len)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative-decoding A/B: fit a drafter to "
                         "the bench workload, then run the identical "
                         "workload spec-on vs spec-off "
                         "(check_serve_budget.py gates spec-on "
                         "tokens/s above spec-off unconditionally)")
    ap.add_argument("--spec-k", type=int, default=8,
                    help="--spec: draft tokens per verify cycle "
                         "(default 8: with --new-tokens 64 the budget "
                         "divides as 1 + 7x9 so no request drops to "
                         "the width-1 tail)")
    ap.add_argument("--spec-width-mult", type=float, default=0.25,
                    help="--spec: drafter width fraction (0.25: the "
                         "drafter burst is K+1 SEQUENTIAL small "
                         "steps, the one part of the cycle the wide "
                         "verify cannot amortize — narrow pays)")
    ap.add_argument("--spec-fit-steps", type=int, default=300,
                    help="--spec: drafter distillation steps (fewer = "
                         "faster bench, lower acceptance)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="LM best checkpoint (default: random tiny "
                         "weights — throughput shape, not quality)")
    ap.add_argument("--vit-hidden", type=int, default=64)
    ap.add_argument("--vit-depth", type=int, default=2)
    ap.add_argument("--vit-heads", type=int, default=4)
    ap.add_argument("--vocab-size", type=int, default=256)
    ap.add_argument("--max-seq-len", type=int, default=512)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--requests-per-client", type=int, default=2)
    ap.add_argument("--concurrency", default="1,2,4,8",
                    help="comma-separated offered-load levels")
    ap.add_argument("--out", default="",
                    help="also write the result JSON here")
    ap.add_argument("--enforce-budget", action="store_true",
                    help="exit 3 when tokens_per_s_per_slot falls below "
                         "the docs/serve_budget.json floor for this "
                         "device kind")
    args = ap.parse_args()
    levels = [int(c) for c in args.concurrency.split(",") if c]

    if args._cold_start_child:
        run_cold_start_child(args)
        return

    if args.cold_start:
        out = run_cold_start_bench(args)
        print(json.dumps(out, indent=1))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)
        if args.enforce_budget:
            from check_serve_budget import check_record, load_budget
            ok, msgs = check_record(out, load_budget())
            for m in msgs:
                print(f"# {m}", file=sys.stderr, flush=True)
            if not ok:
                sys.exit(3)
        return

    if args.router:
        out = run_router_bench(args)
        print(json.dumps(out, indent=1))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)
        if out.get("errors"):
            sys.exit(1)
        return

    if args.http:
        if args.enforce_budget:
            # The floor is keyed on device kind, which a remote HTTP
            # record does not carry — refuse loudly rather than
            # letting the flag silently no-op.
            print("--enforce-budget is not supported with --http "
                  "(no device kind in the record); run the in-process "
                  "engine bench instead", file=sys.stderr)
            sys.exit(2)
        results = [run_http_level(
            args.http.rstrip("/"), c, prompt_len=args.prompt_len,
            new_tokens=args.new_tokens,
            requests_per_client=args.requests_per_client,
            vocab=args.vocab_size) for c in levels]
        out = {"mode": "http", "target": args.http, "levels": results}
        print(json.dumps(out, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)
        return

    import jax

    from tpunet.config import ModelConfig, ServeConfig
    from tpunet.models import create_model, init_variables, num_params
    from tpunet.models.lm import generate
    from tpunet.serve import Engine

    model_cfg = ModelConfig(
        name="lm", vit_hidden=args.vit_hidden, vit_depth=args.vit_depth,
        vit_heads=args.vit_heads, vocab_size=args.vocab_size,
        max_seq_len=args.max_seq_len, dropout_rate=0.0, dtype="float32")
    if args.checkpoint_dir:
        from tpunet.infer.generate import load_lm
        model, variables = load_lm(model_cfg,
                                   checkpoint_dir=args.checkpoint_dir)
    else:
        model = create_model(model_cfg)
        variables = init_variables(model, jax.random.PRNGKey(0),
                                   seq_len=16)

    if args.prefix_frac > 0:
        out = run_prefix_bench(args, model, variables, max(levels))
        print(json.dumps(out, indent=1))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)
        if args.enforce_budget:
            from check_serve_budget import check_record, load_budget
            ok, msgs = check_record(out, load_budget())
            for m in msgs:
                print(f"# {m}", file=sys.stderr, flush=True)
            if not ok:
                sys.exit(3)
        return

    if args.spec:
        out = run_spec_bench(args, model_cfg, model, variables,
                             max(levels))
        print(json.dumps(out, indent=1))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)
        if args.enforce_budget:
            from check_serve_budget import check_record, load_budget
            ok, msgs = check_record(out, load_budget())
            for m in msgs:
                print(f"# {m}", file=sys.stderr, flush=True)
            if not ok:
                sys.exit(3)
        return

    if args.slots_sweep:
        out = run_slots_sweep(args, model, variables)
        print(json.dumps(out, indent=1))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)
        return

    # Sequential baseline: the pre-serve shape — one request at a time
    # through models.lm.generate (warmed compile).
    p = np.zeros((1, args.prompt_len), np.int32)
    generate(model, variables, p, n_new=2)
    t0 = time.perf_counter()
    n_seq = max(2, args.requests_per_client)
    for _ in range(n_seq):
        generate(model, variables, p, n_new=args.new_tokens)
    seq_wall = time.perf_counter() - t0
    seq_tps = n_seq * args.new_tokens / seq_wall

    bucket = 1 << max(4, (args.prompt_len - 1).bit_length())
    cfg = ServeConfig(slots=args.slots, queue_max=max(64, 4 * args.slots),
                      prefill_buckets=(min(bucket, args.max_seq_len),),
                      emit_every_s=0.0, **_lever_overrides(args))
    engine = Engine(model, variables, cfg).start()
    try:
        # warm prefill + decode programs outside the measurement
        _answer(engine.submit(np.zeros(args.prompt_len, np.int32),
                      max_new_tokens=2))
        results = [run_level(
            engine, c, prompt_len=args.prompt_len,
            new_tokens=args.new_tokens,
            requests_per_client=args.requests_per_client,
            vocab=args.vocab_size) for c in levels]
    finally:
        engine.stop()
    out = {
        "mode": "engine",
        "device": jax.devices()[0].device_kind,
        "model_params": num_params(variables["params"]),
        "slots": args.slots,
        "prompt_len": args.prompt_len,
        "new_tokens": args.new_tokens,
        "kv_dtype": cfg.kv_dtype,
        # KV capacity telemetry: pool bytes pinned per slot and per
        # cacheable token (the serve_budget.json kv_bytes_per_token
        # ceiling gates the latter against silent pool bloat).
        "kv_hbm_bytes_per_slot": round(
            engine.kv_pool_bytes() / engine.slots, 1),
        "kv_bytes_per_token": round(engine.kv_bytes_per_token(), 2),
        "sequential_tokens_per_s": round(seq_tps, 1),
        "levels": results,
        "speedup_vs_sequential": {
            str(r["concurrency"]): round(r["tokens_per_s"] / seq_tps, 2)
            for r in results},
    }
    from check_serve_budget import tokens_per_s_per_slot
    tpss = tokens_per_s_per_slot(out)
    if tpss is not None:
        out["tokens_per_s_per_slot"] = round(tpss, 1)
    print(json.dumps(out, indent=1))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    if args.enforce_budget:
        from check_serve_budget import check_record, load_budget
        ok, msgs = check_record(out, load_budget())
        for m in msgs:
            print(f"# {m}", file=sys.stderr, flush=True)
        if not ok:
            sys.exit(3)


if __name__ == "__main__":
    main()
