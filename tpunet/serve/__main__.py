"""CLI entry point: ``python -m tpunet.serve --checkpoint-dir ...``.

Loads the LM family best checkpoint through the same
``infer.generate.load_lm`` path the generation CLI uses (pipeline
checkpoints unstack, tensor-parallel serving via ``--mesh-model``),
optionally a classifier checkpoint for the micro-batched
``/v1/classify`` path, wires the obs registry into ``metrics.jsonl``
and any configured live exporters, and serves until SIGTERM/SIGINT —
which triggers a graceful drain (stop admitting, finish in-flight,
flush telemetry) rather than dropping connections.
"""

from __future__ import annotations

import os
import signal
import sys


def parse_prefill_buckets(spec, max_seq_len: int):
    """Validate ``--prefill-buckets``: comma-separated positive ints,
    none beyond ``--max-seq-len``. A bad entry is a LOUD exit-2 usage
    error — silently filtering a typo'd bucket used to change the
    server's compile set (and reject prompts) without a word."""
    entries = [e.strip() for e in str(spec).split(",") if e.strip()]
    if not entries:
        raise _usage(f"--prefill-buckets {spec!r} names no buckets; "
                     "give at least one padded prompt length, e.g. "
                     "--prefill-buckets 64,256,1024")
    buckets = []
    for raw in entries:
        try:
            bucket = int(raw)
        except ValueError:
            raise _usage(
                f"--prefill-buckets entry {raw!r} is not an integer "
                f"(got {spec!r}; expected comma-separated prompt-"
                "length buckets like 64,256,1024)")
        if bucket < 1:
            raise _usage(f"--prefill-buckets entry {bucket} must be "
                         ">= 1")
        if bucket > max_seq_len:
            raise _usage(
                f"--prefill-buckets entry {bucket} exceeds "
                f"--max-seq-len {max_seq_len}: the KV pool cannot "
                "hold a prompt that long — raise --max-seq-len or "
                "drop the bucket")
        buckets.append(bucket)
    return tuple(buckets)


def _usage(msg: str) -> SystemExit:
    print(f"python -m tpunet.serve: error: {msg}", file=sys.stderr,
          flush=True)
    return SystemExit(2)


def build_argparser():
    import argparse

    from tpunet.config import ServeConfig

    d = ServeConfig()
    p = argparse.ArgumentParser(
        prog="python -m tpunet.serve",
        description="tpunet production inference server")
    p.add_argument("--checkpoint-dir", default="checkpoints",
                   help="LM best-checkpoint directory (infer.generate "
                        "load_lm path)")
    p.add_argument("--host", default=d.host)
    p.add_argument("--port", type=int, default=d.port)
    p.add_argument("--slots", type=int, default=d.slots,
                   help="KV-slot pool size = max in-flight decodes")
    p.add_argument("--queue-max", type=int, default=d.queue_max,
                   help="bounded admission queue; beyond it requests "
                        "are rejected 429 (backpressure)")
    p.add_argument("--prefill-buckets", default=",".join(
        str(b) for b in d.prefill_buckets),
        help="comma-separated padded prompt-length buckets (compile "
             "count = number of buckets)")
    p.add_argument("--kv-pages", type=int, default=d.kv_pages,
                   help="usable KV pages in the shared pool (0 = "
                        "every slot at full length: slots x "
                        "ceil(max-seq-len / kv-page-tokens)); size it "
                        "down to oversubscribe slots against typical "
                        "request lengths")
    p.add_argument("--kv-page-tokens", type=int,
                   default=d.kv_page_tokens,
                   help="tokens per KV page (allocation granule)")
    p.add_argument("--kv-dtype", default=d.kv_dtype,
                   choices=["auto", "bf16", "int8"],
                   help="KV page payload dtype: auto = compute dtype; "
                        "bf16 halves float32 payloads; int8 "
                        "quantizes per written token row (float32 "
                        "scale stored with the page, eval-parity-"
                        "gated) — halves page cost again")
    p.add_argument("--prefix-cache", default=d.prefix_cache,
                   action=argparse.BooleanOptionalAction,
                   help="prefix KV cache (default on, paged only): "
                        "finished prefill pages stay in the pool as "
                        "refcounted content-addressed objects; a new "
                        "request pins its longest cached page-aligned "
                        "prefix and prefills only the suffix (COW at "
                        "the divergence page, LRU-evicted under pool "
                        "pressure)")
    p.add_argument("--prefix-cache-pages", type=int,
                   default=d.prefix_cache_pages,
                   help="pool pages the prefix cache may hold (0 = "
                        "half the usable pool) — bounded below the "
                        "pool so cached pages never starve paying "
                        "slots")
    p.add_argument("--prefix-store", default=d.prefix_store,
                   metavar="DIR",
                   help="shared-filesystem prefix spill/warm-start: "
                        "cached pages publish under DIR (first-writer-"
                        "wins, like --aot-cache) and a respawned "
                        "replica adopts the fleet's prefix set at "
                        "boot; entries scoped by model config + kv "
                        "levers so a lever change is a clean miss")
    p.add_argument("--spec-decode", default=d.spec_decode,
                   action=argparse.BooleanOptionalAction,
                   help="speculative decoding (default off, needs "
                        "paged KV + device sampling): a narrow "
                        "drafter proposes --spec-k tokens per slot "
                        "against its own paged pool, ONE wide verify "
                        "over the main pool scores them, rejection "
                        "rewinds the page-table cursor — output is "
                        "bitwise-identical to spec-off at any "
                        "acceptance rate (docs/serving.md)")
    p.add_argument("--spec-k", type=int, default=d.spec_k,
                   help="draft tokens per verify cycle (a slot emits "
                        "1..K+1 verified tokens per cycle)")
    p.add_argument("--spec-draft-width-mult", type=float,
                   default=d.spec_draft_width_mult,
                   help="drafter width as a fraction of the serving "
                        "model's hidden dim (floored to a multiple "
                        "of the head count; 1.0 = self-speculation "
                        "for parity testing)")
    p.add_argument("--spec-draft-checkpoint", default=d.
                   spec_draft_checkpoint, metavar="NPZ",
                   help="fitted drafter weights (tpunet.serve.spec."
                        "save_drafter_params npz); empty = "
                        "deterministic random init, which is correct "
                        "but drafts nothing useful — fit one against "
                        "real traffic with tpunet.serve.spec."
                        "fit_drafter")
    p.add_argument("--max-new-tokens", type=int,
                   default=d.default_max_new_tokens,
                   help="default per-request generation budget")
    p.add_argument("--max-new-tokens-cap", type=int,
                   default=d.max_new_tokens_cap,
                   help="hard per-request generation ceiling: larger "
                        "asks are clamped to it at admission")
    p.add_argument("--deadline-s", type=float,
                   default=d.default_deadline_s,
                   help="default per-request wall-clock deadline "
                        "(0 = none)")
    p.add_argument("--classify-batch-max", type=int,
                   default=d.classify_batch_max)
    p.add_argument("--classify-window-ms", type=float,
                   default=d.classify_window_ms)
    p.add_argument("--emit-every-s", type=float, default=d.emit_every_s,
                   help="obs_serve record cadence into metrics.jsonl")
    p.add_argument("--drain-timeout-s", type=float,
                   default=d.drain_timeout_s)
    p.add_argument("--metrics-dir", default="",
                   help="directory for metrics.jsonl (default: the "
                        "checkpoint dir); obs records share the "
                        "docs/metrics_schema.md contract")
    p.add_argument("--statsd", default="", metavar="HOST:PORT",
                   help="stream obs_serve records as statsd/UDP gauges")
    p.add_argument("--obs-http", default="", metavar="URL",
                   help="POST obs_serve records as line-JSON")
    p.add_argument("--obs-webhook", default="", metavar="URL",
                   help="POST one templated JSON payload per alert "
                        "record (obs_alert/obs_crash) — wire format "
                        "in docs/metrics_schema.md")
    p.add_argument("--run-id", default=d.run_id,
                   help="replica identity stamped on obs_serve records "
                        "(fleet rollups route by it; default "
                        "serve-<host>-<pid>)")
    p.add_argument("--chaos", default=d.chaos, metavar="SPEC",
                   help="serve-tier fault injection (tpunet/serve/"
                        "chaos.py): kill@tokens=N, kill@prefill[=K], "
                        "stall@tokens=N:ms=M, drop-probe@prob=P:"
                        "seed=X, slow-stream@ms=M — deterministic, "
                        "';'-separated; docs/serving.md grammar")
    p.add_argument("--trace-sample", type=float,
                   default=d.trace_sample, metavar="RATE",
                   help="standalone request-tracing head-sample rate "
                        "in [0,1] (tpunet/obs/tracing.py): applies to "
                        "requests WITHOUT router trace headers; a "
                        "client-supplied X-Trace-Id is always sampled"
                        " (default 0 = header-carried traces only)")
    p.add_argument("--aot-cache", default=d.aot_cache, metavar="DIR",
                   help="AOT warm-start: serialize the compiled decode"
                        " + prefill executables under DIR on first "
                        "boot and deserialize them on later boots — "
                        "replica cold-start drops from compile-bound "
                        "to seconds (single-device replicas; the "
                        "persistent compilation cache covers the rest)")
    # LM architecture (must match the trained checkpoint) — mirrors
    # tpunet.infer.generate's flags.
    p.add_argument("--model", choices=("lm", "lm_pp"), default="lm")
    p.add_argument("--vit-hidden", type=int, default=192)
    p.add_argument("--vit-depth", type=int, default=6)
    p.add_argument("--vit-heads", type=int, default=3)
    p.add_argument("--vocab-size", type=int, default=256)
    p.add_argument("--max-seq-len", type=int, default=1024)
    p.add_argument("--moe-experts", type=int, default=0)
    p.add_argument("--moe-every", type=int, default=2)
    p.add_argument("--moe-top-k", type=int, default=2)
    p.add_argument("--moe-capacity-factor", type=float, default=1.25)
    p.add_argument("--mesh-model", type=int, default=0,
                   help="tensor-parallel serving: shard block weights "
                        "and the KV pool's head dim over N devices")
    p.add_argument("--train-pipe", type=int, default=0)
    p.add_argument("--pp-virtual", type=int, default=2)
    # Optional classifier endpoint.
    p.add_argument("--classifier-checkpoint-dir", default="",
                   help="also serve /v1/classify from this MobileNetV2/"
                        "ViT best checkpoint (micro-batched)")
    p.add_argument("--classifier-model", default="mobilenet_v2")
    p.add_argument("--classifier-image-size", type=int, default=224)
    return p


def build_server(args):
    """Construct (but do not start) the ServeServer from parsed args —
    shared by main() and tests."""
    # Validate the pure-CLI surface BEFORE the jax-importing block
    # below: a typo'd bucket list should exit 2 in milliseconds, not
    # after a runtime import.
    buckets = parse_prefill_buckets(args.prefill_buckets,
                                    args.max_seq_len)
    if args.chaos:
        # Same posture as the bucket list: a typo'd chaos spec is a
        # loud exit-2 BEFORE the model loads, not a mid-serve raise.
        from tpunet.serve.chaos import ServeChaos, ServeChaosError
        try:
            ServeChaos.parse(args.chaos)
        except ServeChaosError as e:
            raise _usage(str(e))

    import dataclasses

    from tpunet.config import (CheckpointConfig, DataConfig, MeshConfig,
                               ModelConfig, ServeConfig)
    from tpunet.infer.generate import load_lm
    from tpunet.obs.registry import JsonlSink
    from tpunet.serve.classify import ClassifyBatcher
    from tpunet.serve.engine import Engine
    from tpunet.serve.frontend import ServeServer
    from tpunet.utils.logging import MetricsLogger

    # Shared persistent compilation cache (tpunet/utils/cache.py):
    # even a replica without --aot-cache warm-starts its compiles from
    # the per-user cache dir the training/test entry points already
    # populate (JAX_COMPILATION_CACHE_DIR wins when set).
    from tpunet.utils.cache import enable_persistent_compile_cache
    enable_persistent_compile_cache()

    cfg = ServeConfig(
        host=args.host, port=args.port, slots=args.slots,
        queue_max=args.queue_max, prefill_buckets=buckets,
        kv_pages=args.kv_pages,
        kv_page_tokens=args.kv_page_tokens, kv_dtype=args.kv_dtype,
        prefix_cache=args.prefix_cache,
        prefix_cache_pages=args.prefix_cache_pages,
        prefix_store=args.prefix_store,
        default_max_new_tokens=args.max_new_tokens,
        max_new_tokens_cap=args.max_new_tokens_cap,
        default_deadline_s=args.deadline_s,
        classify_batch_max=args.classify_batch_max,
        classify_window_ms=args.classify_window_ms,
        emit_every_s=args.emit_every_s,
        drain_timeout_s=args.drain_timeout_s,
        run_id=args.run_id, aot_cache=args.aot_cache,
        chaos=args.chaos, trace_sample=args.trace_sample,
        spec_decode=args.spec_decode, spec_k=args.spec_k,
        spec_draft_width_mult=args.spec_draft_width_mult,
        spec_draft_checkpoint=args.spec_draft_checkpoint)
    model_cfg = ModelConfig(
        name=args.model, vit_hidden=args.vit_hidden,
        vit_depth=args.vit_depth, vit_heads=args.vit_heads,
        vocab_size=args.vocab_size, max_seq_len=args.max_seq_len,
        dropout_rate=0.0, moe_experts=args.moe_experts,
        moe_every=args.moe_every, moe_top_k=args.moe_top_k,
        moe_capacity_factor=args.moe_capacity_factor,
        pp_virtual=args.pp_virtual)
    mesh = None
    if args.mesh_model > 1:
        from tpunet.parallel import make_mesh
        mesh = make_mesh(MeshConfig(data=1, model=args.mesh_model))
    model, variables = load_lm(model_cfg,
                               checkpoint_dir=args.checkpoint_dir,
                               mesh=mesh, train_pipe=args.train_pipe)
    aot_store = None
    if cfg.aot_cache and mesh is None:
        from tpunet.serve.engine import build_aot_store
        aot_store = build_aot_store(cfg.aot_cache, model_cfg, cfg)
    prefix_store = None
    if cfg.prefix_store and cfg.prefix_cache:
        from tpunet.serve.prefixcache import build_prefix_store
        prefix_store = build_prefix_store(cfg.prefix_store, model_cfg,
                                          cfg)
    engine = Engine(model, variables, cfg, mesh=mesh,
                    aot_store=aot_store, prefix_store=prefix_store)
    # The engine serves from its own resident tree and keeps no
    # reference to this one: drop ours, or a float32 checkpoint stays
    # on the device beside the copy the steps read.
    del variables
    if engine.aot_status:
        print(f"aot warm-start: {engine.aot_status}", flush=True)
    registry = engine.registry

    metrics_logger = None
    exporters = []
    metrics_dir = args.metrics_dir or args.checkpoint_dir
    # Black-box flight recorder for the SERVING process (README
    # "Crash forensics"): event ring + crash handlers + watcher into
    # <metrics-dir>/flightrec, so a dead replica leaves a
    # crash_report.json next to its metrics. Same default-ON as the
    # trainer; the engine/frontend record() calls land here.
    recorder = None
    if metrics_dir:
        from tpunet.obs import flightrec
        recorder = flightrec.install(metrics_dir, run_id=args.run_id)
    if metrics_dir:
        metrics_logger = MetricsLogger(metrics_dir, resume=True)
        registry.add_sink(JsonlSink(metrics_logger))
    if args.statsd or args.obs_http or args.obs_webhook:
        from tpunet.config import ExportConfig
        from tpunet.obs.export import build_exporters
        exporters = build_exporters(
            ExportConfig(statsd=args.statsd, http=args.obs_http,
                         webhook=args.obs_webhook),
            registry)
        for exporter in exporters:
            registry.add_sink(exporter)

    batcher = None
    if args.classifier_checkpoint_dir:
        from tpunet.infer.predict import Predictor
        pred = Predictor(
            model_cfg=ModelConfig(name=args.classifier_model,
                                  dropout_rate=0.0),
            data_cfg=DataConfig(image_size=args.classifier_image_size),
            checkpoint_dir=args.classifier_checkpoint_dir)
        batcher = ClassifyBatcher(pred,
                                  batch_max=cfg.classify_batch_max,
                                  window_ms=cfg.classify_window_ms,
                                  registry=registry)
    return ServeServer(engine, classify_batcher=batcher,
                       host=cfg.host, port=cfg.port,
                       metrics_logger=metrics_logger,
                       exporters=exporters, run_id=cfg.run_id,
                       flight_recorder=recorder)


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    server = build_server(args)
    server.start()
    import jax
    dev = jax.devices()[0]
    print(f"tpunet.serve listening on "
          f"http://{args.host}:{server.port} "
          f"(slots={server.engine.slots}, "
          f"buckets={server.engine.buckets}, "
          f"platform={dev.platform}, device_kind={dev.device_kind}, "
          f"visible_chips="
          f"{os.environ.get('TPU_VISIBLE_CHIPS', 'all')})", flush=True)

    import threading
    stop = threading.Event()

    def _term(signum, frame):
        print(f"signal {signum}: draining "
              f"(timeout {args.drain_timeout_s}s)...", flush=True)
        stop.set()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    while not stop.is_set():
        stop.wait(0.5)
        if not server.engine.healthy:
            print(f"engine unhealthy: {server.engine.error}; "
                  "draining", file=sys.stderr, flush=True)
            stop.set()
    clean = server.drain(args.drain_timeout_s)
    from tpunet.utils.cache import compile_stats_line
    print(compile_stats_line(), flush=True)
    print(f"drained ({'clean' if clean else 'forced'})", flush=True)
    return 0 if server.engine.error is None else 2


if __name__ == "__main__":
    sys.exit(main())
