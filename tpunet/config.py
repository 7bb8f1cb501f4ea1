"""Configuration system.

The reference hardcodes every hyperparameter as a module literal
(cifar10_mpi_mobilenet_224.py: IMG_SIZE=224 at :70, batch=128 at :117,
Adam lr=1e-4 at :148, StepLR(10, 0.1) at :149, EPOCHS=20 at :158,
seed=42 at :58). We keep those exact values as *defaults* of a frozen
dataclass tree so every benchmark config is reproducible, and expose an
argparse front-end with presets matching the reference's three launch
modes (serial CPU / single accelerator / distributed data-parallel).
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass, field
from typing import Any, Mapping, Optional, Tuple

# ImageNet normalization statistics — the reference trains with these
# (cifar10_mpi_mobilenet_224.py:81-82) and its Gradio app wrongly serves
# with CIFAR-10 stats (GROUP03.pdf p.22, a train/serve skew bug we fix by
# using one constant everywhere).
IMAGENET_MEAN: Tuple[float, float, float] = (0.485, 0.456, 0.406)
IMAGENET_STD: Tuple[float, float, float] = (0.229, 0.224, 0.225)

CIFAR10_CLASSES: Tuple[str, ...] = (
    "airplane", "automobile", "bird", "cat", "deer",
    "dog", "frog", "horse", "ship", "truck",
)


@dataclass(frozen=True)
class DataConfig:
    """Data pipeline config (reference transforms at :72-89, loaders :117-133)."""

    data_dir: str = "data"
    dataset: str = "cifar10"          # "cifar10" | "synthetic"
    # Auto-download CIFAR-10 when absent (reference download=True, :97).
    # --no-download disables; the error then documents the drop-in path.
    download: bool = True
    image_size: int = 224             # reference IMG_SIZE (:70)
    batch_size: int = 128             # GLOBAL batch (reference :117 is per-rank)
    eval_batch_size: int = 0          # 0 -> same as batch_size
    num_classes: int = 10
    # Augmentation parameters mirroring the reference torchvision stack
    # (:72-82): RandomResizedCrop scale, ColorJitter strengths, rotation.
    rrc_scale: Tuple[float, float] = (0.7, 1.0)
    rrc_ratio: Tuple[float, float] = (0.75, 4.0 / 3.0)
    jitter_brightness: float = 0.3
    jitter_contrast: float = 0.3
    jitter_saturation: float = 0.3
    jitter_hue: float = 0.1
    rotation_degrees: float = 15.0
    mean: Tuple[float, float, float] = IMAGENET_MEAN
    std: Tuple[float, float, float] = IMAGENET_STD
    # Mixup / CutMix (beyond the reference's transforms; 0 = off, the
    # reference behavior). Beta(alpha, alpha) mixing inside the jitted
    # step; with both > 0 each step picks one at random.
    mixup_alpha: float = 0.0
    cutmix_alpha: float = 0.0
    # Synthetic-dataset sizes (CIFAR-10-shaped stand-in for hermetic runs).
    synthetic_train_size: int = 50_000
    synthetic_test_size: int = 10_000
    # Token datasets (model "lm"): "synthetic_lm" generates seeded
    # bigram data with this sequence length and vocab; "text_lm" chunks
    # the raw bytes of `text_path` (byte-level, vocab 256, no
    # tokenizer/downloads). vocab_size must match ModelConfig.vocab_size
    # (the CLI --vocab-size sets both).
    seq_len: int = 128
    vocab_size: int = 256
    text_path: str = ""
    # text_lm only: split the corpus into newline-delimited documents
    # and PACK them into seq_len rows with per-token segment ids;
    # attention and the next-token loss are then masked so nothing
    # crosses a document boundary or touches padding.
    pack_docs: bool = False
    # Deviation from torch DistributedSampler (which pads shards to equal
    # length, :119-124): we drop the train remainder and evaluate the test
    # set exactly (padding with masked examples), which also fixes the
    # reference's rank-local-accuracy wart (:196,224).
    drop_remainder: bool = True
    # Host-side batch assembly through the native C++ prefetcher
    # (cxx/batcher.cc) when its shared library is buildable; falls back
    # to the pure-numpy path silently otherwise.
    native_loader: bool = True

    @property
    def effective_eval_batch_size(self) -> int:
        return self.eval_batch_size or self.batch_size


@dataclass(frozen=True)
class ModelConfig:
    """Model config (reference model at :137-139: torchvision MobileNetV2
    with the classifier head swapped to 10 classes)."""

    name: str = "mobilenet_v2"        # mobilenet_v2 | vit | vit_{tiny,small,base} | vit_pp | lm
    num_classes: int = 10
    width_mult: float = 1.0
    dropout_rate: float = 0.2         # torchvision MobileNetV2 default
    dtype: str = "bfloat16"           # MXU-friendly compute dtype
    param_dtype: str = "float32"
    # ViT family fields (tpunet/models/vit.py); used when name == "vit"
    # (the vit_tiny/small/base presets fix patch/hidden/depth/heads).
    vit_patch: int = 16
    vit_hidden: int = 192
    vit_depth: int = 6
    vit_heads: int = 3
    vit_mlp_ratio: float = 4.0
    # Core attention implementation for attention models:
    # auto (flash on TPU — it wins every measured regime, README
    # long-context table — dense elsewhere) | dense | blockwise
    # (chunked K/V, bounded memory) | flash (Pallas TPU kernel: fused
    # online softmax, scores stay in VMEM; dense fallback off-TPU) |
    # ring (sequence-parallel K/V rotation over the mesh 'seq' axis) |
    # ulysses (sequence-parallel via two all-to-alls, heads resharded).
    # Default 'auto': defaults should encode the measured policy — the
    # flash kernel is fastest in every measured regime on TPU and auto
    # degrades to dense semantics elsewhere. Pass --attention dense for
    # the cross-backend reference implementation.
    attention: str = "auto"
    # K/V chunk for attention="blockwise"; block_q/block_k for "flash".
    attention_block: int = 512
    # Local core inside the sequence-parallel attentions ("ring" and
    # "ulysses"): "auto" (flash kernel on TPU, the pure-JAX path
    # elsewhere), or force "flash"/"blockwise" (the escape hatch if
    # the kernel misbehaves on some shape).
    attention_core: str = "auto"
    # Mixture-of-Experts (ViT family): 0 experts = dense MLPs. Experts
    # are sharded over the mesh 'model' axis (expert parallelism).
    moe_experts: int = 0
    moe_every: int = 2                # sparse MLP in every Nth block
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01      # load-balance loss weight
    # Expert-parallel token dispatch (tpunet/models/moe.py): "auto"
    # prefers the GShard capacity-buffer all_to_all over the expert
    # axis when shapes divide, falling back to the replicated-routing
    # psum lowering; "alltoall"/"replicated" force one (alltoall
    # raises where auto would fall back).
    moe_dispatch: str = "auto"
    # Pipeline parallelism (model name "vit_pp"): GPipe microbatches per
    # step; stages = the mesh 'pipe' axis size.
    pp_microbatches: int = 4
    # Pipeline schedule: "gpipe" (AD-emitted backward: all forwards,
    # then all backwards), "1f1b" (manual-VJP backward interleaving
    # fwd/bwd per microbatch — O(min(S, M)) live stage inputs instead
    # of O(M) stacked per-layer internals; same grads, parity-tested),
    # or "interleaved" (virtual pipeline stages: pp_virtual chunks per
    # device cut the bubble fraction ~pp_virtual-fold at a bounded
    # 1F1B-style memory cost — tpunet/parallel/pp.py interleaved).
    pp_schedule: str = "gpipe"
    # Chunks per device for pp_schedule="interleaved" (Megatron's v);
    # depth must divide into pipe * pp_virtual chunks and
    # pp_microbatches into whole pipe-axis groups.
    pp_virtual: int = 2
    # LM family (model name "lm"): vocab and the learned-position table
    # size (max trainable sequence length).
    vocab_size: int = 256
    max_seq_len: int = 1024
    # Latent-attention decoder (model name "latent_lm",
    # tpunet/models/latent_lm.py): the published config.json keys of
    # the architecture (LatentArch names them), plus ``held_experts``,
    # the routed experts whose weights this chip holds. vocab_size,
    # max_seq_len, dtype and param_dtype above apply as for "lm".
    # ``model_type`` "qwen3_next" among them selects the hybrid family
    # (tpunet/models/hybrid_mixers.py: linear attention with a per-slot
    # state beside grouped-query attention), "cohere2_moe" the parallel
    # family (one LayerNorm a block feeds windowed or position-free
    # grouped-query attention and the expert layer side by side).
    latent: Optional[Mapping[str, Any]] = None
    # Weight of the multi-token-prediction loss where the model has
    # such a module (the train step adds it to the next-token loss).
    mtp_loss_weight: float = 0.3
    # Vocab-sharded cross-entropy (tpunet/ops/vocab_ce.py): "auto"
    # shards the tied output projection + CE over the mesh 'model'
    # axis whenever it divides the vocab, so the replicated [B, T, V]
    # float32 logits never materialize; "sharded"/"full" force one.
    vocab_ce: str = "auto"
    # Rematerialize encoder blocks (jax.checkpoint): recompute block
    # activations in the backward pass instead of storing them — trades
    # ~1/3 more FLOPs for O(depth) less activation memory, the standard
    # lever for long-context training (ViT and LM families).
    remat: bool = False
    # Optional path to a torch state_dict (.pth) with ImageNet-pretrained
    # weights to convert (transfer learning is load-bearing for the ~96%
    # accuracy target — reference README.md:24-26).
    pretrained_path: Optional[str] = None


@dataclass(frozen=True)
class OptimConfig:
    """Optimizer config (reference :147-149: Adam 1e-4 + StepLR(10, 0.1))."""

    name: str = "adam"
    learning_rate: float = 1e-4
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    # LR schedule: "step" is the reference's StepLR (decay by `gamma`
    # every `step_size_epochs`); "cosine" decays to 0 over training;
    # "constant" holds the base rate. `warmup_epochs` (fractional ok)
    # prepends a linear warmup from 0 to any of them.
    schedule: str = "step"
    step_size_epochs: int = 10
    gamma: float = 0.1
    warmup_epochs: float = 0.0
    label_smoothing: float = 0.0
    # Global-gradient-norm clipping (torch clip_grad_norm_ idiom);
    # 0 = off (the reference does not clip).
    clip_norm: float = 0.0
    # Parameter EMA decay (e.g. 0.999); 0 = off. When on, evaluation
    # and the best-checkpoint use the EMA weights.
    ema_decay: float = 0.0
    # Gradient accumulation: split each global batch into this many
    # microbatches inside the jitted step (lax.scan), average the
    # microbatch gradients, apply ONE optimizer update — 1/N the
    # activation memory, the lever for reference-scale batches on
    # small-HBM chips. Gradient math matches the full batch exactly
    # (mean of equal-sized means) for the LM path; image models differ
    # benignly: BN stats update per microbatch and each microbatch
    # draws fresh augmentation/dropout RNG.
    grad_accum: int = 1


@dataclass(frozen=True)
class MeshConfig:
    """Device-mesh config. The reference's only strategy is data parallelism
    (DDP at :142-145); we build a 4-D ('data', 'seq', 'pipe', 'model')
    mesh so sequence parallelism (ring attention over 'seq'), pipeline
    parallelism (GPipe over 'pipe') and tensor/expert-parallel sharding
    (over 'model') layer on without restructuring (SURVEY.md 2b).
    """

    data: int = -1                    # -1 -> all remaining devices
    seq: int = 1                      # sequence/context-parallel axis
    pipe: int = 1                     # pipeline-parallel axis (GPipe)
    model: int = 1                    # tensor-parallel axis
    # ZeRO-1: shard Adam moments over 'data' (params stay replicated,
    # exactly the reference's layout); GSPMD gathers as needed.
    zero1: bool = False
    # FSDP / ZeRO-3: shard params AND Adam moments over 'data' (largest
    # divisible dim per leaf) — 1/N resident param+optimizer memory; the
    # train step gathers params to their compute layout once at its
    # start and Adam updates the 1/N moment shards. Subsumes zero1.
    fsdp: bool = False

    def shape(self, n_devices: int) -> Tuple[int, int, int, int]:
        seq = max(1, self.seq)
        pipe = max(1, self.pipe)
        model = max(1, self.model)
        data = (self.data if self.data > 0
                else max(1, n_devices // (seq * pipe * model)))
        return (data, seq, pipe, model)


@dataclass(frozen=True)
class ExportConfig:
    """Live telemetry export (tpunet/obs/export/): push finished obs
    records to off-host endpoints through a bounded queue drained by a
    background thread — a dead endpoint can never stall a step; full
    queues drop and count (``export_*_dropped``). Coordinator-only,
    like the metrics.jsonl writes."""

    statsd: str = ""                  # "HOST:PORT" UDP statsd endpoint
    statsd_prefix: str = "tpunet"
    http: str = ""                    # line-JSON POST URL
    # Alert webhook (tpunet/obs/export/webhook.py): POST one templated
    # JSON payload per obs_alert / obs_crash / obs_regression record
    # (--obs-webhook URL). Retries with backoff; exhausted pages land
    # in the dead-letter list and the webhook_dead_letter counter.
    webhook: str = ""
    webhook_max_retries: int = 3
    webhook_backoff_s: float = 0.25
    # Bounded export queue: put_nowait from the step path; overflow
    # drops (counted) rather than blocking.
    queue_size: int = 1024
    # close() flush budget and the per-request HTTP socket timeout.
    flush_timeout_s: float = 5.0
    http_timeout_s: float = 1.0


@dataclass(frozen=True)
class ObsConfig:
    """Step-level observability (tpunet/obs/): per-step timing
    histograms, throughput/MFU and input-stall accounting, epoch-
    boundary device-memory gauges and multi-host heartbeat, all
    emitted as ``obs_epoch`` records into ``metrics.jsonl``.

    The default path is deliberately sync-free: every number is a
    host-side ``perf_counter`` lap or an epoch-boundary runtime query,
    so enabling it adds no device round-trips to the step loop."""

    enabled: bool = True
    # Emit an ``obs_step`` record every N steps (0 = per-epoch records
    # only). Host-side values only — no device sync either way.
    step_records_every: int = 0
    # Windowed profiling: capture a jax profiler trace for exactly
    # [profile_start_step, profile_start_step + profile_num_steps).
    # num_steps == 0 traces from start_step to the end of the run
    # (with both at 0 and --profile-dir set: the old whole-run trace);
    # either knob without --profile-dir writes under
    # <checkpoint-dir>/profile.
    profile_start_step: int = 0
    profile_num_steps: int = 0
    # Histogram memory bound: windows beyond this many observations
    # switch from exact percentiles to seeded reservoir sampling
    # (count/mean stay exact; the summary carries ``approx: 1``).
    histogram_max_samples: int = 65536
    # --obs-hbm-attrib: once, at the first step, AOT-compile the train
    # step and decompose its cost-analysis HBM bytes by op category
    # into the hbm_bytes_per_image_* gauge family
    # (tpunet/obs/hlo_bytes.py). Off by default: the extra lowering is
    # one redundant compile (cheap under the persistent cache, not
    # free).
    hbm_attrib: bool = False
    # -- run-health watchdog (tpunet/obs/health.py) -----------------
    # A step slower than stall_factor x the rolling median (and at
    # least stall_min_s) emits a step_stall obs_alert. 0 disables.
    stall_factor: float = 10.0
    stall_min_s: float = 1.0
    # A host-available loss above loss_spike_factor x its warmed-up
    # EMA emits a loss_spike alert (non-finite always alerts). 0
    # disables spike detection.
    loss_spike_factor: float = 5.0
    # No heartbeat for this long emits stale_heartbeat; 0 (default)
    # disables — epoch length varies too much for a universal budget.
    heartbeat_timeout_s: float = 0.0
    # Same-reason alerts within this many steps are suppressed
    # (counted in obs_alerts_suppressed) so a stall pages once.
    alert_cooldown_steps: int = 50
    # Fatal alerts raise RunUnhealthyError instead of just recording:
    # the --halt-on-unhealthy knob, for runs nobody is watching.
    halt_on_unhealthy: bool = False
    # Run identity (docs/metrics_schema.md "Run identity"): every
    # emitted record is stamped run_id/process_index/host so a fleet
    # aggregator can route streams. Empty = generate (and persist
    # under <checkpoint-dir>/run_id; --resume reuses it, so a
    # preemption restore continues the same stream).
    run_id: str = ""
    # Operator GaugePredicate alert rules over exported gauges,
    # evaluated each epoch against registry.snapshot(): "NAME > N",
    # "NAME < N", or "NAME + N/s" (growth rate). Fired rules emit
    # gauge_predicate obs_alerts (--obs-rule, repeatable).
    gauge_rules: Tuple[str, ...] = ()
    # Proactive checkpoint-and-evict (--evict-on-straggler,
    # docs/elasticity.md): a straggler-shaped watchdog alert on THIS
    # replica (step_stall / thread_stalled) triggers the agreed stop
    # with an evict marker — the pod checkpoints now and re-meshes
    # without the slow host instead of letting it stall every step.
    # Off by default; meaningful under the elastic agent.
    evict_on_straggler: bool = False
    # -- flight recorder (tpunet/obs/flightrec/) --------------------
    # Always-on black box: a crash-durable mmap ring of recent
    # structured events, faulthandler + native SIGSEGV/SIGABRT/SIGBUS
    # hooks, the host-thread registry, and a post-mortem watcher that
    # materializes <checkpoint-dir>/flightrec/crash_report.json when
    # the process dies uncleanly. Near-zero cost (~1-2 us per event,
    # no syscalls on the step path); --no-flightrec disables.
    flightrec: bool = True
    # Event-ring capacity (slots; the file is ~120 bytes per slot).
    flightrec_events: int = 1024
    export: ExportConfig = field(default_factory=ExportConfig)


@dataclass(frozen=True)
class ServeConfig:
    """Production inference server (tpunet/serve/): a fixed pool of KV
    slots decoded together by one jitted masked step (continuous
    batching — requests join mid-flight, finished ones free their slot,
    no recompilation), a bounded admission queue with backpressure, and
    a stdlib HTTP frontend. The Gradio app (tpunet/infer/app.py) stays
    the reference-parity demo; this is the heavy-traffic path."""

    host: str = "127.0.0.1"
    port: int = 8000
    # KV-slot pool size = max in-flight decodes = the jitted step's
    # batch dimension. Compiled once; sizing it is the HBM/latency
    # trade (docs/serving.md capacity guidance).
    slots: int = 8
    # Bounded admission: requests beyond this many waiting are REJECTED
    # (429 queue-full) instead of growing latency unboundedly.
    queue_max: int = 64
    # Prefill programs are compiled per padded prompt-length bucket —
    # the compile count is len(buckets), not one per prompt length.
    # Prompts longer than the largest bucket are rejected.
    prefill_buckets: Tuple[int, ...] = (32, 128, 512)
    # The KV cache is paged: per layer, K/V live in a SHARED pool of
    # fixed-size pages addressed through per-slot page tables, so a
    # slot pins HBM proportional to its prompt+generated length — the
    # concurrent-slot multiplier at fixed HBM (docs/serving.md "Paged
    # KV cache & device-side sampling").
    # Usable data pages in the pool (0 = auto: slots *
    # ceil(max_seq_len / kv_page_tokens), every slot at full length).
    # Size it DOWN to oversubscribe slots against typical
    # request lengths; exhaustion defers admissions and, when nothing
    # can advance, preempts the youngest slot back to the queue with
    # its progress kept.
    kv_pages: int = 0
    # Tokens per KV page: the allocation granule. Smaller pages track
    # request length tighter (less tail waste) at more gather/table
    # overhead per step.
    kv_page_tokens: int = 16
    # KV page payload dtype: "auto" stores at the model compute dtype;
    # "bf16" halves float32 payloads; "int8" quantizes each written
    # token row against its own absmax (float32 scale stored with the
    # page, dequantized on gather; eval-parity-gated in
    # tests/test_serve_paged.py).
    kv_dtype: str = "auto"
    # Prefix KV cache (default ON; --no-prefix-cache disables):
    # finished prefill pages stay in the pool as immutable,
    # content-addressed, refcounted objects keyed by token-prefix
    # digest at page granularity. Admission pins the longest cached
    # page-aligned prefix into the new slot's table and re-prefills
    # only the suffix (COW at the divergence page); LRU-evicted under
    # pool pressure — docs/serving.md "Prefix KV cache".
    prefix_cache: bool = True
    # Pool pages the prefix cache may hold (pinned + idle); 0 = auto
    # (half the usable pool). Bounding it below the pool keeps paying
    # slots from ever being starved by cached pages.
    prefix_cache_pages: int = 0
    # Shared-filesystem prefix spill/warm-start (--prefix-store DIR):
    # freshly-cached pages publish to DIR (content-digest tmp+rename,
    # flock first-writer-wins — the AOT store's commit discipline via
    # tpunet/utils/fsatomic.py), and a respawned or scaled-up replica
    # adopts the fleet's prefix set at boot so its first shared-prefix
    # request prefills only the suffix. Entries are scoped by model
    # config + kv levers + runtime, so a lever change is a clean miss.
    # Empty = per-replica cache only.
    prefix_store: str = ""
    # Per-request caps: default/max new tokens, and a wall-clock
    # deadline after which a request is cancelled and its slot freed
    # (0 = no deadline).
    default_max_new_tokens: int = 128
    max_new_tokens_cap: int = 1024
    default_deadline_s: float = 0.0
    # Classifier micro-batching: hold a /v1/classify request at most
    # this long to coalesce a batch, up to classify_batch_max images
    # per jitted batched forward.
    classify_batch_max: int = 8
    classify_window_ms: float = 2.0
    # Emit an ``obs_serve`` record (SLO counters/gauges/histograms)
    # every this many seconds; 0 disables periodic emission (records
    # still flush once on drain).
    emit_every_s: float = 10.0
    # Graceful-drain budget on SIGTERM: stop admitting, finish
    # in-flight work for up to this long, then cancel survivors.
    drain_timeout_s: float = 30.0
    # Replica identity on obs_serve records (fleet SLO rollups route
    # by it). Empty = "serve-<host>-<pid>".
    run_id: str = ""
    # AOT warm-start (--aot-cache DIR, tpunet/utils/cache.py
    # AotProgramStore): serialize the fully-compiled decode +
    # bucketed-prefill executables under DIR at first boot and
    # deserialize them on every later boot — no tracing, no lowering,
    # no XLA — so a respawned replica serves its first token in
    # seconds instead of recompiling (the router tier's autoscaling
    # depends on it; docs/serving.md "AOT warm-start"). Empty = off
    # (the persistent compilation cache still applies). Single-device
    # replicas only; ignored with --mesh-model > 1.
    aot_cache: str = ""
    # Serve-tier fault injection (--chaos, tpunet/serve/chaos.py):
    # deterministic SIGKILL/stall/probe-drop/slow-stream faults
    # addressed by generated-token count or prefill ordinal —
    # docs/serving.md "Mid-stream failover & serve-tier chaos". Empty
    # = no injector installed.
    chaos: str = ""
    # Standalone-serve request tracing (--trace-sample, docs/serving.md
    # "Request tracing"): head-sample this fraction of requests that
    # arrive WITHOUT trace headers, minting a trace_id locally. Under
    # a router the router decides (its headers win); a client-supplied
    # ``X-Trace-Id`` is always sampled. 0 = only header-carried traces.
    trace_sample: float = 0.0
    # Speculative decoding (--spec-decode, docs/serving.md
    # "Speculative decoding"): a small drafter model proposes spec_k
    # tokens per active slot against its OWN paged KV pool, then the
    # main model verifies every slot's drafts in ONE [slots, K+1]-wide
    # jitted forward over the existing pool — up to K+1 verified
    # tokens per slot per verify. Every emitted token comes from the
    # VERIFY distribution, so greedy output is bitwise-identical to
    # spec-off and sampled output stays deterministic per (seed, step)
    # (failover/replay safe). Rejection rewinds the slot's page-table
    # cursor to the last accepted position and recycles the tail
    # pages.
    spec_decode: bool = False
    # Draft tokens proposed per verify cycle (the K in draft-then-
    # verify). Higher K amortizes the verify gather over more tokens
    # but wastes drafter work when acceptance is low — docs/serving.md
    # "Speculative decoding" has the tuning math.
    spec_k: int = 4
    # Drafter width multiplier on the serving model's vit_hidden
    # (rounded to stay divisible by vit_heads). 1.0 shares the main
    # model's parameters (self-speculation — useful for parity tests,
    # never a throughput win); < 1.0 builds a second, narrower model
    # instance whose parameters come from --spec-draft-checkpoint or
    # a deterministic init.
    spec_draft_width_mult: float = 0.5
    # Drafter parameters (.npz from tpunet/serve/spec.py
    # ``save_drafter_params``; empty = deterministic random init,
    # which accepts ~nothing — fit or distill a drafter against real
    # traffic, e.g. ``spec.fit_drafter`` as bench_serve.py --spec
    # does).
    spec_draft_checkpoint: str = ""


@dataclass(frozen=True)
class RouterConfig:
    """Routing + autoscaling front tier (tpunet/router/,
    docs/serving.md "Routing & autoscaling"): a stdlib-threaded HTTP
    proxy that spreads /v1/generate + /v1/classify over N serve
    replicas (least-loaded with session/prefix affinity), evicts and
    respawns unhealthy replicas, and emits hysteresis scale-up/down
    decisions as ``obs_router`` records."""

    host: str = "127.0.0.1"
    port: int = 8100
    # Health/load probe cadence against each replica's /healthz +
    # /metrics; a probe slower than probe_timeout_s counts as a
    # failure, and unhealthy_after consecutive failures evict.
    probe_interval_s: float = 1.0
    probe_timeout_s: float = 2.0
    unhealthy_after: int = 3
    # Session/prefix affinity: requests with the same "session" field
    # (or the same first affinity_prefix prompt tokens/bytes) hash to
    # a stable preferred replica so shared-prompt traffic lands on
    # warm KV — unless the preferred replica's load score exceeds the
    # least-loaded replica's by more than affinity_slack (fraction of
    # its pool), in which case least-loaded wins.
    affinity_prefix: int = 16
    affinity_slack: float = 0.5
    # Re-route budget: a request that hits a dead/draining replica is
    # retried against another replica up to route_retries times (only
    # before any response byte reached the client).
    route_retries: int = 2
    # Per-proxied-request socket timeout toward a replica.
    request_timeout_s: float = 600.0
    # obs_router window record cadence (0 = final record only).
    emit_every_s: float = 10.0
    # Autoscale hysteresis over fleet queue depth per slot (and TTFT
    # SLO burn when ttft_slo_ms > 0): the condition must hold for
    # scale_window_probes consecutive probe rounds to fire, and after
    # any action the policy holds for scale_cooldown_s.
    scale_up_queue_per_slot: float = 1.0
    scale_down_queue_per_slot: float = 0.1
    scale_window_probes: int = 5
    scale_cooldown_s: float = 60.0
    min_replicas: int = 1
    max_replicas: int = 8
    # TTFT SLO (ms): fleet TTFT p99 above it counts as SLO burn > 1
    # and arms scale-up like queue pressure; 0 disables the term.
    ttft_slo_ms: float = 0.0
    # Drain-then-restart budget: SIGTERM -> graceful drain for up to
    # this long -> SIGKILL; in-flight streams finish inside it.
    drain_grace_s: float = 30.0
    # Boot grace: probe failures while a replica is STARTING (loading
    # weights, warming/deserializing programs) don't count toward
    # eviction until this much time has passed since its (re)spawn.
    boot_timeout_s: float = 120.0
    # Backoff before respawning an evicted/dead replica child.
    respawn_backoff_s: float = 1.0
    # Mid-stream failover (--failover / --no-failover, docs/serving.md
    # "Mid-stream failover & serve-tier chaos"): the frontend journals
    # every streamed /v1/generate request (prompt, sampling params,
    # relayed tokens) and, when the serving replica dies mid-stream,
    # re-submits to a survivor with ``resume_tokens`` — the client's
    # ndjson stream continues with no error frame (greedy:
    # token-identical; sampled: deterministic per (seed, step)).
    failover: bool = True
    # Per-request journal bound: a stream that has relayed more than
    # this many tokens is no longer failover-protected (on replica
    # death it gets the honest error frame — the documented
    # degradation mode). Bounds router memory per in-flight stream.
    failover_journal_tokens: int = 4096
    # Resume attempts per request after a mid-stream replica death
    # (each attempt picks a different surviving replica).
    failover_retries: int = 2
    # Serve-tier fault injection forwarded to spawned replicas
    # (--chaos, tpunet/serve/chaos.py grammar plus a ``:replica=I``
    # scope key naming the child index; unscoped events reach every
    # child). Empty = no injection.
    chaos: str = ""
    # End-to-end request tracing (--trace-sample, docs/serving.md
    # "Request tracing"): the frontend mints a trace_id per request
    # and head-samples this fraction of them (deterministic on the
    # id); sampled requests carry ``X-Trace-Id`` to every replica hop
    # — including failover re-submits — and every layer records trace
    # breadcrumbs + an ``obs_trace`` record. A client-supplied
    # ``X-Trace-Id`` is always sampled (explicit opt-in).
    trace_sample: float = 0.01
    # Tail capture for the requests sampling missed
    # (--no-trace-all-on-error disables): an UNsampled request that
    # hits a mid-stream failover or errors still gets a router-hop
    # ``obs_trace`` record — replica-side phases are absent (the
    # replicas never saw trace context), but the seam and outcome are
    # on the books.
    trace_all_on_error: bool = True
    # Synthetic canary probing (--probe-every-s, tpunet/router/
    # prober.py, docs/serving.md "SLOs & probing"): every this many
    # seconds the router issues a pinned greedy known-answer request
    # through its OWN public endpoint — the full proxy path — and
    # judges availability, TTFT/e2e latency, and bitwise golden-output
    # correctness from the client's side, feeding the SLO engine's SLI
    # streams. Each probe carries a minted always-sampled X-Trace-Id,
    # so a failed or slow probe points at a replayable trace. 0 = off.
    probe_every_s: float = 0.0
    # SLO policy file (--slo-policy, docs/slos.json format:
    # objectives + compliance windows + multi-window burn-rate alert
    # rules; full-line // comments allowed). Arming it (or the
    # prober) starts the tpunet/obs/slo.py engine: obs_slo records,
    # slo_* gauges, and edge-latched fast-burn pages / slow-burn
    # tickets through the obs_alert webhook path. Empty = built-in
    # default policy when the prober is armed, otherwise off.
    slo_policy: str = ""
    # Router identity on obs_router records (empty =
    # "router-<host>-<pid>").
    run_id: str = ""


@dataclass(frozen=True)
class CheckpointConfig:
    directory: str = "checkpoints"
    save_best: bool = True            # reference best-by-test-acc (:238-240)
    save_last: bool = True            # upgrade: full state for resume
    resume: bool = False
    keep: int = 2


@dataclass(frozen=True)
class TrainConfig:
    """Top-level config."""

    epochs: int = 20                  # reference EPOCHS (:158)
    seed: int = 42                    # reference torch.manual_seed(42) (:58)
    # Fault injection (--chaos, tpunet/elastic/chaos.py): deterministic
    # SIGKILL/SIGTERM/slow-host/checkpoint-IO faults addressed by step
    # or save ordinal — docs/elasticity.md "Chaos spec grammar". Empty
    # = no injector installed.
    chaos: str = ""
    # Preemption grace window (--preempt-grace-s): seconds the platform
    # grants after SIGTERM. The guard budgets the checkpoint-durability
    # wait inside it and a second SIGTERM escalates to an immediate
    # checkpoint-abandon exit. 0 = unknown/unbounded (legacy behavior).
    preempt_grace_s: float = 0.0
    # Evaluate a saved checkpoint (best params if present, else the
    # last full state) and exit — no training.
    eval_only: bool = False
    log_every_steps: int = 0          # 0 -> per-epoch only, like the reference
    profile_dir: str = ""             # non-empty -> jax.profiler traces
    data: DataConfig = field(default_factory=DataConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Presets: the reference's three launch modes (SURVEY.md section 0).
# ---------------------------------------------------------------------------

def preset(name: str) -> TrainConfig:
    """Return the config for a named launch mode.

    - ``serial``      — reference cifar10_serial_mobilenet_224.py: batch 64.
    - ``single``      — reference cifar10_128batch.py: batch 128, one chip.
    - ``distributed`` — reference cifar10_mpi_mobilenet_224.py: 128 per
      device (global batch = 128 * n_devices is resolved at runtime).
    """
    base = TrainConfig()
    if name == "serial":
        return base.replace(data=dataclasses.replace(base.data, batch_size=64))
    if name == "single":
        return base
    if name == "distributed":
        return base  # global batch scaled by the caller from mesh size
    raise ValueError(f"unknown preset {name!r}; expected serial|single|distributed")


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="tpunet trainer")
    p.add_argument("--preset", default="single",
                   choices=["serial", "single", "distributed"])
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None,
                   help="global batch size")
    p.add_argument("--image-size", type=int, default=None)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--optimizer", default=None,
                   choices=["adam", "adamw", "sgd"],
                   help="adam is the reference stack (:148); adamw "
                        "activates --weight-decay; sgd uses momentum 0.9")
    p.add_argument("--weight-decay", type=float, default=None)
    p.add_argument("--label-smoothing", type=float, default=None)
    p.add_argument("--eval-batch-size", type=int, default=None,
                   help="global eval batch (default: --batch-size)")
    p.add_argument("--lr-schedule", default=None,
                   choices=["step", "cosine", "constant"],
                   help="step = the reference's StepLR(10, 0.1); cosine "
                        "decays to 0 over training")
    p.add_argument("--warmup-epochs", type=float, default=None,
                   help="linear LR warmup over this many (fractional) "
                        "epochs, before any schedule")
    p.add_argument("--clip-norm", type=float, default=None,
                   help="global gradient-norm clip; 0 = off")
    p.add_argument("--ema-decay", type=float, default=None,
                   help="parameter EMA decay (e.g. 0.999); eval and the "
                        "best checkpoint use the EMA weights; 0 = off")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--data-dir", default=None)
    p.add_argument("--dataset", default=None,
                   choices=["cifar10", "synthetic", "synthetic_lm",
                            "text_lm"])
    p.add_argument("--text-file", default=None,
                   help="byte-level corpus file for --dataset text_lm")
    p.add_argument("--pack-docs", action="store_true",
                   help="text_lm: pack newline-delimited documents into "
                        "seq_len rows with segment-masked attention and "
                        "loss (no cross-document attention/prediction)")
    p.add_argument("--no-download", action="store_true",
                   help="never fetch CIFAR-10/pretrained weights over "
                        "the network; fail with drop-in instructions "
                        "instead (reference auto-downloads, :97)")
    p.add_argument("--pretrained", default=None, metavar="PATH|auto",
                   help="torch MobileNetV2 state_dict to convert; 'auto' "
                        "fetches torchvision's ImageNet checkpoint into "
                        "~/.cache/tpunet (the reference's "
                        "pretrained=True, :137)")
    p.add_argument("--model", default=None,
                   choices=["mobilenet_v2", "vit", "vit_tiny", "vit_small",
                            "vit_base", "vit_pp", "lm", "lm_pp"])
    p.add_argument("--seq-len", type=int, default=None,
                   help="sequence length for token datasets (model lm)")
    p.add_argument("--max-seq-len", type=int, default=None,
                   help="LM position-table size (defaults to at least "
                        "--seq-len)")
    p.add_argument("--vocab-size", type=int, default=None,
                   help="vocab for the lm model + synthetic_lm data")
    p.add_argument("--pp-microbatches", type=int, default=None,
                   help="GPipe microbatches per step (vit_pp)")
    p.add_argument("--pp-schedule", default=None,
                   choices=["gpipe", "1f1b", "interleaved"],
                   help="pipeline schedule: gpipe (AD backward), 1f1b "
                        "(manual-VJP backward, bounded activation "
                        "memory), or interleaved (virtual stages: "
                        "--pp-virtual chunks per device, ~v-fold "
                        "smaller bubble at 1F1B-style memory)")
    p.add_argument("--pp-virtual", type=int, default=None,
                   help="chunks per device for --pp-schedule "
                        "interleaved (depth must divide pipe x v)")
    p.add_argument("--attention", default=None,
                   choices=["auto", "dense", "blockwise", "flash",
                            "ring", "ulysses"],
                   help="core attention impl for ViT/LM models; 'flash' "
                        "is the fused Pallas TPU kernel (dense fallback "
                        "off-TPU); 'ring' and 'ulysses' are "
                        "sequence-parallel over the mesh 'seq' axis")
    p.add_argument("--attention-block", type=int, default=None,
                   help="K/V chunk size for --attention blockwise; "
                        "block_q/block_k for --attention flash")
    p.add_argument("--attention-core", default=None,
                   choices=["auto", "flash", "blockwise"],
                   help="local core inside --attention ring/ulysses: "
                        "auto = flash kernel on TPU, the pure-JAX path "
                        "elsewhere; force blockwise as the escape hatch")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize encoder blocks (less activation "
                        "memory, ~1/3 more backward FLOPs)")
    p.add_argument("--zero1", action="store_true",
                   help="shard optimizer moments over the 'data' axis "
                        "(ZeRO-1); params stay replicated")
    p.add_argument("--fsdp", action="store_true",
                   help="fully-sharded data parallelism (ZeRO-3): shard "
                        "params and optimizer moments over 'data'; "
                        "weights are all-gathered just-in-time")
    p.add_argument("--grad-accum", type=int, default=None,
                   help="microbatches accumulated per optimizer step "
                        "(the global batch is split in time; 1/N the "
                        "activation memory; full-batch gradient math "
                        "except per-microbatch BN stats/augment RNG)")
    p.add_argument("--moe-experts", type=int, default=None,
                   help="experts per MoE block (vit/lm/lm_pp); "
                        "0 = dense MLPs")
    p.add_argument("--moe-top-k", type=int, default=None)
    p.add_argument("--moe-every", type=int, default=None)
    p.add_argument("--moe-capacity-factor", type=float, default=None)
    p.add_argument("--moe-aux-weight", type=float, default=None)
    p.add_argument("--moe-dispatch", default=None,
                   choices=["auto", "alltoall", "replicated"],
                   help="expert-parallel token dispatch: GShard "
                        "all_to_all capacity buffers vs replicated "
                        "routing + psum (auto prefers alltoall when "
                        "shapes divide)")
    p.add_argument("--vocab-ce", default=None,
                   choices=["auto", "sharded", "full"],
                   help="LM loss lowering: vocab-sharded logits + CE "
                        "over the mesh 'model' axis (full [B,T,V] "
                        "logits never materialize) vs the full-logits "
                        "path (auto shards when the axis divides the "
                        "vocab)")
    p.add_argument("--dropout-rate", type=float, default=None,
                   help="dropout rate for every model family (default "
                        "0.2, torchvision MobileNetV2's classifier "
                        "dropout; LMs inherit it unless overridden)")
    p.add_argument("--vit-patch", type=int, default=None)
    p.add_argument("--vit-hidden", type=int, default=None)
    p.add_argument("--vit-depth", type=int, default=None)
    p.add_argument("--vit-heads", type=int, default=None)
    p.add_argument("--vit-mlp-ratio", type=float, default=None,
                   help="ViT MLP hidden width as a multiple of the "
                        "embedding width (default 4.0)")
    p.add_argument("--param-dtype", default=None,
                   choices=["float32", "bfloat16"],
                   help="parameter/optimizer-state storage dtype "
                        "(default float32 master params; --dtype "
                        "stays the compute dtype)")
    p.add_argument("--width-mult", type=float, default=None)
    p.add_argument("--synthetic-size", type=int, default=None,
                   help="train-set size when --dataset synthetic")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--eval-only", action="store_true",
                   help="evaluate the saved checkpoint (best params if "
                        "present, else the last full state) and exit")
    p.add_argument("--mesh-data", type=int, default=None)
    p.add_argument("--mesh-seq", type=int, default=None,
                   help="sequence-parallel axis size (ring/ulysses "
                        "attention)")
    p.add_argument("--mesh-pipe", type=int, default=None,
                   help="pipeline-parallel axis size (vit_pp model)")
    p.add_argument("--mesh-model", type=int, default=None,
                   help="tensor-parallel axis size")
    p.add_argument("--dtype", default=None, choices=["bfloat16", "float32"])
    p.add_argument("--profile-dir", default=None,
                   help="jax profiler trace output directory; combine "
                        "with --profile-start-step/--profile-num-steps "
                        "to capture a step window instead of the run")
    p.add_argument("--profile-start-step", type=int, default=None,
                   help="global step at which the profiler trace "
                        "starts (alone: traces to the end of the run, "
                        "under <checkpoint-dir>/profile unless "
                        "--profile-dir is set)")
    p.add_argument("--profile-num-steps", type=int, default=None,
                   help="steps to trace from --profile-start-step "
                        "(0 = until the end of the run); without "
                        "--profile-dir the trace lands under "
                        "<checkpoint-dir>/profile")
    p.add_argument("--no-obs", action="store_true",
                   help="disable the observability subsystem (no "
                        "obs_* records, spans, or step timing)")
    p.add_argument("--obs-step-every", type=int, default=None,
                   help="emit a per-step obs_step record every N "
                        "steps (0 = per-epoch obs records only)")
    p.add_argument("--flightrec", default=None,
                   action=argparse.BooleanOptionalAction,
                   help="black-box flight recorder (default on): "
                        "crash-durable event ring + crash handlers "
                        "that leave <checkpoint-dir>/flightrec/"
                        "crash_report.json (ring tail, per-thread "
                        "stacks, native batcher journal) when the "
                        "process dies; render with "
                        "scripts/obs_crash_report.py")
    p.add_argument("--flightrec-events", type=int, default=None,
                   help="flight-recorder event-ring capacity (slots)")
    p.add_argument("--obs-hbm-attrib", action="store_true",
                   help="decompose the compiled train step's HBM "
                        "bytes by op category into the "
                        "hbm_bytes_per_image_* gauges once at the "
                        "first step (one extra AOT lowering)")
    p.add_argument("--statsd", default=None, metavar="HOST:PORT",
                   help="stream obs records as statsd/UDP gauges to "
                        "this endpoint (non-blocking: bounded queue + "
                        "background sender; drops are counted)")
    p.add_argument("--obs-http", default=None, metavar="URL",
                   help="POST obs records as line-JSON to this URL "
                        "(same non-blocking queue; pair with "
                        "'scripts/obs_dashboard.py --listen PORT')")
    p.add_argument("--obs-webhook", default=None, metavar="URL",
                   help="POST one templated JSON payload per alert "
                        "record (obs_alert/obs_crash/obs_regression) "
                        "to this URL — retried with backoff, "
                        "dead-lettered after webhook_max_retries "
                        "(wire format in docs/metrics_schema.md)")
    p.add_argument("--obs-queue-size", type=int, default=None,
                   help="bounded export queue depth (overflow drops "
                        "records and counts them, never blocks a step)")
    p.add_argument("--obs-hist-samples", type=int, default=None,
                   help="histogram reservoir bound "
                        "(histogram_max_samples): windows beyond this "
                        "many observations switch from exact "
                        "percentiles to seeded reservoir sampling")
    p.add_argument("--alert-cooldown-steps", type=int, default=None,
                   help="suppress same-reason obs_alerts within this "
                        "many steps (counted in obs_alerts_suppressed) "
                        "so a stall pages once")
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="deterministic fault injection "
                        "(docs/elasticity.md): e.g. 'kill@step=5', "
                        "'kill@ckpt=2', 'sigterm@step=8:again=1', "
                        "'slow@step=10:delay=1:steps=3', "
                        "'ioerr@save=1:fails=2'; ';'-separated, "
                        "host=H scopes one process")
    p.add_argument("--preempt-grace-s", type=float, default=None,
                   help="SIGTERM grace window the platform grants: "
                        "the preemption save's durability wait is "
                        "bounded by what remains of it, and a second "
                        "SIGTERM escalates to immediate "
                        "checkpoint-abandon exit (0 = unbounded)")
    p.add_argument("--evict-on-straggler", action="store_true",
                   help="straggler-shaped watchdog alerts (step_stall"
                        "/thread_stalled) on this replica trigger "
                        "checkpoint-now-then-evict through the agreed "
                        "stop — the elastic agent re-meshes the pod "
                        "without the slow host (docs/elasticity.md)")
    p.add_argument("--halt-on-unhealthy", action="store_true",
                   help="abort the run (RunUnhealthyError) on a fatal "
                        "obs_alert: step stall, NaN/spiking loss, or "
                        "missing processes — after the alert record "
                        "is written")
    p.add_argument("--stall-factor", type=float, default=None,
                   help="step_stall alert threshold: a step slower "
                        "than FACTOR x the rolling median (and at "
                        "least --stall-min-s); 0 disables")
    p.add_argument("--stall-min-s", type=float, default=None,
                   help="absolute floor (seconds) a step must exceed "
                        "to count as stalled")
    p.add_argument("--loss-spike-factor", type=float, default=None,
                   help="loss_spike alert threshold: loss above "
                        "FACTOR x its EMA; 0 disables")
    p.add_argument("--heartbeat-timeout", type=float, default=None,
                   metavar="SECONDS",
                   help="stale_heartbeat alert when no epoch "
                        "heartbeat lands for this long (0 = off)")
    p.add_argument("--run-id", default=None,
                   help="explicit run identity stamped on every obs "
                        "record (default: generated and persisted "
                        "under <checkpoint-dir>/run_id; --resume "
                        "reuses it)")
    p.add_argument("--obs-rule", action="append", default=None,
                   metavar="RULE",
                   help="GaugePredicate alert rule over any registry "
                        "snapshot key, e.g. 'mfu < 0.3', "
                        "'step_time_s_p99 > 2', "
                        "'mem_peak_bytes_in_use + 1e6/s' (growth per "
                        "second); repeatable, checked each epoch")
    p.add_argument("--log-every-steps", type=int, default=None,
                   help="emit a step/loss/lr line every N steps (0 = "
                        "per-epoch only, like the reference)")
    p.add_argument("--no-native-loader", action="store_true",
                   help="force the pure-numpy host batch path")
    p.add_argument("--mixup", type=float, default=None, metavar="ALPHA",
                   help="mixup Beta(a,a) strength for image models; "
                        "0 = off")
    p.add_argument("--cutmix", type=float, default=None, metavar="ALPHA",
                   help="CutMix Beta(a,a) strength; with --mixup, each "
                        "step picks one at random")
    return p


def config_from_args(argv=None) -> TrainConfig:
    args = build_argparser().parse_args(argv)
    cfg = preset(args.preset)
    data, model, optim, mesh, ckpt = cfg.data, cfg.model, cfg.optim, cfg.mesh, cfg.checkpoint
    obs = cfg.obs
    if args.no_obs:
        obs = dataclasses.replace(obs, enabled=False)
    if args.obs_step_every is not None:
        obs = dataclasses.replace(obs, step_records_every=args.obs_step_every)
    if args.obs_hbm_attrib:
        obs = dataclasses.replace(obs, hbm_attrib=True)
    if args.flightrec is not None:
        obs = dataclasses.replace(obs, flightrec=args.flightrec)
    if args.flightrec_events is not None:
        obs = dataclasses.replace(obs,
                                  flightrec_events=args.flightrec_events)
    if args.profile_start_step is not None:
        obs = dataclasses.replace(obs,
                                  profile_start_step=args.profile_start_step)
    if args.profile_num_steps is not None:
        obs = dataclasses.replace(obs,
                                  profile_num_steps=args.profile_num_steps)
    export = obs.export
    if args.statsd is not None:
        export = dataclasses.replace(export, statsd=args.statsd)
    if args.obs_http is not None:
        export = dataclasses.replace(export, http=args.obs_http)
    if args.obs_webhook is not None:
        export = dataclasses.replace(export, webhook=args.obs_webhook)
    if args.obs_queue_size is not None:
        export = dataclasses.replace(export,
                                     queue_size=args.obs_queue_size)
    if export is not obs.export:
        obs = dataclasses.replace(obs, export=export)
    if args.halt_on_unhealthy:
        obs = dataclasses.replace(obs, halt_on_unhealthy=True)
    if args.evict_on_straggler:
        obs = dataclasses.replace(obs, evict_on_straggler=True)
    if args.run_id is not None:
        obs = dataclasses.replace(obs, run_id=args.run_id)
    if args.obs_rule:
        obs = dataclasses.replace(obs, gauge_rules=tuple(args.obs_rule))
    for obs_field, arg in (("stall_factor", args.stall_factor),
                           ("stall_min_s", args.stall_min_s),
                           ("loss_spike_factor", args.loss_spike_factor),
                           ("heartbeat_timeout_s",
                            args.heartbeat_timeout),
                           ("histogram_max_samples",
                            args.obs_hist_samples),
                           ("alert_cooldown_steps",
                            args.alert_cooldown_steps)):
        if arg is not None:
            obs = dataclasses.replace(obs, **{obs_field: arg})
    if args.batch_size is not None:
        data = dataclasses.replace(data, batch_size=args.batch_size)
    if args.image_size is not None:
        data = dataclasses.replace(data, image_size=args.image_size)
    if args.data_dir is not None:
        data = dataclasses.replace(data, data_dir=args.data_dir)
    if args.dataset is not None:
        data = dataclasses.replace(data, dataset=args.dataset)
    if args.no_native_loader:
        data = dataclasses.replace(data, native_loader=False)
    if args.no_download:
        data = dataclasses.replace(data, download=False)
    if args.text_file is not None:
        data = dataclasses.replace(data, text_path=args.text_file)
    if args.pack_docs:
        data = dataclasses.replace(data, pack_docs=True)
    if args.mixup is not None:
        data = dataclasses.replace(data, mixup_alpha=args.mixup)
    if args.cutmix is not None:
        data = dataclasses.replace(data, cutmix_alpha=args.cutmix)
    if args.seq_len is not None:
        data = dataclasses.replace(data, seq_len=args.seq_len)
    if args.max_seq_len is not None:
        model = dataclasses.replace(model, max_seq_len=args.max_seq_len)
    if data.seq_len > model.max_seq_len:
        # Long-context runs shouldn't require editing source: grow the
        # position table to cover the requested sequence length.
        model = dataclasses.replace(model, max_seq_len=data.seq_len)
    if args.vocab_size is not None:
        data = dataclasses.replace(data, vocab_size=args.vocab_size)
        model = dataclasses.replace(model, vocab_size=args.vocab_size)
    if args.synthetic_size is not None:
        data = dataclasses.replace(
            data, synthetic_train_size=args.synthetic_size,
            synthetic_test_size=max(1, args.synthetic_size // 4))
    if args.pretrained is not None:
        model = dataclasses.replace(model, pretrained_path=args.pretrained)
    if args.model is not None:
        model = dataclasses.replace(model, name=args.model)
    if args.attention is not None:
        model = dataclasses.replace(model, attention=args.attention)
    if args.attention_block is not None:
        model = dataclasses.replace(model, attention_block=args.attention_block)
    if args.attention_core is not None:
        model = dataclasses.replace(model, attention_core=args.attention_core)
    if args.remat:
        model = dataclasses.replace(model, remat=True)
    if args.zero1:
        mesh = dataclasses.replace(mesh, zero1=True)
    if args.fsdp:
        mesh = dataclasses.replace(mesh, fsdp=True)
    if args.grad_accum is not None:
        optim = dataclasses.replace(optim, grad_accum=args.grad_accum)
    for name in ("vit_patch", "vit_hidden", "vit_depth", "vit_heads",
                 "vit_mlp_ratio", "param_dtype",
                 "moe_experts", "moe_top_k", "moe_every",
                 "moe_capacity_factor", "moe_aux_weight", "moe_dispatch",
                 "vocab_ce", "pp_microbatches", "pp_schedule",
                 "pp_virtual", "dropout_rate"):
        val = getattr(args, name)
        if val is not None:
            model = dataclasses.replace(model, **{name: val})
    if args.width_mult is not None:
        model = dataclasses.replace(model, width_mult=args.width_mult)
    if args.dtype is not None:
        model = dataclasses.replace(model, dtype=args.dtype)
    if args.lr is not None:
        optim = dataclasses.replace(optim, learning_rate=args.lr)
    if args.optimizer is not None:
        optim = dataclasses.replace(optim, name=args.optimizer)
    if args.weight_decay is not None:
        optim = dataclasses.replace(optim, weight_decay=args.weight_decay)
    if args.label_smoothing is not None:
        optim = dataclasses.replace(optim,
                                    label_smoothing=args.label_smoothing)
    if args.eval_batch_size is not None:
        data = dataclasses.replace(data,
                                   eval_batch_size=args.eval_batch_size)
    if args.lr_schedule is not None:
        optim = dataclasses.replace(optim, schedule=args.lr_schedule)
    if args.warmup_epochs is not None:
        optim = dataclasses.replace(optim, warmup_epochs=args.warmup_epochs)
    if args.clip_norm is not None:
        optim = dataclasses.replace(optim, clip_norm=args.clip_norm)
    if args.ema_decay is not None:
        optim = dataclasses.replace(optim, ema_decay=args.ema_decay)
    if args.mesh_data is not None:
        mesh = dataclasses.replace(mesh, data=args.mesh_data)
    if args.mesh_seq is not None:
        mesh = dataclasses.replace(mesh, seq=args.mesh_seq)
    if args.mesh_pipe is not None:
        mesh = dataclasses.replace(mesh, pipe=args.mesh_pipe)
    if args.mesh_model is not None:
        mesh = dataclasses.replace(mesh, model=args.mesh_model)
    if args.checkpoint_dir is not None:
        ckpt = dataclasses.replace(ckpt, directory=args.checkpoint_dir)
    if args.resume:
        ckpt = dataclasses.replace(ckpt, resume=True)
    cfg = cfg.replace(data=data, model=model, optim=optim, mesh=mesh,
                      checkpoint=ckpt, obs=obs)
    if args.epochs is not None:
        cfg = cfg.replace(epochs=args.epochs)
    if args.seed is not None:
        cfg = cfg.replace(seed=args.seed)
    if args.chaos is not None:
        cfg = cfg.replace(chaos=args.chaos)
    if args.preempt_grace_s is not None:
        cfg = cfg.replace(preempt_grace_s=args.preempt_grace_s)
    if args.profile_dir is not None:
        cfg = cfg.replace(profile_dir=args.profile_dir)
    if args.log_every_steps is not None:
        cfg = cfg.replace(log_every_steps=args.log_every_steps)
    if args.eval_only:
        cfg = cfg.replace(eval_only=True)
    return cfg
