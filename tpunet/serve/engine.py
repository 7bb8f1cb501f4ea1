"""Continuous-batching decode engine over a paged KV pool.

One jitted masked decode step is compiled ONCE for the pool batch
``[slots, 1]`` and amortized across every in-flight request: each
iteration feeds every active slot its next token at its own position
(per-row positions + active mask, tpunet/models/vit.py
``Attention._decode_attend``), so requests join mid-flight and finished
ones free their slot without any recompilation. Prefill runs through
the same masked path as a chunked multi-token call, padded to one of a
fixed set of length buckets — the total compile count is bounded at
``1 + len(prefill_buckets)`` programs for the life of the server. On
one device a prefill program is ``[1, bucket]``, one call per admitted
request (a row reaches its tokens only through its row of the page
table); over a mesh it is ``[slots, bucket]``, one call per admission
group.

KV memory is PAGED: per layer, K/V live in a
shared pool of ``kv_pages`` pages of ``kv_page_tokens`` tokens each,
addressed through per-slot page tables the engine owns host-side. A
slot costs HBM proportional to its prompt+generated length, not
``max_seq_len`` — pages are allocated on advance, freed on finish, and
recycled; when the pool is exhausted the YOUNGEST blocked slot is
preempted back to the queue (its progress is kept and resumed by
re-prefilling prompt+generated, token streams never restart). int8
page payloads (``kv_dtype``, per page-row scale, eval-parity-gated)
halve the bf16 page cost again.

Prefix KV cache (``ServeConfig.prefix_cache``, on by default;
tpunet/serve/prefixcache/): finished prefill pages become
immutable, content-addressed, refcounted objects inside the SAME
pool. Admission pins the longest cached page-aligned prefix into the
new slot's page table (zero prefill compute for those tokens),
re-prefills only the suffix, and copy-on-writes at the divergence
page when the full prefix is cached; release unpins, pool pressure
LRU-evicts. With ``--prefix-store`` the pages spill to a shared
filesystem (fsatomic first-writer-wins) and a respawned replica warms
from the fleet's prefix set at boot.

The decode loop keeps ONE STEP IN FLIGHT: it dispatches step N+1
before it reads step N's sampled tokens, and N+1 takes its continuing
rows' tokens from N's output on the device, so the host's work between
two steps (pushing tokens, reaping, admitting, the next arguments)
runs beside the device instead of in front of it. Counts decide the
bookkeeping at dispatch; what only a token's value or the clock
decides (stop token, cancel, deadline) is seen one step late, and that
row of the step ahead is discarded (``Engine._decode_iteration``).

Sampling runs on the DEVICE: one batched temperature/top-k/top-p step
(tpunet/serve/sampling.py, per-slot PRNG keys folded per step) is
fused onto every masked-step program, so only sampled int32 tokens
cross the host boundary. Greedy output is token-identical to
``models.lm.generate`` (engine parity tests); the filters are held to
``models.lm.filter_logits`` (tests/test_serve_paged.py).

Weights are RESIDENT in the type the step uses them in: at
construction the engine derives, from the tree it is given, the tree
its programs read (tpunet/serve/resident.py) — each leaf whose only
use is a narrowing conversion is held converted, so a bfloat16 step
over float32 parameters reads two bytes a product weight, not four,
and rounds nothing per step. The rounding is the step's own, done
once; the caller's tree is left alone and the engine keeps no
reference to it.

Obs wiring: SLO counters/gauges/histograms land in a ``tpunet.obs``
``Registry`` (serve_* names incl. the ``serve_kv_*`` page-pool
gauges, docs/metrics_schema.md ``obs_serve``), everything the engine
thread does runs under a phase (``Engine._phase``: trace spans that
tile the thread, their host seconds summed per engine), and a periodic
``obs_serve`` record is emitted to every attached sink/exporter.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional, Tuple

import numpy as np

from tpunet.obs import flightrec, tracing
from tpunet.obs.spans import PhaseClock, Span, span
from tpunet.serve.scheduler import (FINISH_CANCELLED, FINISH_DEADLINE,
                                    FINISH_DRAIN, FINISH_ERROR,
                                    FINISH_LENGTH, FINISH_STOP,
                                    GenerateRequest, RequestQueue)


class PromptTooLongError(Exception):
    """Prompt exceeds the largest prefill bucket or the KV length."""


# The engine thread's phases (``Engine._phase``): trace spans named
# ``tpunet/serve_<phase>`` that tile everything the thread does, and
# the gauges ``serve_host_s_<phase>`` / ``serve_host_max_s_<phase>``
# an ``obs_serve`` record carries (docs/serving.md "What the engine
# thread was doing").
_PHASE_PREFIX = "tpunet/serve_"
HOST_PHASES = ("admit", "prefill_args", "prefill", "prefix_adopt",
               "publish", "decode_args", "decode", "decode_wait", "idle",
               "spec_prefill", "spec_draft", "spec_verify")


def build_serve_record(reg, *, queue_depth: int, active_slots: int,
                       slots: int, uptime_s: float, window_s: float,
                       final: bool = False) -> dict:
    """The ``obs_serve`` record body (docs/metrics_schema.md):
    cumulative counters + window histogram summaries. Module-level so
    the schema-conformance check can exercise the exact record shape
    without standing up an engine; the TTFT/e2e histograms also export
    their bounded window sample — the fleet aggregator merges replica
    SLO percentiles from sample points, not from per-replica p99s."""
    record = {
        "uptime_s": round(uptime_s, 3),
        "window_s": round(window_s, 3),
        "queue_depth": queue_depth,
        "active_slots": active_slots,
        "slots": slots,
        "requests_total": int(
            reg.counter("serve_requests_total").value),
        "requests_completed": int(
            reg.counter("serve_requests_completed").value),
        "requests_rejected": int(
            reg.counter("serve_requests_rejected").value),
        "tokens_total": int(reg.counter("serve_tokens_total").value),
        "decode_steps_total": int(
            reg.counter("serve_decode_steps_total").value),
        # Steps dispatched while the previous step's tokens were still
        # unread (over decode_steps_total: the share of steps the host
        # stayed off the device's path), and rows computed for a
        # request that a stop token, cancel or deadline had ended.
        "decode_steps_overlapped_total": int(
            reg.counter("serve_decode_steps_overlapped_total").value),
        "decode_rows_discarded_total": int(
            reg.counter("serve_decode_rows_discarded_total").value),
        "prefills_total": int(
            reg.counter("serve_prefills_total").value),
        # Tokens prefill calls embedded for requests / tokens they
        # computed (rows x bucket a call): the calls' useful share.
        "prefill_tokens_total": int(
            reg.counter("serve_prefill_tokens_total").value),
        "prefill_padded_tokens_total": int(
            reg.counter("serve_prefill_padded_tokens_total").value),
    }
    for name, key in (("serve_ttft_s", "ttft"),
                      ("serve_token_s", "token_latency"),
                      ("serve_e2e_s", "e2e"),
                      ("serve_prefill_s", "prefill")):
        hist = reg.histogram(name)
        summ = hist.summary()
        for stat in ("p50", "p90", "p99", "mean", "count"):
            if stat in summ:
                record[f"{key}_{stat}_s" if stat != "count"
                       else f"{key}_count"] = (
                    round(summ[stat], 6) if stat != "count"
                    else int(summ[stat]))
        if key in ("ttft", "e2e") and summ:
            record[f"{key}_sample"] = [
                round(v, 6) for v in hist.export_sample()]
            if summ.get("approx"):
                record[f"{key}_approx"] = 1
    # Paged-KV pool state (serve_kv_* gauges): the capacity signal a
    # fleet operator sizes --kv-pages from.
    for gauge_name, field in (("serve_kv_pages_total", "kv_pages_total"),
                              ("serve_kv_pages_used", "kv_pages_used")):
        val = reg.gauge(gauge_name).value
        record[field] = int(val) if val is not None else 0
    bpt = reg.gauge("serve_kv_bytes_per_token").value
    record["kv_bytes_per_token"] = (round(float(bpt), 2)
                                    if bpt is not None else 0)
    # What the engine was handed and what it holds (tpunet/serve/
    # resident.py): set once, at construction.
    for gauge_name, field in (
            ("serve_weight_bytes_given", "weight_bytes_given"),
            ("serve_weight_bytes_resident", "weight_bytes_resident"),
            ("serve_weight_leaves_precast", "weight_leaves_precast")):
        val = reg.gauge(gauge_name).value
        record[field] = int(val) if val is not None else 0
    # Prefix KV cache (serve_prefix_* instruments; zeros when the
    # cache is off): hit rate is THE steering signal — the router's
    # affinity and the fleet's shared-prefix traffic shape show up
    # here as prefill compute avoided.
    for cname, field in (
            ("serve_prefix_lookups_total", "prefix_lookups_total"),
            ("serve_prefix_hits_total", "prefix_hits_total"),
            ("serve_prefix_hit_tokens_total", "prefix_hit_tokens_total"),
            ("serve_prefix_inserts_total", "prefix_inserts_total"),
            ("serve_prefix_evictions_total", "prefix_evictions_total"),
            ("serve_prefix_cow_total", "prefix_cow_total"),
            ("serve_prefix_spills_total", "prefix_spills_total"),
            ("serve_prefix_warm_loads_total", "prefix_warm_loads_total")):
        record[field] = int(reg.counter(cname).value)
    pages_cached = reg.gauge("serve_prefix_pages_cached").value
    record["prefix_pages_cached"] = (int(pages_cached)
                                     if pages_cached is not None else 0)
    lookups = record["prefix_lookups_total"]
    record["prefix_hit_rate"] = (
        round(record["prefix_hits_total"] / lookups, 4) if lookups
        else 0.0)
    # Speculative decoding (serve_spec_* instruments; zeros with spec
    # off): acceptance rate is THE drafter-quality signal — a drafter
    # that stops matching its serving model shows up here before it
    # shows up as a tokens/s regression.
    for cname, field in (
            ("serve_spec_draft_tokens_total", "spec_draft_tokens_total"),
            ("serve_spec_accepted_tokens_total",
             "spec_accepted_tokens_total"),
            ("serve_spec_rejected_tokens_total",
             "spec_rejected_tokens_total"),
            ("serve_spec_verify_steps_total", "spec_verify_steps_total")):
        record[field] = int(reg.counter(cname).value)
    drafted = record["spec_draft_tokens_total"]
    record["spec_acceptance_rate"] = (
        round(record["spec_accepted_tokens_total"] / drafted, 4)
        if drafted else 0.0)
    verifies = record["spec_verify_steps_total"]
    record["spec_accepted_tokens_per_verify"] = (
        round(record["spec_accepted_tokens_total"] / verifies, 4)
        if verifies else 0.0)
    # The engine thread's seconds by phase (``Engine._phase``: self
    # time, so the phases add up to the thread's time under any of
    # them) and each phase's longest single span since the last
    # record: which phase holds the host, and which one stalled.
    for prefix, field in (("serve_host_s_", "host_s"),
                          ("serve_host_max_s_", "host_max_s")):
        values = ((phase, reg.gauge(prefix + phase).value)
                  for phase in HOST_PHASES)
        record[field] = {phase: round(float(v), 6)
                         for phase, v in values if v is not None}
    if final:
        record["final"] = True
    return record


def build_aot_store(directory: str, model_cfg, serve_cfg):
    """The engine's ``AotProgramStore`` (tpunet/utils/cache.py), keyed
    by every config field that selects a compiled program: the model
    architecture plus the pool shape. A replica booted with a different
    width/depth/slots gets a clean store MISS, never a wrong program
    (the store key additionally folds in jax version + device kind)."""
    import dataclasses

    from tpunet.utils.cache import AotProgramStore

    digest = AotProgramStore.digest({
        "model": dataclasses.asdict(model_cfg),
        "slots": serve_cfg.slots,
        "prefill_buckets": list(serve_cfg.prefill_buckets),
        # The pool's geometry and page dtype each select a DIFFERENT
        # compiled program: fold them in so flipping a flag is a clean
        # miss, never a stale executable.
        "kv_pages": serve_cfg.kv_pages,
        "kv_page_tokens": serve_cfg.kv_page_tokens,
        "kv_dtype": serve_cfg.kv_dtype,
        # What the programs take as ``params``: the resident tree
        # (tpunet/serve/resident.py), not the given one. A store
        # written for the given tree's types misses whole.
        "params": "resident",
        # The step takes a row's token from the previous step's output
        # or from the host (``prev``, ``from_prev``) and names each
        # row's state row (``state_rows``), and a wide call's table
        # has the columns its positions reach: a store written for a
        # twelve- or fourteen-argument step, or for whole tables,
        # misses whole.
        "step": "tokens forwarded, state rows named, wide table cut",
        # Spec-decode levers select a different program SET (drafter
        # width changes the drafter executables, K changes the verify
        # width): spec-on and spec-off engines must never share blobs.
        "spec_decode": getattr(serve_cfg, "spec_decode", False),
        "spec_k": getattr(serve_cfg, "spec_k", 4),
        "spec_draft_width_mult": getattr(
            serve_cfg, "spec_draft_width_mult", 0.5),
    })
    return AotProgramStore(directory, digest)


def _shapes_of(tree):
    """``tree`` with every array replaced by its shape and dtype."""
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _windowed_cache(specs) -> Tuple[int, float]:
    """``(window, share)`` from the mixers' ``cache_spec``s: the longest
    ``window`` any layer states (0: every layer reads its whole row)
    and the share of a token's paged bytes that the windowed layers
    keep."""
    def paged_bytes(spec):
        return sum(width * np.dtype(dtype).itemsize
                   for width, dtype in spec["paged"].values())
    windowed = [s for s in specs if s.get("window")]
    if not windowed:
        return 0, 0.0
    return (max(s["window"] for s in windowed),
            sum(map(paged_bytes, windowed)) / sum(map(paged_bytes, specs)))


class _Slot:
    """Host-side bookkeeping for one KV-cache row."""

    __slots__ = ("req", "pos", "next_token", "generated", "pages",
                 "pinned", "seq")

    def __init__(self, req: GenerateRequest, pos: int, next_token: int,
                 generated: int = 0, seq: int = 0):
        self.req = req
        self.pos = pos            # next cache write position, counted
        #                           at DISPATCH: a step in flight has
        #                           already advanced it
        self.next_token = next_token  # last token READ for this slot
        self.generated = generated  # tokens sampled for the request,
        #                             read or in flight (resume-aware):
        #                             the sampler's next step
        self.pages: List[int] = []  # PRIVATE paged-KV pages (table
        #                             indices from len(pinned) up)
        self.pinned: List = []    # prefix-cache nodes this slot maps
        #                           read-only (table indices 0..k-1)
        self.seq = seq            # admission ordinal (preempt youngest)


class _Step:
    """One dispatched decode step whose sampled tokens the host has
    not read yet."""

    __slots__ = ("sampled", "rows", "t0")

    def __init__(self, sampled, rows, t0: float):
        self.sampled = sampled    # [slots] int32, on the device
        self.rows = rows          # [(slot_i, slot, last)]: ``last`` =
        #                           this token ends the request by its
        #                           length, known at dispatch
        self.t0 = t0              # host clock when the dispatch began


class Engine:
    """Slot-pool continuous-batching engine for one LM.

    ``model``/``variables`` come from ``infer.generate.load_lm`` (pass
    the same ``mesh`` for tensor-parallel serving — the KV pool is then
    created sharded over the mesh 'model' axis to match the attention's
    head-sharded writes). The engine owns a single background thread;
    ``submit`` is thread-safe and non-blocking (bounded queue).

    The thread's loop is reap -> admit (prefill) -> decode, and the
    decode keeps one step in flight (``_decode_iteration``): a step is
    dispatched before the previous one's tokens are read, so a token
    can be "sampled" (counted in ``slot.generated``, its K/V position
    taken) one step before it is "read" (pushed to the request). The
    drain points — preemption, ``drain``/``stop``, the error path, the
    final record, the spec cycle — read the step in flight first
    (``_drain_decode``); a row computed for a request that a stop
    token, cancel or deadline had already ended is discarded there.
    """

    def __init__(self, model, variables, cfg, *, registry=None,
                 mesh=None, aot_store=None, prefix_store=None,
                 drafter_params=None):
        import jax
        import jax.numpy as jnp

        from tpunet.obs.registry import Registry

        self.model = model
        self.variables = variables
        self.cfg = cfg
        self.mesh = mesh
        self.registry = registry if registry is not None else Registry()
        self.max_seq_len = int(model.max_len)
        self.slots = int(cfg.slots)
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {cfg.slots}")
        self.buckets = tuple(sorted(
            b for b in cfg.prefill_buckets if b <= self.max_seq_len))
        if not self.buckets:
            self.buckets = (self.max_seq_len,)
        self.queue = RequestQueue(cfg.queue_max,
                                  on_finish=self._account_finish)
        self._active: List[Optional[_Slot]] = [None] * self.slots

        # -- paged KV geometry (host-owned allocator) ------------------
        from tpunet.models.vit import PagedKV
        self.page_tokens = int(cfg.kv_page_tokens)
        if self.page_tokens < 1:
            raise ValueError(
                f"kv_page_tokens must be >= 1, got {cfg.kv_page_tokens}")
        self.pages_per_slot = -(-self.max_seq_len // self.page_tokens)
        # -- how many table columns a wide call is handed --------------
        # The paged attend of a call wider than one token derives its
        # whole key range from ``page_table.shape[1]`` — the gather,
        # the score matrix, the mask, a latent layer's up-projection
        # and its indexer — so a call that embeds positions
        # start..start+width-1 is handed only the columns those
        # positions can reach (``_reach``), rounded up to one of these
        # doubling buckets: O(reach) keys instead of O(max_seq_len)
        # with NO model change. The columns dropped are the ones the
        # causal mask sets to -inf (exp -> 0: they contribute exactly
        # zero), so outputs do not depend on the bucket; doubling
        # bounds the programs of one token width at
        # log2(pages_per_slot). The target's prefill, the drafter's
        # prefill and the verify / burst programs share the one set.
        buckets, w = [], 4
        while w < self.pages_per_slot:
            buckets.append(w)
            w *= 2
        self._window_buckets = tuple(buckets) + (self.pages_per_slot,)
        # Σ (start + width) and Σ columns × page_tokens over the wide
        # calls of ``_dispatch_step``: ``serve_prefill_key_reach_pct``
        self._prefill_keys = [0, 0]
        self._wide_run: set = set()     # (width, columns) dispatched
        usable = int(cfg.kv_pages) or self.slots * self.pages_per_slot
        if usable < 1:
            raise ValueError(f"kv_pages must be >= 1, got "
                             f"{cfg.kv_pages}")
        self.kv_pages_usable = usable
        # Free list yields ascending page ids (pop from the end);
        # freed pages re-enter at the end, so recycling is LIFO —
        # a just-freed hot page is the next one handed out.
        self._free_pages = list(range(usable, 0, -1))
        self._page_table = np.zeros(
            (self.slots, self.pages_per_slot), np.int32)
        # pages + 1: page 0 is the reserved garbage page (inactive
        # rows and padded prefill tails write there; the allocator
        # never hands it out).
        self._paged_kv = PagedKV(pages=usable + 1,
                                 page_tokens=self.page_tokens,
                                 dtype=cfg.kv_dtype,
                                 mesh_sharded=mesh is not None)
        self._kv_pages_touched: set = set()
        # -- prefix KV cache (tpunet/serve/prefixcache/) ---------------
        # Refcounted content-addressed pages INSIDE the page pool:
        # admission pins the longest cached page-aligned prefix into
        # the new slot's table (zero prefill compute for those pages)
        # and re-prefills only the suffix. Bounded below the pool so
        # paying slots always have headroom; LRU-evicted back to the
        # free list under pool pressure.
        self._prefix = None
        self._prefix_store = None
        # -- fixed state beside the pages (models/hybrid_mixers.py) ----
        # A model whose mixers keep a per-slot state (a recurrence's
        # matrix, a convolution's tail) declares its bytes; the cache
        # tree then holds a state pool of ``slots`` rows beside the
        # page pools, and batch row i of a call names its state row as
        # it names its pages. A trie of KV pages cannot pin a
        # recurrence (an adopted prefix would skip the tokens the state
        # needs) and a rejected draft cannot rewind one: no prefix
        # cache is built, and speculative decoding is refused.
        self._state_bytes_per_slot = int(
            getattr(model, "state_bytes_per_slot", 0))
        # -- pages behind a window (a mixer's ``cache_spec`` "window") -
        # Layers that read only a row's newest ``window`` positions
        # still keep every position in pages (one page table): of the
        # page-bytes the decoding slots hold, the share that lies
        # wholly behind the window is what an allocator by layer kind
        # would free. Counted per dispatched decode step (two integer
        # sums over its rows), published per record.
        self._window_tokens, self._window_bytes_share = _windowed_cache(
            getattr(model, "cache_specs", list)())
        self._window_pages = [0, 0]     # [dead, held], summed over steps
        if self._state_bytes_per_slot and getattr(cfg, "spec_decode",
                                                  False):
            raise ValueError(
                f"{type(model).__name__} keeps a fixed state per slot: a "
                "rejected draft cannot rewind it (spec_decode=True is "
                "refused)")
        if self._state_bytes_per_slot and getattr(cfg, "prefix_cache",
                                                  False):
            import logging
            logging.getLogger(__name__).info(
                "no prefix cache: %s keeps a fixed state per slot, which "
                "cached KV pages cannot restore", type(model).__name__)
        elif getattr(cfg, "prefix_cache", False):
            cap = int(getattr(cfg, "prefix_cache_pages", 0))
            if cap <= 0:
                cap = self.kv_pages_usable // 2
            if cap > 0:
                from tpunet.serve.prefixcache import PrefixCache
                self._prefix = PrefixCache(self.page_tokens, cap,
                                           registry=self.registry)
                self._prefix_store = prefix_store
        # -- speculative decoding (tpunet/serve/spec.py) ---------------
        # A narrow drafter proposes spec_k tokens per active slot
        # against its OWN paged pool, then ONE [slots, K+1]-wide
        # verify over the main pool scores them — up to K+1 verified
        # tokens per slot per cycle. The drafter pool shares THIS
        # page table (identical geometry: same page ids, same
        # page_tokens), so allocate-on-advance, cursor rewind,
        # release, and preemption keep both pools in lockstep with
        # zero extra allocator state. Every emitted token comes from
        # the verify program, so the stream is bitwise identical to
        # spec-off at any acceptance rate.
        self.spec_decode = bool(getattr(cfg, "spec_decode", False))
        self.spec_k = int(getattr(cfg, "spec_k", 4))
        self._drafter_model = None
        self._drafter_params = None
        self._draft_cache = None
        self._drafter_paged_kv = None
        if self.spec_decode:
            if self.spec_k < 1:
                raise ValueError(
                    f"spec_k must be >= 1, got {cfg.spec_k}")
            wm = float(getattr(cfg, "spec_draft_width_mult", 0.5))
            if wm <= 0:
                raise ValueError(
                    "spec_draft_width_mult must be > 0, got "
                    f"{wm}")
            if wm == 1.0:
                # Self-speculation: the drafter IS the serving model
                # (still with its own pool — it runs ahead of the
                # verified cursor). 100% acceptance by construction;
                # useful for parity tests, never a throughput win.
                self._drafter_model = model   # params: the resident
                #                               tree, once it exists
            else:
                if not hasattr(model, "hidden") \
                        or not hasattr(model, "heads"):
                    raise ValueError(
                        "spec_draft_width_mult != 1.0 needs a model "
                        "with width levers (TransformerLM); got "
                        f"{type(model).__name__}")
                heads = int(model.heads)
                dh = max(heads, int(int(model.hidden) * wm)
                         // heads * heads)
                self._drafter_model = model.clone(hidden=dh)
            self._drafter_paged_kv = PagedKV(
                pages=self.kv_pages_usable + 1,
                page_tokens=self.page_tokens, dtype=cfg.kv_dtype,
                mesh_sharded=mesh is not None)
            if drafter_params is not None:
                # In-memory drafter weights (bench_serve --spec fits
                # the drafter to its workload and injects it here).
                self._drafter_params = drafter_params
            elif wm != 1.0:
                import jax as _jax
                from tpunet.models import init_variables
                template = init_variables(
                    self._drafter_model, _jax.random.PRNGKey(0),
                    seq_len=min(16, self.max_seq_len))["params"]
                ckpt = getattr(cfg, "spec_draft_checkpoint", "")
                if ckpt:
                    from tpunet.serve import spec as serve_spec
                    self._drafter_params = \
                        serve_spec.load_drafter_params(ckpt, template)
                else:
                    # Deterministic random init: correct (acceptance
                    # just tends to zero) but pointless for
                    # throughput — fit a drafter for real traffic.
                    self._drafter_params = template
        self._page_ops = None        # (read, write, copy) jitted lazily
        self._admit_seq = 0
        self.peak_active_slots = 0   # high-water mark (bench_serve
        #                              --slots-sweep admitted-slot count)
        # Serve-tier fault injector (--chaos, tpunet/serve/chaos.py):
        # the engine fires token/prefill/stall hooks, the HTTP
        # frontend the probe/stream ones. None when unarmed.
        from tpunet.serve import chaos as serve_chaos
        self.chaos = serve_chaos.install(getattr(cfg, "chaos", ""))
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._drain_kill = threading.Event()
        self._drained = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._thread_handle = None       # flightrec registry handle
        self.error: Optional[str] = None
        self._last_emit = time.perf_counter()
        self._started = time.perf_counter()
        self._host_clock = PhaseClock()  # seconds by phase (_phase)

        # -- device programs (compiled lazily, one per shape) ----------
        # One callable; jit specializes per token shape: [N, 1] decode
        # plus one program per prefill bucket Lb — [1, Lb] on one
        # device, [N, Lb] over a mesh (``_prefill_rows``) — over the
        # table columns a fresh admission reaches (``_programs``: a
        # continued row may add a wider table's). The cache is
        # donated — it is the engine's single biggest buffer and every
        # call replaces it. The page-table rows of the call's slots
        # ride along as one small int32 input, and the batched sampler
        # is FUSED onto the step: the program returns sampled int32
        # tokens, not logits.
        #
        # A row reaches its tokens only through its row of the page
        # table and its fixed state (if the model keeps one) through
        # ``state_rows``, so nothing ties a batch row to a slot: a
        # prefill call is as wide as the one request it admits. A call
        # with a row per slot IS the pool in slot order (row i = slot
        # i), and says so to the model by naming no rows: the state
        # pool is then updated where it lies. Over a mesh GSPMD
        # partitions the pool, and the [slots, bucket] group call is
        # the only path that platform has (no cell measures it).
        self._prefill_rows = 1 if mesh is None else self.slots
        paged_kv = self._paged_kv

        fixed_state, slots = bool(self._state_bytes_per_slot), self.slots

        def _masked_step(params, cache, tokens, positions, active,
                         page_table, last_idx, temp, top_k, top_p,
                         seeds, steps, prev, from_prev, state_rows):
            # A row that continues takes the token the previous decode
            # step sampled for it straight from that step's output,
            # which the host may not have read yet; ``tokens`` carries
            # what only the host knows (a prompt, a slot just
            # prefilled or resumed).
            tokens = jnp.where(from_prev[:, None], prev[:, None], tokens)
            state = {}
            if fixed_state:
                # positions past a row's last real token change no state
                state = {"lengths": last_idx + 1, "state_rows": (
                    None if tokens.shape[0] == slots else state_rows)}
            logits, mutated = model.apply(
                {"params": params, "cache": cache}, tokens, train=False,
                decode=True, pos_offset=positions, decode_active=active,
                paged_kv=paged_kv, page_table=page_table,
                mutable=["cache"], **state)
            from tpunet.serve.sampling import batched_sample
            rows = jnp.take_along_axis(
                logits, last_idx[:, None, None],
                axis=1)[:, 0].astype(jnp.float32)
            toks = batched_sample(rows, temp, top_k, top_p, seeds,
                                  steps)
            return mutated["cache"], toks

        self._step = jax.jit(_masked_step, donate_argnums=(1,))
        self._cache = self._make_cache()
        # Every program is traced for the RESIDENT tree: each weight
        # held in the type the step converts it to (tpunet/serve/
        # resident.py), so no step reads a weight at full width to
        # round it again. The engine keeps no reference to the tree it
        # was given; the caller's arrays are the caller's.
        params, precast = self._resident(model, variables["params"],
                                         self._cache, paged_kv)
        self.variables = {**variables, "params": params}
        from tpunet.serve.resident import tree_bytes
        for name, value in (
                ("serve_weight_bytes_given",
                 tree_bytes(variables["params"])),
                ("serve_weight_bytes_resident", tree_bytes(params)),
                ("serve_weight_leaves_precast", precast)):
            self.registry.gauge(name).set(value)
        self._inactive_tok = np.zeros((self.slots, 1), np.int32)
        self._zero_idx = np.zeros((self.slots,), np.int32)
        # -- one decode step in flight (docs/serving.md) ---------------
        # The plain decode loop dispatches step N+1 before it reads
        # step N's tokens. ``_in_flight`` is the step dispatched and
        # not yet read; ``_sampled`` the newest decode step's [slots]
        # output on the device, which the next step takes its
        # continuing rows' tokens from (a device array from the first
        # call on, so the program is compiled for one kind of input).
        self._in_flight: Optional[_Step] = None
        self._last_read_t = 0.0
        replicated = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            replicated = NamedSharding(mesh, P())
        self._sampled = jnp.zeros((self.slots,), jnp.int32,
                                  device=replicated)
        if self._drafter_model is not None:
            self._draft_cache = self._make_cache(
                model=self._drafter_model,
                paged_kv=self._drafter_paged_kv)
            if self._drafter_params is None:    # self-speculation
                self._drafter_params = params
            else:
                self._drafter_params, _ = self._resident(
                    self._drafter_model, self._drafter_params,
                    self._draft_cache, self._drafter_paged_kv)
            self._build_spec_programs()
        self._init_kv_gauges()
        # AOT warm-start (tpunet/utils/cache.py AotProgramStore): the
        # engine's program set is closed — [N, 1] decode + one program
        # per bucket (``_programs``) — so fully-compiled executables
        # deserialize at boot and the jit path above becomes the
        # fallback for shapes the store has never seen. Single-device
        # only: a sharded pool would bake device assignments into the
        # executable.
        self._aot: dict = {}            # (width, table columns) -> program
        self.aot_status: dict = {}
        if aot_store is not None and mesh is None:
            self._warm_start_aot(aot_store)
        # Prefix warm-start AFTER the pool exists and BEFORE the
        # engine thread runs: a respawned/scaled-up replica adopts the
        # fleet's spilled prefix set instead of cold KV, so its very
        # first shared-prefix request prefills only the suffix.
        if self._prefix is not None and self._prefix_store is not None:
            self._warm_start_prefix()
        # Lazy: nothing is lowered or printed until a reader of the
        # device trace asks for the programs' texts.
        from tpunet.obs import device_time
        device_time.register_programs(self.program_texts)

    def _resident(self, model, params, cache, paged_kv):
        """``(tree, leaves precast)``: ``params`` as the engine holds
        them (tpunet/serve/resident.py), judged on ``model``'s own
        apply over ``cache`` at the decode width and at the widest
        bucket — the two paths a model takes (one token against the
        pool; a chunk of them), each traced once, nothing run."""
        from tpunet.serve.resident import resident_params

        def apply(params, cache, tokens, positions, active, page_table):
            return model.apply(
                {"params": params, "cache": cache}, tokens, train=False,
                decode=True, pos_offset=positions, decode_active=active,
                paged_kv=paged_kv, page_table=page_table,
                mutable=["cache"])

        cache_s = _shapes_of(cache)
        return resident_params(
            apply, params,
            [(cache_s, *self._step_avals(width)[2:6])
             for width in (1, self.buckets[-1])])

    def _window(self, need: int) -> int:
        """Smallest window bucket covering ``need`` page-table columns
        (the whole row where none does)."""
        return next((w for w in self._window_buckets if w >= need),
                    self.pages_per_slot)

    def _reach(self, width: int, start: int = 0) -> int:
        """Page-table columns handed to a call that embeds positions
        ``start .. start + width - 1``: nothing past its last position
        can be attended to or written. ``start`` 0 is a fresh
        admission's. The width-1 step keeps the whole table
        (``tpunet_paged_decode`` walks each row to its live length)."""
        if width == 1:
            return self.pages_per_slot
        return self._window(-(-(start + width) // self.page_tokens))

    def _rows_at(self, width: int) -> int:
        """Batch rows of the masked step the engine dispatches at token
        width ``width``: every slot for the width-1 decode program (a
        width-1 bucket prefills through it), ``_prefill_rows`` for a
        bucket-wide one."""
        return self.slots if width == 1 else self._prefill_rows

    def _step_avals(self, width: int, reach: Optional[int] = None) -> list:
        """``_masked_step``'s fifteen arguments at token width
        ``width``, as shapes: the one statement of its signature. The
        table has ``reach`` columns, a fresh admission's
        (``_reach(width)``) unless given."""
        import jax

        n = self._rows_at(width)
        columns = reach or self._reach(width)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
        f32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32)  # noqa: E731
        return [_shapes_of(self.variables["params"]),
                _shapes_of(self._cache),
                i32(n, width),                          # tokens
                i32(n),                                 # positions
                jax.ShapeDtypeStruct((n,), bool),       # active
                i32(n, columns),                        # page_table
                i32(n),                                 # last_idx
                f32(n), i32(n), f32(n),                 # temp, top_k, top_p
                i32(n), i32(n),                         # seeds, steps
                i32(n),                                 # prev
                jax.ShapeDtypeStruct((n,), bool),       # from_prev
                i32(n)]                                 # state_rows

    def _programs(self) -> list:
        """``(width, table columns)`` of every masked-step program the
        engine holds: the width-1 step over the whole table, a fresh
        admission of each bucket (``_reach(bucket)``), and every other
        reach a continued row — a prefix hit, a resume — has been
        dispatched with (at most log2(``pages_per_slot``) a bucket,
        each compiled at its first use)."""
        fresh = {(width, self._reach(width))
                 for width in (1,) + self.buckets}
        return sorted(fresh | self._wide_run)

    def program_texts(self) -> dict:
        """``{label: optimized HLO text}`` of the masked step, one per
        program of ``_programs()``: ``.../w<width>`` for the width-1
        step and for a fresh admission of each bucket,
        ``.../k<columns>/w<width>`` for a continued row's other
        reaches, so a label's tail always names the token width. The
        AOT executable's own text where one was warm-started, else the
        jit program lowered for the live parameters and pool (a mesh
        engine's carry their shardings) and the host inputs' shapes —
        the signature the running program was compiled for, so a
        program that has run compiles nothing. Registered with
        tpunet/obs/device_time.py at construction and called only by a
        reader of the device trace, never by the engine."""
        texts = {}
        for width, columns in self._programs():
            program = self._aot.get((width, columns))
            if program is None:
                program = self._step.lower(
                    self.variables["params"], self._cache,
                    *self._step_avals(width, columns)[2:]).compile()
            reach = ("" if columns == self._reach(width)
                     else f"/k{columns}")
            texts[f"jit_{self._step.__name__}{reach}/w{width}"] = \
                program.as_text()
        return texts

    def _warm_start_aot(self, store) -> None:
        """Load (or compile-and-save) every program the pool can run
        on fresh admissions (``_programs()`` before any call): the
        tag carries the table's columns beside the token width.
        Deserialization skips tracing/lowering/XLA entirely — the
        compile-bound replica cold-start becomes an mmap + relink."""
        import jax
        for width, columns in self._programs():
            tag = f"w{width}" if width == 1 else f"k{columns}w{width}"
            program = store.load("masked_step", tag)
            if program is None:
                # Compile fresh (persistent compile cache off): a
                # cache-served executable saves a poison blob that
                # fails to deserialize at the next boot.
                from tpunet.utils.cache import serializable_compile
                with serializable_compile():
                    program = self._step.lower(
                        *self._step_avals(width, columns)).compile()
                saved = store.save("masked_step", tag, program)
                self.aot_status[tag] = ("compiled+saved" if saved
                                        else "compiled")
            else:
                self.aot_status[tag] = "loaded"
            self._aot[(width, columns)] = program
        if self._drafter_model is None:
            return
        # Spec programs are part of the replica's closed program set
        # too: drafter prefill per bucket, the K+1 draft burst, and
        # the [slots, K+1] verify — a spec-on replica cold-starts
        # without tracing just like a spec-off one. The store digest
        # folds the spec levers, so spec-on/off never share blobs.
        avals = self._step_avals(1)
        params_s, cache_s, pos_s, act_s = (avals[0], avals[1], avals[3],
                                           avals[4])
        samp_s = tuple(avals[7:])    # temp, top_k, top_p, seeds, steps
        dparams_s = _shapes_of(self._drafter_params)
        dcache_s = _shapes_of(self._draft_cache)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
        k = self.spec_k
        programs = []
        # Burst/verify are compiled per attention-window bucket (the
        # engine slices the page table to the live window at call
        # time); the full closed set is log2(pages_per_slot) pairs.
        for win in self._window_buckets:
            win_s = i32(self.slots, win)
            programs.append(
                ("spec_draft_burst", f"k{k}w{win}",
                 self._draft_burst_fn,
                 (dparams_s, dcache_s, i32(self.slots), pos_s, act_s,
                  win_s) + samp_s))
            programs.append(
                ("spec_verify", f"k{k}w{win}", self._verify_fn,
                 (params_s, cache_s, i32(self.slots, k + 1), pos_s,
                  act_s, win_s) + samp_s))
        for width in self.buckets:
            win = self._reach(width)
            programs.append(
                ("spec_draft_prefill", f"w{width}",
                 self._draft_prefill_fn,
                 (dparams_s, dcache_s, i32(self.slots, width), pos_s,
                  act_s, i32(self.slots, win))))
        from tpunet.utils.cache import serializable_compile
        for name, tag, fn, shapes in programs:
            program = store.load(name, tag)
            if program is None:
                with serializable_compile():
                    program = fn.lower(*shapes).compile()
                saved = store.save(name, tag, program)
                self.aot_status[f"{name}-{tag}"] = (
                    "compiled+saved" if saved else "compiled")
            else:
                self.aot_status[f"{name}-{tag}"] = "loaded"
            self._spec_aot[(name, tag)] = program

    def _dispatch_step(self, toks, positions, active, last_idx,
                       slot_i=None, from_prev=None):
        """Dispatch one masked-step program: the AOT executable for
        this token width and table width when warm-started, the jit
        fallback otherwise. Batch row i is slot i, or — ``slot_i``
        given — the call's one row is that slot (a [1, bucket]
        prefill). A call wider than one token is handed the table
        columns its positions can reach (``_reach``), not the row's
        ``pages_per_slot``. Rows set in ``from_prev`` (decode only)
        take their token from the newest decode step's output on the
        device, not from ``toks``. Returns (cache, sampled tokens)
        without waiting for either.

        The call may still be in flight when the host next touches its
        own state, so every numpy argument is a buffer of the call's
        own: the page table is copied (the allocator rewrites
        ``_page_table`` in place, and the CPU backend may alias host
        memory), the rest are built per call."""
        rows, width = toks.shape
        table = (self._page_table if slot_i is None
                 else self._page_table[slot_i:slot_i + 1])
        if width > 1:
            # the columns the call's positions can reach (idle rows
            # stand at position 0); the width-1 step keeps the table
            start = int(np.max(positions))
            columns = self._reach(width, start)
            table = table[:, :columns]
            self._wide_run.add((width, columns))
            keys = columns * self.page_tokens
            self._prefill_keys[0] += min(start + width, keys)
            self._prefill_keys[1] += keys
        program = self._aot.get((width, table.shape[1]), self._step)
        if from_prev is None:
            prev = np.zeros((rows,), np.int32)
            from_prev = np.zeros((rows,), bool)
        else:
            prev = self._sampled
        state_rows = (np.arange(rows, dtype=np.int32) if slot_i is None
                      else np.full((1,), slot_i, np.int32))
        return program(
            self.variables["params"], self._cache, toks, positions,
            active, table.copy(), *self._sampling_args(last_idx, slot_i),
            prev, from_prev, state_rows)

    def _sampling_args(self, last_idx, slot_i=None):
        """Per-row sampling parameters for the fused device sampler:
        temperature/top-k/top-p/seed from each resident request, plus
        each slot's sampled-token count (the per-step key fold — a
        preempted-and-resumed request continues its exact sample
        stream). The count is the SLOT's, not ``len(req.tokens)``: a
        token in flight is sampled and not yet pushed. One row per
        slot, or the one row of ``slot_i``."""
        slots = (self._active if slot_i is None
                 else self._active[slot_i:slot_i + 1])
        n = len(slots)
        temp = np.zeros(n, np.float32)
        top_k = np.zeros(n, np.int32)
        top_p = np.zeros(n, np.float32)
        seeds = np.zeros(n, np.int32)
        steps = np.zeros(n, np.int32)
        for i, slot in enumerate(slots):
            if slot is None:
                continue
            r = slot.req
            temp[i] = r.temperature
            top_k[i] = r.top_k
            top_p[i] = r.top_p
            seeds[i] = r.seed    # admission-validated into [0, 2**31)
            steps[i] = slot.generated
        return [np.asarray(last_idx, np.int32), temp, top_k, top_p,
                seeds, steps]

    # -- speculative-decoding programs (docs/serving.md) ----------------

    def _build_spec_programs(self) -> None:
        """Three jitted spec programs, all [slots]-wide and masked
        like the main step (one compile each, AOT-serializable):

        - drafter prefill: write-only full-prompt pass filling the
          drafter pool (per prefill bucket).
        - draft burst: K+1 fused drafter steps. Iteration j consumes
          token t_j at position pos+j, writes drafter K/V there, and
          samples d_{j+1} with the SAME (seed, step=s0+j) key the
          verifier will use — lockstep keys are what make a perfect
          drafter accept at temperature > 0 too. The K+1'th draft is
          discarded, but its K/V write keeps the drafter pool gapless
          after a full acceptance (both cursors then cover pos+K).
        - verify: ONE [slots, K+1] forward over the main pool scoring
          [next_token, d_1..d_K] at positions pos..pos+K, sampling
          choice c_j per position with step s0+j.
        """
        import jax
        import jax.numpy as jnp

        from tpunet.serve.sampling import (batched_sample,
                                           batched_sample_positions)

        dmodel = self._drafter_model
        dpaged = self._drafter_paged_kv
        model = self.model
        paged = self._paged_kv
        k = self.spec_k

        def _draft_prefill(params, cache, tokens, positions, active,
                           page_table):
            _, mutated = dmodel.apply(
                {"params": params, "cache": cache}, tokens,
                train=False, decode=True, pos_offset=positions,
                decode_active=active, paged_kv=dpaged,
                page_table=page_table, mutable=["cache"])
            return mutated["cache"]

        def _draft_burst(params, cache, first_tok, positions, active,
                         page_table, temp, top_k, top_p, seeds,
                         steps0):
            def body(carry, j):
                cache, tok = carry
                logits, mutated = dmodel.apply(
                    {"params": params, "cache": cache}, tok[:, None],
                    train=False, decode=True, pos_offset=positions + j,
                    decode_active=active, paged_kv=dpaged,
                    page_table=page_table, mutable=["cache"])
                nxt = batched_sample(
                    logits[:, 0].astype(jnp.float32), temp, top_k,
                    top_p, seeds, steps0 + j)
                return (mutated["cache"], nxt), nxt
            (cache, _), drafts = jax.lax.scan(
                body, (cache, first_tok),
                jnp.arange(k + 1, dtype=jnp.int32))
            # drafts is [K+1, B] = d_1..d_{K+1}; d_{K+1} lies beyond
            # the verify window and is dropped.
            return cache, drafts[:k].T

        def _verify(params, cache, tokens, positions, active,
                    page_table, temp, top_k, top_p, seeds, steps0):
            logits, mutated = model.apply(
                {"params": params, "cache": cache}, tokens,
                train=False, decode=True, pos_offset=positions,
                decode_active=active, paged_kv=paged,
                page_table=page_table, mutable=["cache"])
            choices = batched_sample_positions(
                logits.astype(jnp.float32), temp, top_k, top_p,
                seeds, steps0)
            return mutated["cache"], choices

        self._draft_prefill_fn = jax.jit(_draft_prefill,
                                         donate_argnums=(1,))
        self._draft_burst_fn = jax.jit(_draft_burst,
                                       donate_argnums=(1,))
        self._verify_fn = jax.jit(_verify, donate_argnums=(1,))
        self._spec_aot: dict = {}

    def _dispatch_spec(self, name: str, tag: str, fallback, args):
        """Run one spec program: the AOT executable when warm-started,
        the jit fallback otherwise (mirrors ``_dispatch_step``)."""
        program = self._spec_aot.get((name, tag))
        if program is None:
            program = fallback
        return program(*args)

    def drafter_pool_bytes(self) -> int:
        """Resident bytes of the drafter's KV pool (0 with spec off) —
        reported separately from ``kv_pool_bytes`` because the drafter
        pool is the spec lever's EXTRA memory cost (width 0.5 ≈ +50%
        KV bytes), and the bench must account for it honestly."""
        from tpunet.serve.resident import tree_bytes
        return 0 if self._draft_cache is None \
            else tree_bytes(self._draft_cache)

    # -- pool construction ---------------------------------------------

    def _make_cache(self, model=None, paged_kv=None):
        import jax
        import jax.numpy as jnp
        model = model if model is not None else self.model
        if paged_kv is None:
            paged_kv = self._paged_kv
        shapes = jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((self.slots, self.max_seq_len), jnp.int32),
                decode=True, paged_kv=paged_kv,
                page_table=jnp.zeros((self.slots, self.pages_per_slot),
                                     jnp.int32)))

        def zeros(s):
            if self.mesh is not None:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                tp = self.mesh.shape.get("model", 1)
                heads = int(getattr(model, "heads", 0))
                if s.ndim == 2 and tp > 1 and heads % tp == 0 \
                        and s.shape[1] == getattr(model, "hidden", 0):
                    # page pool [rows, H * D]: whole heads per device
                    # (a lane-padded row has no head-aligned split)
                    spec = P(None, "model")
                else:
                    spec = P()
                return jnp.zeros(s.shape, s.dtype,
                                 device=NamedSharding(self.mesh, spec))
            return jnp.zeros(s.shape, s.dtype)

        return jax.tree_util.tree_map(zeros, shapes["cache"])

    def kv_pool_bytes(self) -> int:
        """Resident bytes of the KV cache tree (the page pool and its
        scale sidecars) — the capacity number ``bench_serve.py``
        reports per slot. The state pool is not in it
        (``state_pool_bytes``)."""
        from tpunet.serve.resident import tree_bytes
        return tree_bytes(self._cache) - self.state_pool_bytes()

    def state_pool_bytes(self) -> int:
        """Resident bytes of the per-slot state pool: ``slots`` rows of
        what the model's mixers declare (0 for a model whose every
        cache is paged)."""
        return self._state_bytes_per_slot * self.slots

    def kv_bytes_per_token(self) -> float:
        """KV bytes pinned per cacheable token position across the
        whole pool (pages incl. scale sidecars)."""
        return self.kv_pool_bytes() / (self._paged_kv.pages
                                       * self.page_tokens)

    def _init_kv_gauges(self) -> None:
        reg = self.registry
        reg.gauge("serve_kv_bytes_per_token").set(
            round(self.kv_bytes_per_token(), 2))
        reg.gauge("serve_kv_pages_total").set(self.kv_pages_usable)
        reg.gauge("serve_kv_pages_used").set(0)
        # Which attend path the [slots, 1] decode program was built
        # with (tpunet/ops/paged_decode.py): 1 = the in-place
        # kernel, 0 = gather + dense. Static: set here, once.
        import jax
        from tpunet.ops import paged_decode
        pool = jax.tree_util.tree_leaves(self._cache)[0]
        reg.gauge("serve_decode_attend_kernel").set(int(
            paged_decode.kernel_applies(self._paged_kv, 1, pool.dtype)))
        # Rows of a bucket-wide prefill program (1 = a call per admitted
        # request, ``slots`` = one call per admission group). Static.
        reg.gauge("serve_prefill_rows_per_call").set(self._prefill_rows)
        if self._prefix is not None:
            reg.gauge("serve_prefix_pages_cached").set(0)
        if self._state_bytes_per_slot:
            reg.gauge("serve_state_pool_bytes").set(self.state_pool_bytes())
            reg.gauge("serve_prefix_cache_enabled").set(0)
        # What a model says of itself once (models/latent_lm.py: bytes
        # a token keeps per cache kind, experts held of the router's
        # width); a model without the method adds nothing.
        for name, value in getattr(self.model, "serve_gauges",
                                   dict)().items():
            reg.gauge(name).set(value)

    def _update_kv_gauges(self) -> None:
        self.registry.gauge("serve_kv_pages_used").set(
            self.kv_pages_usable - len(self._free_pages))

    # -- paged-KV page allocator (engine thread only) -------------------

    def _alloc_pages_for(self, slot_i: int, n_tokens: int,
                         first_index: int = 0):
        """Allocate pages covering ``n_tokens`` prefill positions for
        an admission, starting at page-table index ``first_index``
        (indices below it are prefix-cache pins); None when the pool
        cannot cover it right now (the request stays queued).
        All-or-nothing. Under pressure, unpinned prefix-cache pages
        are LRU-evicted back to the free list first — cached pages
        never starve a paying admission."""
        need = -(-n_tokens // self.page_tokens) - first_index
        while len(self._free_pages) < need:
            if not self._evict_prefix_page():
                return None
        pages = [self._free_pages.pop() for _ in range(need)]
        for j, p in enumerate(pages):
            self._page_table[slot_i, first_index + j] = p
        self._kv_pages_touched.update(pages)
        self.registry.counter("serve_kv_page_allocs_total").inc(need)
        return pages

    def _ensure_page_capacity(self, slot_i: int, slot: _Slot,
                              through_pos: int = -1) -> bool:
        """Allocate-on-advance: make sure the page covering the slot's
        next write position exists (pinned prefix pages count toward
        coverage; new pages are always PRIVATE — decode never writes a
        shared page). ``through_pos`` extends coverage to a LATER
        position (a spec burst writes pos..pos+K in one cycle; the
        over-allocation is what the rejection rewind recycles). False
        = pool exhausted even after evicting every evictable prefix
        page (the slot sits this iteration out, or gets preempted)."""
        need = max(slot.pos, through_pos) // self.page_tokens + 1
        while len(slot.pinned) + len(slot.pages) < need:
            if not self._free_pages and not self._evict_prefix_page():
                return False
            p = self._free_pages.pop()
            self._page_table[slot_i,
                             len(slot.pinned) + len(slot.pages)] = p
            slot.pages.append(p)
            self._kv_pages_touched.add(p)
            self.registry.counter("serve_kv_page_allocs_total").inc()
        return True

    def _release_pages(self, slot_i: int, slot: _Slot) -> None:
        """Free-on-finish with recycling: the slot's PRIVATE pages
        re-enter the free list (LIFO), its prefix pins drop their
        refcount (the pages stay cached — eviction, not release,
        returns them to the pool), and its table row resets to the
        garbage page."""
        self._free_pages.extend(slot.pages)
        slot.pages = []
        if slot.pinned:
            self._prefix.unpin(slot.pinned)
            slot.pinned = []
        self._page_table[slot_i, :] = 0
        self._update_kv_gauges()

    def _evict_prefix_page(self) -> bool:
        """Pool-pressure relief valve: LRU-evict one unpinned prefix
        page back to the free list. False when the cache is off or
        everything cached is pinned by a live slot (then the normal
        preempt/completability logic takes over — pins are released by
        finish AND by preemption, so cached pages can never deadlock a
        request the completability guard admitted)."""
        if self._prefix is None:
            return False
        page = self._prefix.evict_one()
        if page is None:
            return False
        self._free_pages.append(page)
        return True

    # -- prefix-cache page ops (engine thread / init only) --------------

    def _build_page_ops(self):
        """Three tiny jitted programs over the whole paged cache tree
        (every leaf is flat-row-indexed ``[pages * page_tokens, ...]``
        — K/V pages and their scale sidecars alike): read one page's
        rows to a host-transferable tree, scatter rows into a page,
        and device-copy page -> page (the COW divergence copy). Page
        indices are traced scalars, so ONE compiled program covers
        every page."""
        import jax
        from jax import lax
        pt = self.page_tokens

        def read(cache, page):
            start = page * pt
            return jax.tree_util.tree_map(
                lambda leaf: lax.dynamic_slice_in_dim(
                    leaf, start, pt, axis=0), cache)

        def write(cache, rows, page):
            start = page * pt
            return jax.tree_util.tree_map(
                lambda leaf, r: lax.dynamic_update_slice_in_dim(
                    leaf, r.astype(leaf.dtype), start, axis=0),
                cache, rows)

        def copy(cache, src, dst):
            return write(cache, read(cache, src), dst)

        return (jax.jit(read),
                jax.jit(write, donate_argnums=(0,)),
                jax.jit(copy, donate_argnums=(0,)))

    def _page_ops_lazy(self):
        if self._page_ops is None:
            self._page_ops = self._build_page_ops()
        return self._page_ops

    def _copy_page(self, src: int, dst: int) -> None:
        """Device-copy one pool page (COW at the divergence page: the
        fresh private copy takes the suffix write, the shared source
        stays immutable)."""
        _, _, copy = self._page_ops_lazy()
        self._cache = copy(self._cache, np.int32(src), np.int32(dst))

    def _read_page_rows(self, page: int) -> list:
        """One page's rows as host numpy leaves in flatten order (the
        spill payload; the store digest guarantees the reader's tree
        matches)."""
        import jax
        read, _, _ = self._page_ops_lazy()
        rows = read(self._cache, np.int32(page))
        return [np.asarray(leaf) for leaf in
                jax.tree_util.tree_leaves(jax.device_get(rows))]

    def _spill_prefix_page(self, node, parent_digest: str) -> None:
        """Write-through one freshly-inserted prefix page to the
        shared store (fsatomic first-writer-wins: N replicas spilling
        the fleet-common system prefix commit it once). Best-effort —
        a read-only disk degrades to a per-replica cache."""
        if self._prefix_store is None \
                or self._prefix_store.exists(node.digest):
            return
        rows = self._read_page_rows(node.page)
        if self._prefix_store.save(node.digest, parent_digest,
                                   node.depth, rows):
            self.registry.counter("serve_prefix_spills_total").inc()

    def _warm_start_prefix(self) -> None:
        """Adopt the fleet's spilled prefix set into this replica's
        pool at boot (depth order: a page is adopted only under its
        already-adopted parent, so a capacity- or pool-truncated load
        still leaves a prefix-closed trie). Bounded by the cache
        capacity AND the free list — warm pages are all evictable, so
        they can never crowd out the first real admission."""
        import jax
        from tpunet.serve.prefixcache import keys as pk
        leaves, treedef = jax.tree_util.tree_flatten(self._cache)
        _, write, _ = self._page_ops_lazy()
        loaded = 0
        for entry in self._prefix_store.load_all(
                limit=self._prefix.capacity):
            digest = entry.get("digest", "")
            depth = int(entry.get("depth", 0))
            rows = entry.get("rows")
            if not digest or self._prefix.get(digest) is not None:
                continue
            parent = None
            if depth > 0:
                parent = self._prefix.get(entry.get("parent", pk.ROOT))
                if parent is None or parent.depth != depth - 1:
                    continue      # orphan: its parent didn't make it
            if not isinstance(rows, list) or len(rows) != len(leaves) \
                    or any(r.shape != (self.page_tokens,) + tuple(
                        leaf.shape[1:])
                        for r, leaf in zip(rows, leaves)):
                continue          # foreign/torn entry: skip, not crash
            if self._prefix.pages_cached >= self._prefix.capacity \
                    or not self._free_pages:
                break
            page = self._free_pages.pop()
            self._kv_pages_touched.add(page)
            rows_tree = jax.tree_util.tree_unflatten(treedef, rows)
            self._cache = write(self._cache, rows_tree, np.int32(page))
            self._prefix.insert(digest, parent, depth, page)
            loaded += 1
        if loaded:
            self.registry.counter(
                "serve_prefix_warm_loads_total").inc(loaded)
            self._update_kv_gauges()

    def _adopt_prefix_pages(self, slot_i: int, slot: _Slot,
                            resume: np.ndarray) -> None:
        """Post-prefill insert: every full page covered by the
        request's PROMPT (never decode-generated tokens — those are
        request-specific) becomes a cached, refcounted node. A
        concurrent duplicate (two same-prefix admissions in one batch
        both missed lookup) dedups here: the private page goes back to
        the free list and the slot repoints at the cached twin — the
        contents are bitwise-identical, both produced by the same
        deterministic prefill program. Capacity holds via LRU
        eviction; when nothing is evictable the page simply stays
        private."""
        from tpunet.serve.prefixcache import keys as pk
        pt = self.page_tokens
        full = int(slot.req.prompt.size) // pt
        prev = slot.pinned[-1] if slot.pinned else None
        first = len(slot.pinned)
        for j, digest in enumerate(
                pk.iter_chain_digests(resume, pt, full, first), first):
            node = self._prefix.get(digest)
            if node is not None:
                # Duplicate: recycle our private page, share theirs.
                self._free_pages.append(slot.pages.pop(0))
                self._page_table[slot_i, j] = node.page
            else:
                while self._prefix.pages_cached >= self._prefix.capacity:
                    if not self._evict_prefix_page():
                        return     # full of pinned pages: stay private
                node = self._prefix.insert(
                    digest, prev, j, slot.pages.pop(0))
                self._spill_prefix_page(
                    node, prev.digest if prev is not None else pk.ROOT)
            self._prefix.pin([node])
            slot.pinned.append(node)
            prev = node

    def _choose_preempt_victim(self, blocked) -> int:
        """Pick the slot index to preempt from ``blocked``
        [(slot_i, slot), ...]: the YOUNGEST admission whose resume
        prefill (prompt + generated) still fits a bucket. Preempting
        an unresumable slot turns transient pool pressure into a
        client-visible error, so one is chosen only when every
        blocked slot is unresumable (then the youngest fails —
        unavoidable, but never a healthy request while a resumable
        victim exists). Oldest-resumable-survives keeps forward
        progress: the surviving residents eventually finish and free
        pages."""
        largest = self.buckets[-1]
        resumable = [it for it in blocked
                     if it[1].req.prompt.size
                     + len(it[1].req.tokens) <= largest]
        pool = resumable if resumable else blocked
        return max(pool, key=lambda it: it[1].seq)[0]

    def _preempt_slot(self, slot_i: int) -> None:
        """Pool exhausted and nothing can advance: push the youngest
        blocked request back to the HEAD of the queue with its
        progress intact (tokens already streamed stay valid; on
        re-admission the engine re-prefills prompt+generated and the
        sample stream continues at its per-step key fold). Called
        with no decode step in flight: ``req.tokens`` is everything
        the device has sampled."""
        slot = self._active[slot_i]
        self._active[slot_i] = None
        self._release_pages(slot_i, slot)
        req = slot.req
        req.preemptions += 1
        req._preempt_t = time.perf_counter()
        self.registry.counter("serve_kv_preemptions_total").inc()
        flightrec.record("req", f"preempt {req.id}")
        if req.trace_id:
            tracing.crumb("preempt", req.trace_id, req.trace_hop,
                          rid=req.id)
        self.queue.requeue_front([req])
        self.registry.gauge("serve_active_slots").set(
            self.active_slots())
        self.registry.gauge("serve_queue_depth").set(self.queue.depth())

    # -- public API ------------------------------------------------------

    def start(self) -> "Engine":
        # Host-thread registry (tpunet/obs/flightrec/): a decode
        # iteration wedged on the device past the budget pages
        # thread_stalled; idle waits (empty pool) do not.
        self._thread_handle = flightrec.register_thread(
            "serve-engine", stall_after_s=120.0)
        flightrec.record("serve", f"engine start slots={self.slots}")
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="tpunet-serve-engine")
        self._thread.start()
        return self

    @property
    def healthy(self) -> bool:
        return (self.error is None and self._thread is not None
                and self._thread.is_alive())

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def active_slots(self) -> int:
        return sum(1 for s in self._active if s is not None)

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise PromptTooLongError(
            f"prompt of {prompt_len} tokens exceeds the largest "
            f"prefill bucket ({self.buckets[-1]})")

    def submit(self, prompt, **kw) -> GenerateRequest:
        """Admit a request (or raise QueueFullError / DrainingError /
        PromptTooLongError / ValueError). The generation budget is
        clamped to the operator cap and the KV length, but never
        silently: ``req.requested_max_new_tokens`` keeps what the
        client asked for, ``req.max_new_tokens`` is the EFFECTIVE
        budget the frontend reports back. Never blocks."""
        if self.error is not None:
            from tpunet.serve.scheduler import DrainingError
            raise DrainingError(f"engine failed: {self.error}")
        kw.setdefault("max_new_tokens", self.cfg.default_max_new_tokens)
        requested = int(kw["max_new_tokens"])
        kw["max_new_tokens"] = min(requested,
                                   self.cfg.max_new_tokens_cap)
        if (kw.get("deadline_s") or 0) <= 0 \
                and self.cfg.default_deadline_s > 0:
            kw["deadline_s"] = self.cfg.default_deadline_s
        req = GenerateRequest(prompt, **kw)
        req.requested_max_new_tokens = requested
        try:
            n = int(req.prompt.size)
            # A cross-replica resume (router failover) re-prefills
            # prompt PLUS the journaled tokens: the combined length
            # must fit a bucket, like any preempt-resume.
            self.bucket_for(n + req.resume_offset)
            if n + req.max_new_tokens > self.max_seq_len:
                req.max_new_tokens = self.max_seq_len - n
                if req.max_new_tokens < 1:
                    raise PromptTooLongError(
                        f"prompt of {n} tokens leaves no room to "
                        f"generate (max_seq_len {self.max_seq_len})")
            # Completability guard: a request whose FULL length
            # cannot fit the page pool even alone would preempt
            # itself forever — reject it up front instead.
            worst = -(-(n + req.max_new_tokens) // self.page_tokens)
            if worst > self.kv_pages_usable:
                raise PromptTooLongError(
                    f"request needs {worst} KV pages at full "
                    f"length but the pool has "
                    f"{self.kv_pages_usable}; lower "
                    "max_new_tokens or grow --kv-pages")
            if req.resume_offset and req.stop_token is not None \
                    and req.stop_token in req.tokens:
                # The journal already contains the stop token: the
                # donor died between streaming it and the done frame.
                # An uninterrupted run stops THERE — finish as 'stop'
                # without a slot, never generate past it.
                req.finish(FINISH_STOP)
                self._account_finish(req, FINISH_STOP)
                self.registry.counter("serve_requests_total").inc()
                return req
            if req.resume_offset \
                    and req.resume_offset >= req.max_new_tokens:
                # Mid-stream-failover resume whose journal already
                # meets the (possibly clamped) budget: the donor
                # replica died between its last token and the done
                # frame. Nothing to decode — finish as length without
                # ever taking a slot.
                req.finish(FINISH_LENGTH)
                self._account_finish(req, FINISH_LENGTH)
                self.registry.counter("serve_requests_total").inc()
                return req
            self.queue.submit(req)       # may raise QueueFull/Draining
        except Exception:
            self.registry.counter("serve_requests_rejected").inc()
            raise
        # Request-lifecycle breadcrumb into the flight-recorder ring:
        # submit -> prefill -> first_token -> finish become the
        # queue/prefill/decode phases on the unified timeline
        # (tpunet/obs/history/timeline.py). ~1-2 us each, no-op
        # without an armed recorder.
        flightrec.record("req", f"submit {req.id} len={req.prompt.size}")
        if req.resume_offset:
            # Cross-replica resume (router failover): without this
            # mark the request's second half starts with a bare
            # prefill and the timeline can't tell a resumed stream
            # from a fresh one.
            flightrec.record(
                "req", f"resume {req.id} off={req.resume_offset}")
        if req.trace_id:
            tracing.crumb("submit", req.trace_id, req.trace_hop,
                          rid=req.id)
        self.registry.counter("serve_requests_total").inc()
        self.registry.gauge("serve_queue_depth").set(self.queue.depth())
        self._wake.set()
        return req

    def _kill_survivors(self, reason: str) -> None:
        """Finish every in-flight and still-queued request with
        ``reason``, through the shared accounting — after the decode
        step in flight has been read, so every token the device
        computed for an unfinished request is pushed, once. Only safe
        from the engine thread, or once it can no longer run."""
        self._drain_decode()
        for i, slot in enumerate(self._active):
            if slot is not None:
                self._finish_slot(i, reason)
        while True:
            reqs = self.queue.pop_ready(self.queue.queue_max)
            if not reqs:
                break
            for req in reqs:
                req.finish(reason)
                self._account_finish(req, reason)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: stop admitting, let in-flight (and
        already-queued) requests finish, then stop the loop. Returns
        True when everything finished inside the timeout; leftovers are
        cancelled with finish_reason='drain'."""
        self._draining.set()
        waiting = self.queue.close()
        self._wake.set()
        if self._thread is None or not self._thread.is_alive():
            # Never started (or already dead): there is no loop to
            # finish the work — fail fast instead of waiting a budget
            # that can never be met.
            clean = self.active_slots() == 0 and not waiting
            self._kill_survivors(FINISH_DRAIN)
            self._stop.set()
            self._drained.set()
            return clean
        budget = timeout if timeout is not None \
            else self.cfg.drain_timeout_s
        clean = self._drained.wait(budget)
        if not clean:
            # Timeout: the ENGINE finishes survivors (in-flight and
            # still-queued alike) with reason 'drain' — through
            # _finish_slot so the serve_finished_drain counters and
            # e2e accounting stay truthful, and distinguishable from a
            # client-initiated cancel.
            self._drain_kill.set()
            self._wake.set()
            self._drained.wait(5.0)
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        return clean

    def stop(self) -> None:
        """Hard stop (tests / error paths): cancel everything. Unlike
        cancel() alone, every in-flight request is FINISHED here —
        clients blocked in result()/events() must unblock now, not at
        their own timeout."""
        self._draining.set()
        self.queue.fail_all("engine stopped")
        for slot in list(self._active):
            if slot is not None:
                slot.req.cancel()
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
        # The loop exits at the top of its while without a final reap:
        # finish whatever it left behind (thread joined or never ran,
        # so this is single-threaded now).
        self._kill_survivors(FINISH_CANCELLED)

    # -- engine loop -----------------------------------------------------

    def _run(self) -> None:
        handle = self._thread_handle
        try:
            while not self._stop.is_set():
                # Claim busy only when there is (potential) work: an
                # empty iteration is a poll, not work, and marking it
                # busy would (a) lie to the thread_stalled watchdog
                # and (b) flood the flight-recorder ring with ~100
                # busy/idle transition events per second from an idle
                # server, evicting the request breadcrumbs the
                # timeline exporter needs. A wedged device call always
                # had work, so stall detection is unaffected.
                if (self.active_slots() or self.queue.depth()
                        or self._drain_kill.is_set()):
                    handle.beat("busy")
                else:
                    handle.beat("idle")
                did_work = self._iterate()
                if self._draining.is_set() and self.active_slots() == 0 \
                        and self.queue.depth() == 0:
                    break
                if not did_work:
                    handle.beat("idle")
                    with self._phase("tpunet/serve_idle"):
                        self._wake.wait(timeout=0.02)
                    self._wake.clear()
            handle.beat("idle")
            self._emit_record(final=True)
        except BaseException as e:  # noqa: BLE001 — engine death is a
            # liveness event: surface through /healthz and fail every
            # request fast rather than hanging clients.
            self.error = f"{type(e).__name__}: {e}"
            flightrec.record("serve", f"engine error: {e}")
            try:
                # Tokens of a step that did finish reach their clients
                # before the error does.
                self._drain_decode()
            except Exception:  # noqa: BLE001 — the step in flight is
                # what failed, or fell with the device: the requests
                # fail below either way.
                self._in_flight = None
            for slot in self._active:
                if slot is not None:
                    slot.req.finish(FINISH_ERROR, error=self.error)
            self._active = [None] * self.slots
            self.queue.fail_all(self.error)
        finally:
            self._drained.set()

    def _iterate(self) -> bool:
        """One engine iteration: reap -> admit(prefill) -> decode
        (dispatch the next step, read the one in flight: the reap and
        the admission of the NEXT iteration then run beside the step
        just dispatched). Returns False when there was nothing to do
        (caller sleeps)."""
        if self._drain_kill.is_set():
            # Drain timeout expired: everything still alive finishes
            # with reason 'drain' (the shutdown took it, not a client).
            self._kill_survivors(FINISH_DRAIN)
            return False
        if self.chaos is not None:
            self.chaos.maybe_stall()    # wedged-replica injection
        self._reap()
        admitted = self._admit()
        stepped = self._decode_iteration()
        now = time.perf_counter()
        if self.cfg.emit_every_s > 0 \
                and now - self._last_emit >= self.cfg.emit_every_s:
            self._emit_record()
        return admitted or stepped

    def _phase(self, name: str, ring: bool = False, **args) -> Span:
        """One phase of the engine thread: the ``TraceAnnotation`` of
        that name (the profiler's clock, which the device's operations
        share; ``args`` are encoded only while a trace is on) whose
        host seconds also go to ``self._host_clock``. The phases TILE
        the thread: between them they cover everything an iteration
        does, as siblings or as children of one another and never as a
        parent around another's whole extent, so a reader can give each
        instant of a device gap to the innermost phase open at it.
        ``ring`` spans (the device calls) also land in the
        flight-recorder ring — the unified timeline's device phases,
        the crash tail's "which phase was the replica in"; the finer
        phases do not (they would evict the request breadcrumbs the
        timeline reads). Always on: no switch."""
        return Span(span(name, **args), name,
                    flightrec if ring else None, self._host_clock)

    def _reap(self) -> None:
        """Free slots whose request was cancelled or hit its deadline
        (cooperative cancellation point)."""
        now = time.perf_counter()
        due = [(i, FINISH_CANCELLED if slot.req.cancelled
                else FINISH_DEADLINE)
               for i, slot in enumerate(self._active)
               if slot is not None
               and (slot.req.cancelled or slot.req.expired(now))]
        if due:
            with self._phase("tpunet/serve_admit"):
                for i, reason in due:
                    self._finish_slot(i, reason)

    def _account_finish(self, req, reason: str) -> None:
        """Finish accounting shared by slot-finishes and requests the
        QUEUE finishes before they ever reach a slot: the counters must
        reconcile (requests_total == rejected + sum(finished_*))."""
        reg = self.registry
        flightrec.record("req", f"finish {req.id} {reason}")
        reg.counter(f"serve_finished_{reason}").inc()
        if reason in (FINISH_LENGTH, FINISH_STOP):
            reg.counter("serve_requests_completed").inc()
        if req.e2e_s is not None:
            reg.histogram("serve_e2e_s").observe(req.e2e_s)
        if req.trace_id:
            # Close this hop's replica span: crumb for the timeline
            # join, one obs_trace record with the phase decomposition
            # for the fleet rollup. The empty-trace_id check above is
            # the whole cost on the unsampled path.
            tracing.crumb("finish", req.trace_id, req.trace_hop,
                          rid=req.id, reason=reason)
            record = tracing.build_trace_record(
                trace_id=req.trace_id, hop=req.trace_hop,
                role="replica", finish_reason=reason,
                queue_s=req.queue_s, prefill_s=req.prefill_s,
                prefill_bucket=req.prefill_bucket,
                first_decode_s=req.first_decode_s,
                tokens=len(req.tokens) - req.resume_offset,
                preemptions=req.preemptions,
                preempt_wall_s=req.preempt_wall_s or None,
                resume_offset=req.resume_offset,
                ttft_s=req.ttft_s, e2e_s=req.e2e_s,
                error=req.error or "")
            tracing.observe_trace(reg, record)
            reg.emit("obs_trace", record)

    def _finish_slot(self, i: int, reason: str) -> None:
        slot = self._active[i]
        self._active[i] = None
        self._release_pages(i, slot)
        slot.req.finish(reason)
        self._account_finish(slot.req, reason)
        self.registry.gauge("serve_active_slots").set(self.active_slots())

    def _admit(self) -> bool:
        """Admit waiting requests into free slots and prefill them,
        grouped by bucket. Admission is FIFO and all-or-nothing per
        request — when the
        pool cannot cover the next request's prompt, it (and everyone
        behind it) goes back to the queue head until pages free up."""
        free = [i for i, s in enumerate(self._active) if s is None]
        if not free:
            return False
        reqs = self.queue.pop_ready(len(free))
        self.registry.gauge("serve_queue_depth").set(self.queue.depth())
        if not reqs:
            return False
        with self._phase("tpunet/serve_admit"):
            admitted, by_bucket = self._fit(reqs, free)
        if not admitted:
            return False
        for bucket, group in sorted(by_bucket.items()):
            self._prefill(bucket, group)
        if self._drafter_model is not None:
            # Drafter pool warm-up rides the same admission beat. The
            # drafter re-embeds the FULL prompt (prefix hits included)
            # so the grouping key is the full-length bucket, not the
            # suffix bucket the main prefill used.
            draft_groups: dict = {}
            for slot_i, _, _, resume, _, _, _ in admitted:
                if self._active[slot_i] is None:
                    continue     # finished inside its own prefill
                draft_groups.setdefault(
                    self.bucket_for(int(resume.size)), []).append(
                        (slot_i, resume))
            for bucket, rows in sorted(draft_groups.items()):
                self._draft_prefill(bucket, rows)
        with self._phase("tpunet/serve_admit"):
            self._update_kv_gauges()
            now_active = self.active_slots()
            self.peak_active_slots = max(self.peak_active_slots,
                                         now_active)
            self.registry.gauge("serve_active_slots").set(now_active)
        return True

    def _fit(self, reqs, free):
        """Give each popped request, in order, a free slot, its pinned
        prefix pages and the private pages its prompt needs; what the
        pool cannot cover goes back to the queue's head. Returns the
        admitted rows and the same grouped by prefill bucket."""
        import collections
        if self._thread_handle is not None:
            # A request can land between the top-of-loop idle beat and
            # this pop; mark busy BEFORE the prefill device call, or a
            # wedged call would hang an officially-idle thread and the
            # thread_stalled watchdog would never fire.
            self._thread_handle.beat("busy")
        admitted = []    # (slot_i, bucket, req, resume, pages, start,
        #                   pinned)
        pending = collections.deque(reqs)
        free_iter = iter(free)
        slot_i = next(free_iter, None)
        while pending and slot_i is not None:
            req = pending[0]
            # Resume-prefill for preempted requests: re-embed the
            # prompt PLUS everything already generated, so the slot
            # picks up exactly where it left off.
            if req.tokens:
                resume = np.concatenate(
                    [req.prompt, np.asarray(req.tokens, np.int32)])
            else:
                resume = req.prompt
            n = int(resume.size)
            try:
                # Conservative full-length fit (cache hits are never
                # guaranteed — eviction must not turn an admissible
                # request into an error later).
                bucket = self.bucket_for(n)
            except PromptTooLongError as e:
                # A resumed request can outgrow the largest prefill
                # bucket; it cannot be re-prefilled — fail it loudly
                # rather than wedge the queue head.
                pending.popleft()
                req.finish(FINISH_ERROR, error=f"preempt-resume: {e}")
                self._account_finish(req, FINISH_ERROR)
                continue
            start = 0
            pinned: List = []
            cow_src = None
            if self._prefix is not None:
                from tpunet.serve.prefixcache import keys as pk
                # Pin cap (n-1)//page_tokens: at least one suffix
                # token is always re-prefilled — the logits at
                # position n-1 come from compute, never from
                # cached K/V (pages store only K/V rows).
                # ONE pass over the prompt's key chain: the lookup
                # draws from it as far as it hits, the COW source is
                # its next key.
                digests = pk.iter_chain_digests(
                    resume, self.page_tokens, n // self.page_tokens)
                pinned = self._prefix.lookup(
                    resume, (n - 1) // self.page_tokens,
                    digests=digests)
                start = len(pinned) * self.page_tokens
                if n % self.page_tokens == 0 and pinned \
                        and start == n - self.page_tokens:
                    # Full page-aligned match: the divergence page
                    # is cached too. COW it below instead of
                    # re-prefilling its whole page.
                    cow_src = self._prefix.get(next(digests))
                # Pin BEFORE allocating: allocation may evict
                # unpinned cache pages, and the chain (and COW
                # source) must survive until mapped/copied.
                if cow_src is not None:
                    self._prefix.pin(pinned + [cow_src])
                elif pinned:
                    self._prefix.pin(pinned)
            pages = self._alloc_pages_for(slot_i, n,
                                          first_index=len(pinned))
            if pages is None:
                if cow_src is not None:
                    self._prefix.unpin(pinned + [cow_src])
                elif pinned:
                    self._prefix.unpin(pinned)
                break            # pool pressure: FIFO order holds
            # Map the pinned prefix pages into the slot's table
            # (indices 0..k-1): the suffix prefill and every
            # decode step read them through the gather; nothing
            # ever writes them (positions >= start only).
            for j, node in enumerate(pinned):
                self._page_table[slot_i, j] = node.page
            if cow_src is not None:
                # Copy-on-write at the divergence page: seed the
                # private copy from its cached twin, then prefill
                # only the final token (which overwrites its own
                # row in the copy — the shared page stays
                # immutable).
                self._copy_page(cow_src.page, pages[0])
                self._prefix.unpin([cow_src])
                start = n - 1
                self.registry.counter("serve_prefix_cow_total").inc()
            pending.popleft()
            if start:
                # The suffix picks the bucket: a 500-token prompt with
                # 480 cached tokens prefills through the 32-bucket
                # program — the TTFT win rides the smaller dispatch.
                bucket = self.bucket_for(n - start)
            admitted.append((slot_i, bucket, req, resume, pages, start,
                             pinned))
            slot_i = next(free_iter, None)
        if pending:
            self.queue.requeue_front(pending)
            self.registry.gauge("serve_queue_depth").set(
                self.queue.depth())
        by_bucket = {}
        for slot_i, bucket, req, resume, pages, start, pinned \
                in admitted:
            by_bucket.setdefault(bucket, []).append(
                (slot_i, req, resume, pages, start, pinned))
        return admitted, by_bucket

    def _prefill(self, bucket: int, group) -> None:
        """Prefill every admitted request padded to this bucket: one
        [1, bucket] call per request (``_prefill_rows`` 1), in
        admission order, each request's first token pushed as soon as
        its own call returns, or — over a mesh — one [slots, bucket]
        device call for the group. ``group`` rows are
        ``(slot_i, req, resume_tokens, pages, start, pinned)``;
        resume_tokens is prompt+generated for a preempted request
        resuming mid-stream, ``start`` is the first position NOT
        covered by pinned prefix-cache pages."""
        for slot_i, req, resume, pages, _, pinned in group:
            # Slot every request BEFORE the first device call: if a
            # step raises, the engine's failure handler finds (and
            # fails) them in _active instead of stranding popped
            # requests.
            self._admit_seq += 1
            slot = _Slot(req, pos=int(resume.size), next_token=0,
                         generated=len(req.tokens),
                         seq=self._admit_seq)
            slot.pages = pages
            slot.pinned = pinned
            self._active[slot_i] = slot
        if self._rows_at(bucket) == self.slots:
            self._prefill_call(bucket, group)
        else:
            for row in group:
                self._prefill_call(bucket, [row], row[0])

    def _prefill_call(self, bucket: int, group, slot_i=None) -> None:
        """One chunked-prefill device call: every row of ``group`` at
        its slot's batch row, or the one request of ``group`` as the
        only row of a [1, bucket] call over slot ``slot_i``'s pages.
        K/V land in each slot's pages and the next token is sampled
        on the device from the last REAL position.
        The padded tail writes garbage K/V beyond the prompt — masked
        invariant: a decode query at position p attends only j <= p
        and overwrites position p first, so padding is never visible.
        Only the suffix ``resume[start:]`` is embedded, at
        ``positions = start``, so the scatter never touches a pinned
        page (writes go to positions >= start only) while the attend
        reads the pinned K/V through the page table."""
        t0 = time.perf_counter()
        with self._phase("tpunet/serve_prefill_args"):
            toks, positions, active, last_idx = self._prefill_args(
                bucket, group, slot_i, t0)
        # bucket and prompt beside the span: a reader can set a span's
        # length against the prompt's (encoded only while a trace is on)
        seen = dict(bucket=bucket, prompt_tokens=sum(
            int(r.size) for _, _, r, _, _, _ in group))
        with self._phase("tpunet/serve_prefill", ring=True, **seen):
            self._cache, sampled = self._dispatch_step(
                toks, positions, active, last_idx, slot_i)
            for s_i, *_ in group:
                self._active[s_i].generated += 1
            # The call queues behind a decode step in flight (the pool
            # orders them on the device). That step finishes first:
            # read it first, so its tokens reach their clients when it
            # ends and not a prefill later. This call's own first
            # token is then read synchronously — one bubble a call,
            # refilled by the next decode dispatch.
            self._drain_decode()
            sampled = np.asarray(sampled)
        # Adopt freshly-written full prompt pages into the prefix
        # cache (and spill them) BEFORE the finish checks below can
        # release a short request's pages.
        if self._prefix is not None:
            with self._phase("tpunet/serve_prefix_adopt", **seen):
                for s_i, req, resume, pages, start, pinned in group:
                    slot = self._active[s_i]
                    if slot is not None:
                        self._adopt_prefix_pages(s_i, slot, resume)
                self._update_kv_gauges()
        with self._phase("tpunet/serve_publish"):
            self._publish_first_tokens(bucket, group, slot_i, sampled, t0)

    def _prefill_args(self, bucket: int, group, slot_i, t0: float):
        """The host's arguments of one prefill call, with each
        request's breadcrumbs and stamps (``t0``: when the call's host
        work began)."""
        rows = self.slots if slot_i is None else 1
        toks = np.zeros((rows, bucket), np.int32)
        active = np.zeros((rows,), bool)
        last_idx = np.zeros((rows,), np.int32)
        positions = np.zeros((rows,), np.int32)
        for s_i, req, resume, _, start, _ in group:
            n = int(resume.size)
            row = s_i if slot_i is None else 0
            toks[row, :n - start] = resume[start:]
            active[row] = True
            last_idx[row] = n - start - 1
            positions[row] = start
        for _, req, resume, _, start, _ in group:
            # A resume-prefill (preempt-resume or cross-replica
            # failover resume) re-embeds prompt+generated; the
            # distinct verb keeps the timeline honest about which
            # prefills are re-work.
            if int(resume.size) > int(req.prompt.size):
                flightrec.record("req", f"resume_prefill {req.id}")
            else:
                flightrec.record("req", f"prefill {req.id}")
            if start:
                flightrec.record(
                    "req", f"prefix_hit {req.id} tokens={start}")
            if req.prefill_start_t is None:
                req.prefill_start_t = t0
                req.prefill_bucket = bucket
            if req._preempt_t is not None:
                req.preempt_wall_s += t0 - req._preempt_t
                req._preempt_t = None
            if req.trace_id:
                tracing.crumb("prefill", req.trace_id, req.trace_hop,
                              rid=req.id, b=bucket)
        if self.chaos is not None:
            self.chaos.on_prefill()     # kill@prefill injection point
        return toks, positions, active, last_idx

    def _publish_first_tokens(self, bucket: int, group, slot_i, sampled,
                              t0: float) -> None:
        """The tail of ``_prefill_call``: each row's first token to its
        request, the stamps and histograms, the finish checks."""
        reg = self.registry
        rows = self.slots if slot_i is None else 1
        prefill_done = time.perf_counter()
        for s_i, req, resume, _, start, _ in group:
            n = int(resume.size)
            row = s_i if slot_i is None else 0
            if req.prefill_done_t is None:
                req.prefill_done_t = prefill_done
            first = int(sampled[row])
            fresh = req.first_token_t is None
            self._active[s_i].next_token = first
            req.push_token(first)
            if fresh:
                flightrec.record("req", f"first_token {req.id}")
                if req.trace_id:
                    tracing.crumb("first_token", req.trace_id,
                                  req.trace_hop, rid=req.id)
                reg.histogram("serve_ttft_s").observe(req.ttft_s)
            reg.counter("serve_tokens_total").inc()
            if self.chaos is not None:
                self.chaos.on_token()   # kill/stall@tokens (post-push:
                #                         the token reached the stream)
            self._slot_maybe_finish(s_i, first)
        reg.counter("serve_prefills_total").inc()
        if self._state_bytes_per_slot:
            # each row started at position 0: its state row began anew
            reg.counter("serve_state_rows_reset_total").inc(len(group))
        # Suffix tokens only: with a prefix hit this is the REAL
        # prefill compute — bench_serve's prefill_tokens_per_request
        # dropping to ~the suffix length is the tentpole's measured
        # win. Padded tokens are what the call computed (rows x
        # bucket): their ratio is the call's useful share.
        reg.counter("serve_prefill_tokens_total").inc(
            sum(int(r.size) - st for _, _, r, _, st, _ in group))
        reg.counter("serve_prefill_padded_tokens_total").inc(
            rows * bucket)
        reg.histogram("serve_prefill_s").observe(
            time.perf_counter() - t0)

    def _draft_prefill(self, bucket: int, rows) -> None:
        """Prefill the DRAFTER's paged pool for freshly admitted
        slots: one write-only full-prompt pass per bucket. ``rows``
        are ``(slot_i, resume_tokens)``.

        The drafter always embeds the FULL prompt from position 0,
        even when the main prefill rode a prefix-cache hit. Pinned
        prefix page ids are shared across slots and the drafter pool
        mirrors the main page table verbatim, so a drafter write to a
        shared page id is an IDEMPOTENT rewrite: every slot pinning
        that page holds the same token prefix and the drafter is
        deterministic, hence bit-identical K/V. Re-deriving instead
        of caching drafter pages keeps the drafter pool warm with
        ZERO extra allocator state and no drafter-side COW (the
        divergence page's drafter rows are simply written here). The
        cost is one drafter-width full prefill per admission — part
        of the lever's price, measured by ``bench_serve --spec``."""
        toks = np.zeros((self.slots, bucket), np.int32)
        active = np.zeros((self.slots,), bool)
        positions = np.zeros((self.slots,), np.int32)
        for slot_i, resume in rows:
            toks[slot_i, :int(resume.size)] = resume
            active[slot_i] = True
        # Prompt positions span 0..bucket-1, so the attention window
        # is static per bucket — the tag stays ``w{bucket}``.
        win = self._reach(bucket)
        with self._phase("tpunet/serve_spec_prefill", ring=True):
            self._draft_cache = self._dispatch_spec(
                "spec_draft_prefill", f"w{bucket}",
                self._draft_prefill_fn,
                (self._drafter_params, self._draft_cache, toks,
                 positions, active, self._page_table[:, :win]))

    def _ends_by_length(self, slot: _Slot) -> bool:
        """Whether the token the slot's newest dispatch samples is its
        request's last by count: the budget is spent or the KV length
        is. Counts alone decide it, so it is known at dispatch."""
        return (slot.generated >= slot.req.max_new_tokens
                or slot.pos + 1 > self.max_seq_len)

    def _slot_maybe_finish(self, slot_i: int, token: int,
                           last: Optional[bool] = None) -> bool:
        """Stop checks after a token was read and pushed; ``last`` is
        ``_ends_by_length`` as of the dispatch that sampled it (left
        out by a caller that reads each token before it dispatches
        again: the counts as they stand). True when the slot was
        freed."""
        slot = self._active[slot_i]
        req = slot.req
        if req.stop_token is not None and token == req.stop_token:
            self._finish_slot(slot_i, FINISH_STOP)
            return True
        if self._ends_by_length(slot) if last is None else last:
            self._finish_slot(slot_i, FINISH_LENGTH)
            return True
        return False

    def _decode_iteration(self) -> bool:
        """One masked decode step across the whole pool, ONE STEP
        AHEAD of the host: dispatch step N+1, then read step N.

        Every active slot consumes its pending token at its own
        position and samples the next one (fused on the device). What
        a step needs of its predecessor that the host does not already
        know is each row's sampled token, and that is on the device:
        a continuing row takes it from there (``_masked_step``'s
        ``prev``), so the host's work for step N+2 — pushing N's
        tokens, ``_reap``, ``_admit``, page capacity, the arguments —
        runs while N+1 does. Counts, not token values, decide the
        bookkeeping (``slot.pos``, ``slot.generated``, the sampler's
        step, the length finish), so it is done at dispatch. A row
        whose LAST token is in flight is not dispatched again; it
        keeps its slot and pages until that token is read and pushed.

        What only a token's value or the clock decides — a stop token,
        a cancel, a deadline — is seen after N+1 went out with the row
        live. That row of N+1 is DISCARDED when read: never pushed,
        never counted. The slot's pages were released by host code
        that ran after N+1's dispatch, so whatever program next writes
        them runs after N+1 on the device, and the one K/V row N+1
        wrote there is padding under ``_prefill_call``'s masked
        invariant. It cannot lie in a page the prefix cache adopted:
        the cache adopts only full pages of the PROMPT, decode writes
        at positions past the prompt, on the slot's private pages.

        Each slot's next write page is allocated here
        (allocate-on-advance); a slot the pool cannot extend sits the
        iteration out, and when NOTHING can advance the youngest
        blocked slot is preempted back to the queue so the others
        drain and free pages — after the step in flight has been read:
        preemption re-queues prompt + generated and needs every token
        on the host."""
        if self._drafter_model is not None:
            return self._spec_decode_iteration()
        flight = self._in_flight
        # A resident slot that ends by its counts has its last token in
        # flight: a read would have freed it (counts move at dispatch
        # only, and a prefill frees such a slot itself).
        live = [(i, s) for i, s in enumerate(self._active)
                if s is not None and not self._ends_by_length(s)]
        ready = []
        blocked = []
        args = None
        if live:
            with self._phase("tpunet/serve_decode_args"):
                for i, slot in live:
                    if self._ensure_page_capacity(i, slot):
                        ready.append((i, slot))
                    else:
                        blocked.append((i, slot))
                if ready:
                    self._update_kv_gauges()
                    args = self._decode_args(ready)
        if not ready:
            if flight is not None:
                # Nothing to dispatch behind it: its rows end with it,
                # or wait for the pages it may free.
                self._drain_decode()
                return True
            if blocked:
                self._preempt_slot(self._choose_preempt_victim(blocked))
                return True          # freed pages; retry next iteration
            return False
        self._decode_width1(ready, args)
        return True

    def _decode_args(self, live) -> tuple:
        """The host's arguments of one [slots, 1] masked decode call
        for ``live`` slots, behind the clock at which the step's host
        work began (``_Step.t0``)."""
        t0 = time.perf_counter()
        flight = self._in_flight
        ahead = ({i: slot for i, slot, _ in flight.rows}
                 if flight is not None else {})
        toks = self._inactive_tok.copy()
        positions = np.zeros((self.slots,), np.int32)
        active = np.zeros((self.slots,), bool)
        from_prev = np.zeros((self.slots,), bool)
        for i, slot in live:
            if ahead.get(i) is slot:
                from_prev[i] = True      # its token is still unread
            else:
                toks[i, 0] = slot.next_token
            positions[i] = slot.pos
            active[i] = True
        return t0, toks, positions, active, from_prev

    def _decode_width1(self, live, args) -> None:
        """Dispatch one [slots, 1] masked decode call for ``live``
        slots (page capacity already ensured by the caller, ``args``
        from ``_decode_args``), then read the step that was in flight
        before it; the new one stays in flight. One
        ``tpunet/serve_decode`` span per dispatched step covers both.
        The spec path's tail fallback reads its step at once
        (``_drain_decode``): that cycle stays synchronous."""
        t0, toks, positions, active, from_prev = args
        flight = self._in_flight
        reg = self.registry
        with self._phase("tpunet/serve_decode", ring=True):
            self._cache, self._sampled = self._dispatch_step(
                toks, positions, active, self._zero_idx,
                from_prev=from_prev)
            rows = []
            for i, slot in live:
                slot.pos += 1
                slot.generated += 1
                rows.append((i, slot, self._ends_by_length(slot)))
            self._in_flight = _Step(self._sampled, rows, t0)
            reg.counter("serve_decode_steps_total").inc()
            if self._window_tokens:
                pt, seen = self.page_tokens, self._window_pages
                for _, slot in live:
                    seen[0] += max(0, (slot.pos - self._window_tokens) // pt)
                    seen[1] += -(-slot.pos // pt)
            if flight is not None:
                reg.counter("serve_decode_steps_overlapped_total").inc()
                self._read_decode(flight)

    def _drain_decode(self) -> None:
        """Read the decode step in flight, if there is one: every
        token the device has computed is then on the host and pushed.
        Called before anything that needs that (preemption, the final
        record, the kill paths, the spec cycle) and where waiting for
        it costs nothing (behind a prefill call, an empty pool)."""
        flight, self._in_flight = self._in_flight, None
        if flight is not None:
            self._read_decode(flight)

    def _read_decode(self, step: _Step) -> None:
        """Block until ``step`` has finished, then push its tokens and
        run the checks a token's value decides. A row whose slot was
        freed since the dispatch (stop token, cancel, deadline) is
        discarded."""
        with self._phase("tpunet/serve_decode_wait", ring=True):
            sampled = np.asarray(step.sampled)
        with self._phase("tpunet/serve_publish"):
            self._publish_step(step, sampled)

    def _publish_step(self, step: _Step, sampled) -> None:
        """A read step's tokens to their requests, the period's
        histograms, the finish checks."""
        now = time.perf_counter()
        # The period between two consecutive reads; from its own
        # dispatch for a step that was not dispatched ahead.
        lap = now - max(step.t0, self._last_read_t)
        self._last_read_t = now
        reg = self.registry
        reg.histogram("serve_decode_iter_s").observe(lap)
        # per-token latency: the step produced one token for each
        # live slot, each of which waited that period.
        reg.histogram("serve_token_s").observe(lap)
        for i, slot, last in step.rows:
            if self._active[i] is not slot:
                reg.counter("serve_decode_rows_discarded_total").inc()
                continue
            nxt = int(sampled[i])
            slot.next_token = nxt
            slot.req.push_token(nxt)
            reg.counter("serve_tokens_total").inc()
            if self.chaos is not None:
                self.chaos.on_token()   # kill/stall@tokens (post-push)
            self._slot_maybe_finish(i, nxt, last)

    # -- speculative decode path (docs/serving.md) ----------------------

    def _spec_decode_iteration(self) -> bool:
        """One draft+verify cycle across the pool: burst-eligible
        slots draft K tokens and verify them in one wide call (1..K+1
        verified tokens each); tail slots — too close to max_seq_len
        for a full burst — fall back to the existing width-1 program
        in the same iteration. A slot nearing its TOKEN budget still
        bursts: the emit loop breaks exactly at max_new_tokens (the
        overshot verify positions are wasted compute, and the slot
        releases its pages on finish), which keeps every live slot on
        the wide program instead of serializing request tails into
        width-1 iterations. POOL PRESSURE can also force a width-1
        cycle; such a slot may re-enter the burst later with a
        drafter-pool gap at the width-1-advanced positions. The gap
        costs acceptance (garbage drafter K/V -> bad drafts), never
        correctness: every emitted token comes from the verify (or
        width-1 decode) program, and rejection falls back to one
        verified token per cycle."""
        live = [(i, s) for i, s in enumerate(self._active)
                if s is not None]
        if not live:
            return False
        k = self.spec_k
        burst, seq_ready, blocked = [], [], []
        for i, slot in live:
            eligible = slot.pos + k + 1 <= self.max_seq_len
            # A burst writes pos..pos+K (both pools; shared table) —
            # ensure coverage through pos+K, or fall back to width-1
            # coverage before counting the slot as blocked.
            if eligible and self._ensure_page_capacity(
                    i, slot, through_pos=slot.pos + k):
                burst.append((i, slot))
            elif self._ensure_page_capacity(i, slot):
                seq_ready.append((i, slot))
            else:
                blocked.append((i, slot))
        if blocked and not burst and not seq_ready:
            self._preempt_slot(self._choose_preempt_victim(blocked))
            return True              # freed pages; retry next iteration
        self._update_kv_gauges()
        if not burst and not seq_ready:
            return False
        if burst:
            self._spec_burst(burst)
        if seq_ready:
            # Tail and capacity-starved slots advance one verified
            # token through the plain width-1 program. Their drafter
            # pool now lags the main cursor — benign per the
            # docstring's acceptance-vs-correctness argument.
            tail = [(i, s) for i, s in seq_ready
                    if self._active[i] is s]
            with self._phase("tpunet/serve_decode_args"):
                args = self._decode_args(tail)
            self._decode_width1(tail, args)
            self._drain_decode()
        return True

    def _spec_burst(self, burst) -> None:
        """Draft K+1, verify K+1, accept, rewind — the spec hot path.
        Acceptance (tpunet/serve/spec.py ``accept_drafts``) keeps the
        longest prefix where draft d_j matched verify choice c_{j-1};
        the slot emits c_0..c_a (ALL from the verify program, which is
        the bitwise spec-off-parity argument), advances its cursor by
        a+1, and the rejected tail pages go back to the free list."""
        k = self.spec_k
        reg = self.registry
        t0 = time.perf_counter()
        first = np.zeros((self.slots,), np.int32)
        positions = np.zeros((self.slots,), np.int32)
        active = np.zeros((self.slots,), bool)
        for i, slot in burst:
            first[i] = slot.next_token
            positions[i] = slot.pos
            active[i] = True
        # Attention window: the smallest page-slot bucket covering
        # every burst slot's pos+K. Both programs see the SLICED
        # table — they attend over (and gather) only the live key
        # window instead of all max_seq_len rows, which is where the
        # verify's per-position cost lives on short sequences.
        win = self._reach(k + 1, max(int(s.pos) for _, s in burst))
        table = self._page_table[:, :win]
        # temp/top_k/top_p/seeds/steps0 — steps0[i] = len(req.tokens)
        # is the sequential sampler's next step counter, so draft and
        # verify keys stay in lockstep with the spec-off stream.
        samp = self._sampling_args(self._zero_idx)[1:]
        with self._phase("tpunet/serve_spec_draft", ring=True):
            self._draft_cache, drafts = self._dispatch_spec(
                "spec_draft_burst", f"k{k}w{win}",
                self._draft_burst_fn,
                (self._drafter_params, self._draft_cache, first,
                 positions, active, table, *samp))
            drafts = np.asarray(drafts)
        verify_toks = np.zeros((self.slots, k + 1), np.int32)
        verify_toks[:, 0] = first
        verify_toks[:, 1:] = drafts
        with self._phase("tpunet/serve_spec_verify", ring=True):
            self._cache, choices = self._dispatch_spec(
                "spec_verify", f"k{k}w{win}", self._verify_fn,
                (self.variables["params"], self._cache, verify_toks,
                 positions, active, table, *samp))
            choices = np.asarray(choices)
        lap = time.perf_counter() - t0
        reg.counter("serve_decode_steps_total").inc()
        reg.histogram("serve_decode_iter_s").observe(lap)
        reg.histogram("serve_token_s").observe(lap)
        with self._phase("tpunet/serve_publish"):
            self._publish_burst(burst, drafts, choices)

    def _publish_burst(self, burst, drafts, choices) -> None:
        """A verified burst's accepted tokens to their requests, the
        acceptance counters, the finish checks, the rewinds."""
        from tpunet.serve import spec as serve_spec
        k = self.spec_k
        reg = self.registry
        rows = np.asarray([i for i, _ in burst])
        accepted = serve_spec.accept_drafts(drafts[rows],
                                            choices[rows])
        for (i, slot), a in zip(burst, accepted):
            a = int(a)
            reg.counter("serve_spec_draft_tokens_total").inc(k)
            reg.counter("serve_spec_accepted_tokens_total").inc(a)
            reg.counter("serve_spec_rejected_tokens_total").inc(k - a)
            reg.counter("serve_spec_verify_steps_total").inc()
            finished = False
            for j in range(a + 1):
                tok = int(choices[i, j])
                slot.pos += 1
                slot.generated += 1
                slot.next_token = tok
                slot.req.push_token(tok)
                reg.counter("serve_tokens_total").inc()
                if self.chaos is not None:
                    self.chaos.on_token()   # post-push: the token
                    #                         reached the stream —
                    #                         only VERIFIED tokens are
                    #                         ever journaled upstream
                if self._slot_maybe_finish(i, tok):
                    finished = True
                    break
            if not finished:
                self._rewind_slot_pages(i, slot)
        drafted = reg.counter("serve_spec_draft_tokens_total").value
        acc = reg.counter("serve_spec_accepted_tokens_total").value
        reg.gauge("serve_spec_acceptance_rate").set(
            round(acc / drafted, 4) if drafted else 0.0)
        self._update_kv_gauges()

    def _rewind_slot_pages(self, slot_i: int, slot: _Slot) -> None:
        """Cursor rewind after a (partial) rejection: free the private
        tail pages beyond the last verified position. The rows holding
        rejected K/V are simply recycled — the masked write-then-read
        invariant makes stale rows invisible, so the rewind is pure
        host bookkeeping (no device work). Structurally clamped at
        pinned prefix pages: a burst writes only positions >= the
        prefill suffix start, which live on PRIVATE pages, and only
        ``slot.pages`` (the private list) is ever freed — a shared
        prefix page can never be rewound or mutated (pinned either at
        admission COW time or never written at all; pinned by test in
        tests/test_serve_paged.py)."""
        keep_hi = (slot.pos - 1) // self.page_tokens
        keep_private = max(0, keep_hi + 1 - len(slot.pinned))
        tail = slot.pages[keep_private:]
        if not tail:
            return
        del slot.pages[keep_private:]
        base = len(slot.pinned) + keep_private
        for j in range(base, base + len(tail)):
            self._page_table[slot_i, j] = 0
        # reversed(): the page covering the NEXT write position goes
        # back on top of the LIFO free list, so the very next
        # allocate-on-advance hands the same page straight back.
        self._free_pages.extend(reversed(tail))

    # -- obs -------------------------------------------------------------

    def _emit_record(self, final: bool = False) -> None:
        """One ``obs_serve`` record (docs/metrics_schema.md) per window:
        cumulative counters + window histograms, then a fresh window.
        The final record counts every token computed: the decode step
        in flight is read first."""
        if final:
            self._drain_decode()
        reg = self.registry
        now = time.perf_counter()
        window = now - self._last_emit
        self._last_emit = now
        # The operator's copy of the phases: cumulative seconds, and
        # the longest single span since the last record (then reset).
        for name, total in self._host_clock.totals.items():
            phase = name[len(_PHASE_PREFIX):]
            reg.gauge("serve_host_s_" + phase).set(
                round(total.seconds, 6))
            reg.gauge("serve_host_max_s_" + phase).set(
                round(total.longest, 6))
            total.longest = 0.0
        if self._prefill_keys[1]:
            reached, handed = self._prefill_keys
            reg.gauge("serve_prefill_key_reach_pct").set(round(
                100.0 * reached / handed, 4))
        if self._window_pages[1]:
            dead, held = self._window_pages
            reg.gauge("serve_cache_window_dead_pct").set(round(
                100.0 * self._window_bytes_share * dead / held, 4))
        record = build_serve_record(
            reg, queue_depth=self.queue.depth(),
            active_slots=self.active_slots(), slots=self.slots,
            uptime_s=now - self._started, window_s=window, final=final)
        if self.chaos is not None:
            # A record from a chaos-armed replica says so: bench and
            # history comparisons must never mistake injected faults
            # for organic regressions.
            record["chaos"] = self.chaos.render()
        # Host-thread gauges ride the serve registry too: GET /metrics
        # and exporters see thread_* ages for the engine loop and any
        # exporter drains.
        from tpunet.obs.flightrec.threads import THREADS
        THREADS.export_gauges(reg)
        reg.emit("obs_serve", record)
        reg.reset_window()
