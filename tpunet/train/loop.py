"""Epoch-driven training loop with best-checkpoint tracking.

Mirrors the reference's main() shape (cifar10_mpi_mobilenet_224.py:52-252):
per-epoch [reshuffled sharded train pass -> full eval pass -> scheduler
tick -> rank-0 epoch log line -> best-accuracy tracking], then a final
save — re-built on jit/shardings: one XLA program per train step (which
internally augments, runs the model, all-reduces grads over the mesh and
updates Adam), device-resident metric accumulation, exact global metrics,
and crash-safe Orbax checkpoints with true resume (the reference restarts
from epoch 0, SURVEY.md section 5).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from tpunet.ckpt import Checkpointer
from tpunet.config import TrainConfig
from tpunet.data import (eval_batches, get_dataset, steps_per_epoch,
                         timed_batches, train_batches)
from tpunet.obs import JsonlSink, Observability, RunUnhealthyError
from tpunet.obs import device_time, flightrec
from tpunet.obs.perf import train_flops_per_unit
from tpunet.elastic import events as elastic_events
from tpunet.parallel import (batch_sharding, make_mesh, replicated_sharding,
                             shard_host_batch)
from tpunet.ops.partition import traced_under
from tpunet.parallel.mesh import mesh_shape_dict
from tpunet.parallel.tp import rules_for, state_shardings, tree_shardings
from tpunet.train import metrics as M
from tpunet.train.state import create_train_state, lr_schedule
from tpunet.train.steps import (make_eval_step, make_lm_eval_step,
                                make_lm_train_step, make_train_step)
from tpunet.utils import Timer, epoch_line, log0
from tpunet.utils.logging import MetricsLogger, summary_lines
from tpunet.utils.preemption import PreemptionGuard
from tpunet.utils.prng import root_key, step_key


# Models whose batches are rows of tokens (targets = the row shifted).
TOKEN_MODELS = ("lm", "lm_pp", "latent_lm")


class Trainer:
    """Owns the mesh, state, jitted steps, and the epoch loop."""

    def __init__(self, cfg: TrainConfig, mesh=None, dataset=None):
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_mesh(cfg.mesh)
        ds = dataset if dataset is not None else get_dataset(cfg.data)
        self.train_x, self.train_y, self.test_x, self.test_y = ds
        self.spe = steps_per_epoch(len(self.train_x), cfg.data.batch_size)
        if self.spe == 0:
            raise ValueError("batch size larger than training set")

        self.is_lm = cfg.model.name in TOKEN_MODELS
        is_token_data = cfg.data.dataset in ("synthetic_lm", "text_lm")
        if self.is_lm != is_token_data:
            raise ValueError(
                f"model {cfg.model.name!r} and dataset "
                f"{cfg.data.dataset!r} are different families (a token "
                "model needs token data, e.g. --dataset synthetic_lm)")
        if self.is_lm and cfg.model.vocab_size != cfg.data.vocab_size:
            raise ValueError(
                f"model vocab {cfg.model.vocab_size} != data vocab "
                f"{cfg.data.vocab_size}; out-of-range tokens would be "
                "silently clamped by the embedding")
        state = create_train_state(
            cfg.model, cfg.optim, root_key(cfg.seed),
            image_size=cfg.data.image_size,
            steps_per_epoch=self.spe, epochs=cfg.epochs, mesh=self.mesh,
            seq_len=cfg.data.seq_len, allow_download=cfg.data.download)
        repl = replicated_sharding(self.mesh)
        bsh = batch_sharding(self.mesh)
        # Tensor parallelism: params (and, via mirrored tree paths, their
        # Adam moments) matching the model's TP path rules are sharded
        # over the 'model' mesh axis; everything else is replicated, which
        # is exactly the reference's DDP layout (README:77).
        # state_shardings is also the elastic re-mesh contract: a
        # resized world builds this against ITS mesh and the restore
        # re-shards every FSDP leaf onto the new data axis
        # (docs/elasticity.md).
        state_sh = state_shardings(
            state, cfg.model, self.mesh, zero1=cfg.mesh.zero1,
            fsdp=cfg.mesh.fsdp)
        # Multi-controller too: every process holds the identical host
        # state (deterministic same-seed init), which is what
        # device_put onto a global-mesh sharding assumes.
        self.state = jax.device_put(state, state_sh)

        # out_shardings pinned: without it XLA may propagate shard_map
        # internals (e.g. a 'seq'-sharded pos-embed gradient) onto the
        # returned state, which would then mismatch in_shardings on the
        # next call.
        accum = cfg.optim.grad_accum
        if accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {accum}")
        if not 0.0 <= cfg.optim.ema_decay < 1.0:
            # decay >= 1 silently freezes the EMA at the random init and
            # eval/best-checkpoint would measure that forever.
            raise ValueError(f"ema_decay must be in [0, 1), got "
                             f"{cfg.optim.ema_decay}")
        if cfg.log_every_steps < 0:
            raise ValueError(f"log_every_steps must be >= 0, got "
                             f"{cfg.log_every_steps}")
        if cfg.data.mixup_alpha < 0 or cfg.data.cutmix_alpha < 0:
            raise ValueError("mixup/cutmix alphas must be >= 0")
        if self.is_lm and (cfg.data.mixup_alpha > 0
                           or cfg.data.cutmix_alpha > 0):
            raise ValueError("mixup/cutmix are image-family options; "
                             "the LM train step does not read them")
        if not 0.0 <= cfg.optim.warmup_epochs < cfg.epochs:
            # warmup >= the whole run would keep every step on the ramp
            # (base LR never reached, cosine horizon collapses to 1).
            raise ValueError(
                f"warmup_epochs ({cfg.optim.warmup_epochs}) must be in "
                f"[0, epochs={cfg.epochs})")
        if cfg.data.batch_size % accum:
            raise ValueError(
                f"batch size {cfg.data.batch_size} is not divisible by "
                f"grad_accum {accum}")
        ndata = self.mesh.shape.get("data", 1)
        if (cfg.data.batch_size // accum) % ndata:
            raise ValueError(
                f"microbatch {cfg.data.batch_size // accum} "
                f"(batch {cfg.data.batch_size} / grad_accum {accum}) is "
                f"not divisible by the data-axis size {ndata}")
        if (cfg.model.name in ("vit_pp", "lm_pp") and accum > 1
                and self.mesh.shape.get("pipe", 1) > 1):
            # Time-microbatching (accum) wraps stage-microbatching
            # (GPipe): each accum slice must still split into
            # pp_microbatches per data shard.
            npipe_mb = cfg.model.pp_microbatches
            per_shard = cfg.data.batch_size // accum // ndata
            if per_shard % npipe_mb:
                raise ValueError(
                    f"grad_accum x pipeline: per-data-shard microbatch "
                    f"{per_shard} (batch {cfg.data.batch_size} / accum "
                    f"{accum} / data {ndata}) is not divisible by "
                    f"pp_microbatches {npipe_mb}")
        # FSDP gathers params to their COMPUTE layout at step start: the
        # TP/PP spec (without the FSDP catch-alls) for model/pipe leaves,
        # replicated for the rest — tensor/pipeline compute sharding is
        # preserved; only the resting 'data' shard is gathered.
        gather_sh = None
        if cfg.mesh.fsdp:
            gather_sh = tree_shardings(
                state.params, self.mesh,
                rules_for(cfg.model, mesh=self.mesh))
        packed = cfg.data.pack_docs
        if packed:
            if cfg.data.dataset != "text_lm":
                raise ValueError(
                    f"--pack-docs packs text_lm documents; dataset is "
                    f"{cfg.data.dataset!r} (its labels are not segment "
                    "ids)")
            if not self.is_lm:
                raise ValueError("--pack-docs needs --model lm or "
                                 "lm_pp (the segment-masked attention "
                                 "paths)")
            if cfg.model.attention not in ("dense", "flash", "auto",
                                           "ulysses"):
                raise ValueError(
                    f"--pack-docs needs a segment-capable attention "
                    f"core (dense/flash/auto, or ulysses for packed x "
                    f"SP), got {cfg.model.attention!r} — ring's "
                    "state-merging core has no segment operands")
        train_fn = (make_lm_train_step(cfg.optim, cfg.model, self.mesh,
                                       gather_params=gather_sh,
                                       packed=packed)
                    if self.is_lm
                    else make_train_step(cfg.data, cfg.optim, cfg.model,
                                         self.mesh,
                                         gather_params=gather_sh))
        eval_fn = (make_lm_eval_step(cfg.model, self.mesh,
                                     gather_params=gather_sh,
                                     packed=packed) if self.is_lm
                   else make_eval_step(cfg.data, gather_params=gather_sh))
        # Traced under the mesh so the Pallas kernels split over it
        # (tpunet/ops/partition.py).
        self.train_step = jax.jit(
            traced_under(self.mesh, train_fn),
            in_shardings=(state_sh, bsh, bsh, repl),
            out_shardings=(state_sh, repl),
            donate_argnums=0)
        self.eval_step = jax.jit(
            traced_under(self.mesh, eval_fn),
            in_shardings=(state_sh, bsh, bsh, bsh))

        self._prefetcher = None
        if (cfg.data.native_loader and not cfg.eval_only
                and not cfg.data.pack_docs):
            # The native gather moves raw bytes per row, so uint8 image
            # rows and int32 token rows share the same path. Packed
            # datasets carry [B, T] segment ids in the label slot, which
            # the prefetcher's scalar-label ABI doesn't cover — numpy
            # path there.
            # The long-standing resume heap-corruption bug that used
            # to force a numpy-loader fallback here was root-caused
            # (flight-recorder evidence, runs/flightrec-repro-r7) to
            # buffer donation of orbax-restored state — nothing to do
            # with the prefetcher — and fixed at the source
            # (Checkpointer.restore_state re-materializes restored
            # arrays), so resumed runs keep the native path.
            from tpunet.data import native
            if native.available():
                local = cfg.data.batch_size // jax.process_count()
                self._prefetcher = native.NativePrefetcher(
                    self.train_x, self.train_y.astype(np.int32),
                    local)

        self._schedule = lr_schedule(cfg.optim, self.spe, cfg.epochs)
        # Observability (tpunet/obs/): per-step timing + stall split +
        # windowed profiling. Constructed before the Checkpointer so
        # checkpoint dispatch/wait can report into the same registry.
        self.obs = Observability(
            cfg.obs, profile_dir=cfg.profile_dir,
            checkpoint_dir=cfg.checkpoint.directory,
            unit="tokens" if self.is_lm else "examples",
            # resume keeps the persisted run_id, so the restored
            # stream continues the same fleet identity.
            resume=cfg.checkpoint.resume)
        if self.obs.enabled:
            # Config fingerprint joins runs of the same workload: the
            # run-history store and cross-run regression compare
            # (tpunet/obs/history/) only judge run N against run N-1
            # when the fingerprints match, and BENCH artifacts join to
            # training runs through the same hash.
            from tpunet.obs.history import train_fingerprint
            ident = self.obs.registry.identity()
            self.obs.registry.set_identity(
                **ident, config_fingerprint=train_fingerprint(cfg))
        from tpunet.models import create_model, num_params
        self.obs.set_flops_per_unit(train_flops_per_unit(
            cfg.model, cfg.data, n_params=num_params(state.params)))
        if self.obs.enabled:
            # Set once: the bytes of parameters this process trains and
            # what the model says of itself (latent_lm: experts held).
            gauges = {"train_params_resident_bytes": sum(
                p.nbytes for p in jax.tree_util.tree_leaves(state.params))}
            gauges.update(getattr(
                create_model(cfg.model, mesh=self.mesh), "train_gauges",
                lambda seq_len: {})(cfg.data.seq_len))
            for name, value in gauges.items():
                self.obs.registry.gauge(name).set(value)
        self.ckpt = Checkpointer(cfg.checkpoint, obs=self.obs)
        self.guard = PreemptionGuard(deadline_s=cfg.preempt_grace_s)
        # Fault injection (--chaos): armed process-globally so the
        # checkpointer's IO hooks reach the same injector; scoped to
        # this process index (host=H events address one gang member).
        self._chaos = None
        if cfg.chaos:
            from tpunet.elastic import chaos as chaos_mod
            self._chaos = chaos_mod.install(
                cfg.chaos, process_index=jax.process_index())
        # Elastic-agent context (TPUNET_ELASTIC_* env): generation
        # gauges for the fleet view, the previous incarnation's mesh
        # for the "recovered" record, and this incarnation's mesh
        # persisted for the NEXT one.
        self._elastic = elastic_events.agent_env()
        self._prev_mesh = None
        if self._elastic is not None:
            run_dir = cfg.checkpoint.directory
            self._prev_mesh = elastic_events.read_mesh(run_dir)
            if jax.process_index() == 0:
                elastic_events.write_mesh(run_dir,
                                          mesh_shape_dict(self.mesh))
            if self.obs.enabled:
                reg = self.obs.registry
                reg.gauge("elastic_generation").set(
                    self._elastic["generation"])
                reg.gauge("elastic_world_processes").set(
                    jax.process_count())
        self._watchdog_halt = None
        # Proactive checkpoint-and-evict (--evict-on-straggler): a
        # straggler-shaped alert on THIS replica requests the agreed
        # stop with an evict marker — the pod checkpoints now, the
        # elastic agent re-meshes without the slow host.
        self._evict_requested = None
        if (self.obs.watchdog is not None
                and cfg.obs.evict_on_straggler):
            def _evict(record):
                if self._evict_requested is None:
                    # Claim at ALERT time (first claim wins — several
                    # replicas' watchdogs may fire near-simultaneously
                    # under a pod-wide slowdown): the claimer is the
                    # evicted replica; everyone still requests the
                    # agreed stop so the pod checkpoints together.
                    claimed = elastic_events.write_evict_marker(
                        cfg.checkpoint.directory,
                        process_index=jax.process_index(),
                        host=elastic_events.agent_host(),
                        reason=str(record.get("reason", "straggler")),
                        detail=record)
                    print(f"[process {jax.process_index()}] EVICT "
                          f"{'claimed' if claimed else 'joined'} "
                          f"after watchdog alert: {record}",
                          flush=True)
                    self._evict_requested = record
                    self.guard.request()
            self.obs.watchdog.on_evict = _evict
        if jax.process_count() > 1 and self.obs.watchdog is not None:
            # Multi-host --halt-on-unhealthy: a fatal alert on any one
            # process must not raise there (the others would wedge in
            # their next collective). Route it through the preemption
            # guard instead — _stop_agreed's allgather then stops
            # every host at a step boundary with a partial-epoch save,
            # after which train() re-raises so the exit code still
            # says "unhealthy" (2), not "clean preemption" (0).
            def _halt(record):
                # print (not log0): the detecting host may not be the
                # coordinator, and its log is where the evidence goes.
                print(f"[process {jax.process_index()}] HALT requested "
                      f"by watchdog: {record}", flush=True)
                self._watchdog_halt = record
                self.guard.request()
            self.obs.watchdog.on_fatal = _halt
        self.global_step = 0
        self.start_epoch = 1
        self.best_acc = 0.0
        self.history: List[Dict[str, float]] = []
        self._hbm_attrib_pending = bool(cfg.obs.enabled
                                        and cfg.obs.hbm_attrib)
        # Lazy: nothing is lowered until a reader of the device trace
        # (or --obs-hbm-attrib) asks for the step's text.
        device_time.register_programs(self.program_texts)
        if cfg.checkpoint.resume:
            self._try_resume()

    # ------------------------------------------------------------------

    def _pp_layout(self) -> np.ndarray:
        """[pipe, virtual] when the stacked params are stored in the
        interleaved schedule's chunk-PERMUTED order, else [0, 0] —
        persisted with the state so a resume under a different
        (schedule, pipe, virtual) fails loudly instead of silently
        reinterpreting a layer-scrambled stack
        (tpunet/parallel/pp.py interleaved_layer_order)."""
        il = (self.cfg.model.pp_schedule == "interleaved"
              and self.mesh.shape.get("pipe", 1) > 1)
        return np.asarray(
            [self.mesh.shape.get("pipe", 1), self.cfg.model.pp_virtual]
            if il else [0, 0], np.int32)

    def _payload(self, completed: bool = True) -> Dict:
        return {
            "state": self.state,
            "epoch": np.asarray(self.start_epoch, np.int32),
            # 0 marks a mid-epoch (preemption) save: resume re-runs that
            # epoch instead of skipping its remaining data (at-least-once
            # semantics; the restored step counter keeps the LR schedule
            # continuous either way).
            "completed": np.asarray(int(completed), np.int32),
            "global_step": np.asarray(self.global_step, np.int32),
            "best_acc": np.asarray(self.best_acc, np.float32),
            "pp_layout": self._pp_layout(),
        }

    def _try_resume(self) -> None:
        restored = self.ckpt.restore_state(self._payload())
        if restored is None:
            return
        got = [int(x) for x in np.asarray(restored.get(
            "pp_layout", np.zeros(2, np.int32)))]
        want = [int(x) for x in self._pp_layout()]
        if got != want:
            def name(lay):
                return ("gpipe/1f1b layout" if lay[0] == 0 else
                        f"interleaved pipe={lay[0]} virtual={lay[1]}")
            raise ValueError(
                f"checkpoint stack layout mismatch: saved with "
                f"{name(got)}, resuming with {name(want)} — the "
                "interleaved schedule stores chunk-permuted layer "
                "stacks, so resume with the same --pp-schedule/"
                "--mesh-pipe/--pp-virtual as the original run")
        self.state = restored["state"]
        completed = int(restored.get("completed", 1))
        self.start_epoch = int(restored["epoch"]) + (1 if completed else 0)
        self.global_step = int(restored["global_step"])
        self.best_acc = float(restored["best_acc"])
        flightrec.record("train", f"resume restored epoch="
                                  f"{int(restored['epoch'])} "
                                  f"step={self.global_step}")
        log0(f"Resumed from epoch {int(restored['epoch'])}"
             f"{'' if completed else ' (partial)'} "
             f"(best acc {self.best_acc:.4f})")

    # ------------------------------------------------------------------

    def _epoch_batches(self, epoch: int):
        cfg = self.cfg
        if self._prefetcher is not None:
            from tpunet.data.pipeline import host_index_sequence
            idx = host_index_sequence(
                len(self.train_x), global_batch=cfg.data.batch_size,
                seed=cfg.seed, epoch=epoch,
                process_index=jax.process_index(),
                process_count=jax.process_count())
            return self._prefetcher.iter_epoch(idx)
        return train_batches(
            self.train_x, self.train_y,
            global_batch=cfg.data.batch_size,
            seed=cfg.seed, epoch=epoch,
            process_index=jax.process_index(),
            process_count=jax.process_count())

    # Multi-host preemption polling period (steps). The agreement
    # collective blocks the host, so it runs every K steps, in lockstep
    # on all hosts; a preemption grace window is tens of seconds, far
    # longer than K steps. Env-overridable (TPUNET_STOP_POLL_STEPS) so
    # the chaos harness can exercise agreed stops inside tiny epochs
    # (docs/elasticity.md).
    STOP_POLL_STEPS = int(os.environ.get("TPUNET_STOP_POLL_STEPS", "16"))

    def _agree_stop(self, tag: str) -> bool:
        """Cross-host OR of the local stop flag. Routed through the
        coordination-service KV store (tpunet/parallel/dist.agree_any)
        because this runs CONCURRENTLY with the async checkpoint
        worker's orbax cross-host barriers — two XLA host collectives
        from two threads interleave differently per process and abort
        the transport (the gloo preamble crash the chaos evict leg
        reproduced). Allgather remains the no-coordination-service
        fallback, where no concurrent orbax barriers can exist."""
        from tpunet.parallel.dist import agree_any
        stop = agree_any(tag, self.guard.requested)
        if stop is None:
            from jax.experimental import multihost_utils
            import jax.numpy as jnp
            flags = multihost_utils.process_allgather(
                jnp.asarray(self.guard.requested))
            stop = bool(np.asarray(flags).any())
        if stop:
            self.guard.request()  # keep local flag consistent for train()
        return stop

    def _stop_agreed(self) -> bool:
        """Cross-host-agreed preemption decision. The signal flag is
        process-local; if hosts diverged on it, the ones still issuing
        the sharded train step would deadlock in its collectives and the
        multi-host Orbax save would wedge. All hosts agree in lockstep
        (every STOP_POLL_STEPS steps) and stop if ANY host was
        signalled — per-VM spot preemption hits workers too, not just
        the coordinator."""
        if jax.process_count() == 1:
            return self.guard.requested
        if self.global_step % self.STOP_POLL_STEPS:
            return False
        return self._agree_stop(f"stop/{self.global_step}")

    def _epoch_stop_agreed(self, epoch: int) -> bool:
        """Epoch-boundary stop agreement. The in-loop ``_stop_agreed``
        only polls every STOP_POLL_STEPS, so a signal landing in the
        final stretch of an epoch can leave hosts DIVERGED at the
        epoch boundary: the signalled host would take the partial-save
        path (a collective orbax save) while the rest enter eval —
        deadlock. One agreement per epoch, run by every host in
        lockstep right after the epoch, closes that hole."""
        if jax.process_count() == 1:
            return self.guard.requested
        return self._agree_stop(f"estop/{epoch}")

    def train_one_epoch(self, epoch: int) -> Dict[str, float]:
        cfg = self.cfg
        every = cfg.log_every_steps
        acc = None
        obs = self.obs
        # Hoisted once per epoch: the disabled path pays exactly one
        # branch per step, no spans, no timer objects, no wrapper
        # around the batch iterator.
        obs_hot = obs.hot
        # The epoch's two edges run once a pass with the device drained
        # or draining: named, so that a device gap there says which.
        with obs.span("tpunet/train_epoch_start"):
            obs.begin_epoch(epoch)
            batches = self._epoch_batches(epoch)
        if obs_hot:
            batches = timed_batches(
                batches, obs.observe_data_wait,
                wait_ctx=lambda: obs.span("tpunet/data_wait"))
            sync = lambda: jax.block_until_ready(self.state)  # noqa: E731
            step_timer = Timer()
        for bx, by in batches:
            if self._stop_agreed():
                break  # preemption: stop at a step boundary
            rng = step_key(cfg.seed, self.global_step)
            if self._hbm_attrib_pending:
                self._hbm_attrib_pending = False
                self._attribute_hbm_bytes(bx, by, rng)
            if obs_hot:
                # Profile-window edge check; the sync fence runs only
                # on the two steps where a window opens/closes. The
                # lap measures host-side dispatch wall time — under
                # saturated async dispatch that converges to device
                # step time; epoch totals are exact either way (the
                # end-of-epoch summarize() is the window-edge sync).
                obs.before_step(self.global_step, sync)
                step_timer.lap()
                if self._chaos is not None:
                    # Fault injection fires INSIDE the measured step
                    # window, host-side: SIGKILL/SIGTERM/slow-host
                    # land exactly where real faults strike — and an
                    # injected straggler delay shows up in step_time_s
                    # where the watchdog's stall detector looks.
                    self._chaos.step(self.global_step)
                with obs.step_span(self.global_step):
                    gx, gy = shard_host_batch(self.mesh, bx,
                                              by.astype(np.int32))
                    self.state, m = self.train_step(self.state, gx, gy,
                                                    rng)
                obs.observe_step(self.global_step, step_timer.lap())
            else:
                if self._chaos is not None:
                    self._chaos.step(self.global_step)
                gx, gy = shard_host_batch(self.mesh, bx,
                                          by.astype(np.int32))
                self.state, m = self.train_step(self.state, gx, gy, rng)
            acc = m if acc is None else M.accumulate(acc, m)
            self.global_step += 1
            if obs_hot and obs.profiler.running:
                # A window ending exactly at the epoch boundary must
                # close HERE, not on the next epoch's first step —
                # otherwise the trace bleeds across eval/checkpoint.
                obs.profiler.on_step(self.global_step, sync)
            if every and self.global_step % every == 0:
                # Opt-in per-step line (forces a device sync for the
                # metric values; per-epoch-only, like the reference,
                # when log_every_steps == 0).
                sm = M.summarize(m)
                # The loss is a host float here anyway — feed the
                # watchdog's NaN/spike detector at no extra sync cost.
                obs.observe_loss(self.global_step, sm["loss"])
                # The step just taken consumed optax's PRE-increment
                # count, i.e. schedule(global_step - 1) — print the LR
                # that actually produced this loss.
                lr = float(self._schedule(self.global_step - 1))
                log0(f"  step {self.global_step} "
                     f"loss {sm['loss']:.4f} acc {sm['accuracy']:.4f} "
                     f"lr {lr:.3e}")
        acc = acc if acc is not None else M.zeros_metrics()
        with obs.span("tpunet/train_summarize"):
            summary = M.summarize(acc)    # the chunk's device fence
            means = M.summarize_step_means(acc)
        if means and obs.enabled:
            # losses apart and the no-drop experts' routing load, as
            # gauges and as one record per pass over the data
            for name, value in means.items():
                obs.registry.gauge("train_" + name).set(value)
            obs.registry.emit("obs_train_means", {
                "epoch": epoch, "step": self.global_step,
                **{"train_" + k: round(v, 6) for k, v in means.items()}})
        return summary

    def program_texts(self) -> Dict[str, str]:
        """``{label: optimized HLO text}`` of the train step: the one
        way to the step's text — the device trace's scope table
        (tpunet/obs/device_time.py) and --obs-hbm-attrib both read it.
        Lowered from the live state and one global batch's shape under
        the step's batch sharding, which is the signature the running
        step was compiled for: once that has run, nothing compiles."""
        def batch(rows, dtype):
            return jax.ShapeDtypeStruct(
                (self.cfg.data.batch_size,) + rows.shape[1:], dtype,
                sharding=batch_sharding(self.mesh))
        text = self.train_step.lower(
            self.state, batch(self.train_x, self.train_x.dtype),
            batch(self.train_y, np.int32),
            step_key(self.cfg.seed, 0)).compile().as_text()
        return {device_time.module_name(text): text}

    def _attribute_hbm_bytes(self, bx, by, rng) -> None:
        """--obs-hbm-attrib: once, before the first step, mirror the
        per-op-category decomposition of the train step's
        cost-analysis HBM bytes into the hbm_bytes_per_image_* gauges
        (tpunet/obs/hlo_bytes.py). The step's arguments are not read
        (``program_texts`` lowers from the state and the batch's
        shape); any failure is logged and training proceeds
        (attribution is observability, never a reason to stop a
        run)."""
        try:
            from tpunet.obs import hlo_bytes
            (text,) = self.program_texts().values()
            per_chip = max(1, self.cfg.data.batch_size
                           // jax.device_count())
            self.obs.set_hbm_breakdown(hlo_bytes.per_image_breakdown(
                text, per_chip))
        except Exception as e:  # pragma: no cover - backend-specific
            log0(f"hbm byte attribution failed: {e}")

    def current_lr(self) -> float:
        """The LR the NEXT step will use (host-side schedule lookup)."""
        return float(self._schedule(self.global_step))

    def evaluate_checkpoint(self) -> Dict[str, float]:
        """--eval-only: load the saved weights and run one evaluation
        pass — the best-params checkpoint when present (what inference
        serves), else the last full train state."""
        # Eval-only runs have no step loop to drive the windowed
        # profiler, but a configured --profile-dir still means "trace
        # this run": open the trace here; Trainer.close() (main.py's
        # finally) stops and flushes it.
        prof = self.obs.profiler
        if prof.active and not prof.running:
            prof.on_step(prof.start_step)
        best = self.ckpt.restore_best({
            "params": self.state.params,
            "batch_stats": self.state.batch_stats})
        if best is not None:
            kw = dict(params=best["params"],
                      batch_stats=best["batch_stats"])
            if self.cfg.optim.ema_decay > 0:
                # the best checkpoint already holds the EMA pair, and
                # evaluate() reads the ema_* fields when EMA is on
                kw.update(ema_params=best["params"],
                          ema_batch_stats=best["batch_stats"])
            self.state = self.state.replace(**kw)
        elif self.ckpt.latest_step() is not None:
            self._try_resume()
        else:
            raise FileNotFoundError(
                f"no checkpoint under {self.cfg.checkpoint.directory!r} "
                "(need best/ or state/ to --eval-only)")
        return self.evaluate()

    def evaluate(self) -> Dict[str, float]:
        cfg = self.cfg
        state = self.state
        if cfg.optim.ema_decay > 0:
            # Evaluate the EMA weights + EMA BN stats as a pair (what
            # the best-checkpoint saves). Both mirror their live trees
            # shape-for-shape and shard-for-shard (tp.py FSDP_RULES),
            # so in_shardings still match.
            state = state.replace(params=state.ema_params,
                                  batch_stats=state.ema_batch_stats)
        acc = None
        with self.obs.span("tpunet/eval"):
            for bx, by, bm in eval_batches(
                    self.test_x, self.test_y,
                    global_batch=cfg.data.effective_eval_batch_size,
                    process_index=jax.process_index(),
                    process_count=jax.process_count()):
                gx, gy, gm = shard_host_batch(
                    self.mesh, bx, by.astype(np.int32), bm)
                m = self.eval_step(state, gx, gy, gm)
                acc = m if acc is None else M.accumulate(acc, m)
        return M.summarize(acc if acc is not None else M.zeros_metrics())

    # ------------------------------------------------------------------

    def train(self) -> List[Dict[str, float]]:
        cfg = self.cfg
        log0(f"Train samples: {len(self.train_x)}")
        log0(f"Test samples: {len(self.test_x)}")
        from tpunet.models import num_params
        log0(f"Total parameters: {num_params(self.state.params)}")
        log0("Host loader: " + ("native C++ prefetcher"
                                if self._prefetcher is not None else "numpy"))
        log0("Starting training...")
        flightrec.record("train", "starting training loader="
                         + ("native" if self._prefetcher is not None
                            else "numpy"))
        log0("")
        metrics_log = MetricsLogger(cfg.checkpoint.directory,
                                    resume=cfg.checkpoint.resume)
        # obs records (obs_epoch / obs_step) share the run's
        # metrics.jsonl; MetricsLogger already restricts writes to the
        # coordinator.
        self.obs.add_sink(JsonlSink(metrics_log))
        if (self.obs.enabled and self._elastic is not None
                and self._elastic["generation"] > 0):
            # A re-meshed incarnation: the recovery record that pairs
            # with the agent's shrink/grow/restart — same run_id, the
            # NEW mesh, and the restore stamp that proves which
            # checkpoint carried the run across (docs/elasticity.md).
            self.obs.registry.emit(
                "obs_elastic", elastic_events.build_elastic_record(
                    "recovered",
                    generation=self._elastic["generation"],
                    new_world=jax.process_count(),
                    old_mesh=self._prev_mesh,
                    new_mesh=mesh_shape_dict(self.mesh),
                    epoch=self.start_epoch, step=self.global_step))
        # The PLAIN epoch records below bypass Registry.emit, so stamp
        # them here: without identity the fleet aggregator would file
        # them under a junk per-file stream instead of this run's.
        identity = self.obs.registry.identity()
        total = Timer()
        self.guard.install()
        try:
            for epoch in range(self.start_epoch, cfg.epochs + 1):
                timer = Timer()
                train_m = self.train_one_epoch(epoch)
                train_secs = timer.elapsed()
                # Watchdog loss checks run BEFORE the hard NaN guard:
                # the obs_alert record lands in metrics.jsonl (and the
                # live exporters) even when the guard below aborts the
                # run, so the post-mortem explains itself. Under
                # --halt-on-unhealthy this raises RunUnhealthyError.
                self.obs.observe_loss(self.global_step, train_m["loss"])
                if not np.isfinite(train_m["loss"]):
                    # Failure detection (SURVEY.md section 5: the
                    # reference has none — a NaN run would burn its full
                    # SLURM walltime producing garbage). Stop BEFORE
                    # save_state so the resume chain keeps the last
                    # finite epoch, not the poisoned weights — and make
                    # that checkpoint durable first (saves are async;
                    # raising past an uncommitted save would break the
                    # message's promise).
                    self.ckpt.wait()
                    raise FloatingPointError(
                        f"non-finite train loss ({train_m['loss']}) at "
                        f"epoch {epoch}; the last completed checkpoint "
                        f"is still finite — resume from it with a lower "
                        f"--lr or with --clip-norm")
                if self._epoch_stop_agreed(epoch):
                    if self.guard.escalated:
                        # Second SIGTERM inside the grace window: the
                        # platform is saying NOW. Best-effort abandon:
                        # no save, no durability wait — a save that
                        # gets SIGKILLed mid-write is strictly worse
                        # than resuming from the last intact
                        # checkpoint (which is exactly what --resume
                        # does).
                        flightrec.record(
                            "train", f"escalated preemption epoch="
                                     f"{epoch}")
                        log0(f"Second preemption signal at epoch "
                             f"{epoch} (step {self.global_step}); "
                             "abandoning checkpoint work and exiting "
                             "immediately")
                        self.start_epoch = epoch
                        self.ckpt.abandon()
                        break
                    # Preempted mid-epoch: persist the advanced state,
                    # marked partial so --resume re-runs this epoch's
                    # remaining data instead of skipping it.
                    flightrec.record("train", f"preemption epoch="
                                              f"{epoch}")
                    if self._evict_requested is not None:
                        # The agreed stop is an EVICT (marker already
                        # claimed at alert time); emit the
                        # obs_elastic breadcrumb that explains it
                        # (record-first: the straggler obs_alert is
                        # already in the stream).
                        if self.obs.enabled:
                            self.obs.registry.emit(
                                "obs_elastic",
                                elastic_events.build_elastic_record(
                                    "evict_requested",
                                    cause=str(
                                        self._evict_requested.get(
                                            "reason", "straggler")),
                                    epoch=epoch,
                                    step=self.global_step,
                                    detail=self._evict_requested))
                    log0(f"Preemption requested at epoch {epoch} (step "
                         f"{self.global_step}); "
                         + ("saving state and exiting"
                            if cfg.checkpoint.save_last else
                            "state NOT saved (checkpoint.save_last is "
                            "off) — exiting"))
                    self.start_epoch = epoch
                    self.ckpt.save_state(epoch,
                                         self._payload(completed=False))
                    # Self-describing history: the eval pass was skipped,
                    # so resumed metrics.jsonl readers can tell this row
                    # apart from a completed epoch (VERDICT r1 item 10).
                    metrics_log.log({
                        **identity,
                        "epoch": epoch, "partial": True,
                        "step": self.global_step,
                        "seconds": timer.elapsed(),
                        "train_loss": train_m["loss"],
                        "train_accuracy": train_m["accuracy"],
                    })
                    self.obs.end_epoch(
                        epoch=epoch, step=self.global_step,
                        units=train_m["count"],
                        train_seconds=train_secs, partial=True)
                    if self._watchdog_halt is not None:
                        # The "preemption" was the watchdog's agreed
                        # multi-host halt: the partial state is saved,
                        # now make the exit say UNHEALTHY — an
                        # orchestrator that auto-requeues preemptions
                        # must not silently restart a sick run.
                        self.ckpt.wait()
                        raise RunUnhealthyError(
                            f"run unhealthy (agreed multi-host halt): "
                            f"{self._watchdog_halt}; partial state "
                            f"saved at epoch {epoch}")
                    break
                test_m = self.evaluate()
                secs = timer.elapsed()
                log0(epoch_line(epoch, cfg.epochs, secs,
                                train_m["loss"], train_m["accuracy"],
                                test_m["loss"], test_m["accuracy"]))
                record = {
                    **identity,
                    "epoch": epoch, "seconds": secs,
                    "step": self.global_step,
                    # throughput over the epoch (eval pass included),
                    # in each family's metric unit: images/sec for the
                    # vision models (comparable with BASELINE.md's
                    # derived img/s), next-token predictions/sec for
                    # the LM (its metric count is B*(T-1) per batch).
                    ("tokens_per_sec" if self.is_lm else
                     "examples_per_sec"):
                        round(train_m["count"] / secs, 2),
                    "train_loss": train_m["loss"],
                    "train_accuracy": train_m["accuracy"],
                    "test_loss": test_m["loss"],
                    "test_accuracy": test_m["accuracy"],
                }
                self.history.append(record)
                metrics_log.log(record)
                if test_m["accuracy"] > self.best_acc:
                    self.best_acc = test_m["accuracy"]
                    # With EMA on, the test accuracy was measured on the
                    # EMA weights + EMA BN stats — save that pair (what
                    # inference loads).
                    ema_on = cfg.optim.ema_decay > 0
                    lay = self._pp_layout()
                    self.ckpt.save_best({
                        "params": (self.state.ema_params if ema_on
                                   else self.state.params),
                        "batch_stats": (self.state.ema_batch_stats
                                        if ema_on
                                        else self.state.batch_stats),
                    }, meta={
                        "model": cfg.model.name,
                        "pp_schedule": cfg.model.pp_schedule,
                        "pp_layout_pipe": int(lay[0]),
                        "pp_layout_virtual": int(lay[1]),
                    })
                self.start_epoch = epoch
                self.ckpt.save_state(epoch, self._payload())
                # After the save dispatches so this epoch's own
                # checkpoint shows in its cumulative ckpt counters.
                self.obs.end_epoch(
                    epoch=epoch, step=self.global_step,
                    units=train_m["count"], train_seconds=train_secs,
                    eval_seconds=secs - train_secs)
            else:
                # Every epoch completed (no preemption/evict break):
                # tell the elastic agents the run is DONE, not
                # preempted — without this a supervising agent would
                # faithfully relaunch a finished run.
                if self._elastic is not None \
                        and jax.process_index() == 0:
                    elastic_events.mark_done(cfg.checkpoint.directory)
        finally:
            self.guard.uninstall()
        log0("")
        for line in summary_lines(self.best_acc, total.elapsed()):
            log0(line)
        if self.guard.escalated:
            self.ckpt.abandon()
        else:
            # Durability barrier, bounded by whatever remains of the
            # preemption grace window (unbounded on a normal exit or
            # without --preempt-grace-s).
            self.ckpt.wait(timeout=self.guard.remaining())
        return self.history

    def close(self) -> None:
        # Each cleanup independent (nested finally): a failing
        # checkpoint flush cannot skip the profiler flush or the
        # prefetcher shutdown, or vice versa.
        try:
            self.obs.close(lambda: jax.block_until_ready(self.state))
        finally:
            try:
                if self._prefetcher is not None:
                    self._prefetcher.close()
                    self._prefetcher = None
            finally:
                self.ckpt.close()
