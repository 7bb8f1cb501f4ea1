#!/usr/bin/env python
"""tpunet training entry point.

Replaces all three reference training scripts with one CLI over presets
(SURVEY.md section 0):

  python train.py --preset serial       # cifar10_serial_mobilenet_224.py
  python train.py --preset single       # cifar10_128batch.py
  python train.py --preset distributed  # cifar10_mpi_mobilenet_224.py

Distributed runs need no mpirun/rank plumbing: launch the same command on
every TPU-VM worker (see launch/run_pod.sh); process topology comes from
the platform via jax.distributed.initialize.
"""

from __future__ import annotations

import dataclasses

import jax

from tpunet.config import config_from_args
from tpunet.obs import RunUnhealthyError
from tpunet.parallel import initialize_distributed, sync_hosts
from tpunet.train.loop import Trainer
from tpunet.utils import log0
from tpunet.utils.cache import (compile_stats_line,
                                enable_persistent_compile_cache)


def main(argv=None) -> int:
    initialize_distributed()
    enable_persistent_compile_cache()
    cfg = config_from_args(argv)
    # Profiling is owned by the obs subsystem now (tpunet/obs/spans.py
    # WindowedProfiler): --profile-dir alone still traces the whole
    # run, but the trace starts/stops at step boundaries inside the
    # trainer so --profile-start-step/--profile-num-steps can scope it.

    n_proc = jax.process_count()
    if n_proc > 1:
        # Reference semantics: per-rank batch of 128 => global scales with
        # world size (cifar10_mpi_mobilenet_224.py:117 + mpirun -np N).
        cfg = cfg.replace(data=dataclasses.replace(
            cfg.data, batch_size=cfg.data.batch_size * n_proc))
    dev = jax.devices()[0]
    log0(f"JAX devices: {jax.device_count()} "
         f"({jax.local_device_count()} local), processes: {n_proc}, "
         f"platform: {dev.platform}, device_kind: {dev.device_kind}")

    # Dataset fetch gate (reference rank-0 download + barrier, :93-102):
    # process 0 materializes the data first, other hosts wait — and
    # ONLY the dataset. Trainer construction issues device-layout
    # computations (the sharded state device_put / jit-identity), and
    # cross-process collectives must run in the SAME order on every
    # process (gloo on CPU gangs pairs them strictly by sequence; the
    # old p0-builds-Trainer-before-the-barrier shape interleaved p0's
    # layout computations with the others' barrier psum and died in
    # gloo's preamble check) — so construction is symmetric, after the
    # barrier.
    if n_proc > 1:
        if jax.process_index() == 0:
            from tpunet.data import get_dataset
            get_dataset(cfg.data)
        sync_hosts("dataset-ready")
    trainer = Trainer(cfg)

    try:
        if cfg.eval_only:
            m = trainer.evaluate_checkpoint()
            log0(f"Eval: Test Loss: {m['loss']:.4f} "
                 f"Test Acc: {m['accuracy']:.4f}")
        else:
            trainer.train()
    except RunUnhealthyError as e:
        # --halt-on-unhealthy tripped: the obs_alert record is already
        # in metrics.jsonl (and the live exporters) — exit nonzero
        # without a traceback, like a failed health check should.
        log0(f"ABORT: {e}")
        return 2
    finally:
        # Runs on the NaN-guard/preemption-raise paths too; close()
        # flushes checkpoints AND any still-open profiler trace, each
        # independently (Trainer.close's own try/finally).
        trainer.close()
        log0(compile_stats_line())
    return 0


if __name__ == "__main__":  # python -m tpunet.main
    import sys

    sys.exit(main())


