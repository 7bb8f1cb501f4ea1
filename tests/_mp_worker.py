"""Worker for the 2-process multi-controller test (run via subprocess).

Boots jax.distributed against a localhost coordinator (the analogue of
the reference's mpirun + localhost:29500 rendezvous,
cifar10_mpi_mobilenet_224.py:28-35), builds the global mesh spanning both
processes' virtual CPU devices, trains one epoch of the tiny synthetic
workload, and prints metrics as JSON for the parent to compare.
"""

import json
import os
import sys

# Worker-process environment ONLY: tests import this module for its
# *_case() config factories, and mutating XLA_FLAGS at import time
# would silently re-initialize the IMPORTING process's backend with 4
# devices (a solo `pytest tests/test_multiprocess.py::<one test>` hit
# exactly that).
if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

if __name__ == "__main__":
    jax.config.update("jax_platforms", "cpu")

    # Persistent compile cache, shared with tests/conftest.py and the
    # dryrun: the two controllers compile IDENTICAL programs, so
    # whichever wins the race warms the other (and any prior test run
    # warms both).
    from tpunet.utils.cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()


def fsdp_lm_case():
    """(cfg, dataset) for the FSDP+grad-accum LM case — the ONE source
    of truth shared by the worker and the test's single-process
    reference (FSDP: params + Adam moments sharded over the
    cross-process 'data' axis)."""
    from tpunet.config import (CheckpointConfig, DataConfig, MeshConfig,
                               ModelConfig, OptimConfig, TrainConfig)
    from tpunet.data.lm import synthetic_lm

    cfg = TrainConfig(
        epochs=1, seed=42,
        data=DataConfig(dataset="synthetic_lm", batch_size=16,
                        seq_len=32, vocab_size=32),
        model=ModelConfig(name="lm", vit_hidden=64, vit_depth=2,
                          vit_heads=4, dropout_rate=0.0,
                          dtype="float32", vocab_size=32,
                          max_seq_len=32),
        optim=OptimConfig(learning_rate=3e-3, grad_accum=2),
        mesh=MeshConfig(fsdp=True),
        checkpoint=CheckpointConfig(save_best=False, save_last=False),
    )
    return cfg, synthetic_lm(64, 32, seq_len=32, vocab=32, seed=7)


def pp_lm_case():
    """(cfg, dataset) for the PIPELINED LM case under multi-controller:
    the 1F1B executor's shard_map (activation ppermutes over 'pipe',
    microbatch scheduling, the manual VJP) spans a mesh whose 'data'
    axis crosses the process boundary — the closest analogue of the
    reference's multi-node pipeline story (its DDP is single-axis;
    this is schedule + cross-process sharding together)."""
    from tpunet.config import (CheckpointConfig, DataConfig, MeshConfig,
                               ModelConfig, OptimConfig, TrainConfig)
    from tpunet.data.lm import synthetic_lm

    cfg = TrainConfig(
        epochs=1, seed=42,
        data=DataConfig(dataset="synthetic_lm", batch_size=16,
                        seq_len=32, vocab_size=32),
        model=ModelConfig(name="lm_pp", vit_hidden=64, vit_depth=4,
                          vit_heads=4, dropout_rate=0.0,
                          dtype="float32", vocab_size=32,
                          max_seq_len=32, pp_microbatches=2,
                          pp_schedule="1f1b"),
        optim=OptimConfig(learning_rate=3e-3),
        mesh=MeshConfig(data=4, pipe=2),
        checkpoint=CheckpointConfig(save_best=False, save_last=False),
    )
    return cfg, synthetic_lm(64, 32, seq_len=32, vocab=32, seed=7)


def packed_lm_case(tmp_dir=None):
    """(cfg, dataset) for the packed-sequence LM case: both controllers
    train on packed documents with [B, T] segment-id labels crossing
    the process boundary — exercises 2-D label sharding, the
    segment-masked step, and count-weighted metrics multi-controller.
    Each process writes its OWN copy of the (deterministic, identical)
    corpus — a shared path would race: the workers reach this right
    after the rendezvous, and one could read the file mid-truncation.
    """
    from tpunet.config import (CheckpointConfig, DataConfig, MeshConfig,
                               ModelConfig, OptimConfig, TrainConfig)
    from tpunet.data.lm import text_lm_packed

    tmp_dir = tmp_dir or f"/tmp/tpunet-mp-packed-{os.getpid()}"
    os.makedirs(tmp_dir, exist_ok=True)
    path = os.path.join(tmp_dir, "docs.txt")
    docs = ([b"alpha beta gamma delta"] * 30 + [b"tiny"] * 60) * 2
    with open(path, "wb") as f:
        f.write(b"\n".join(docs))
    cfg = TrainConfig(
        epochs=1, seed=42,
        data=DataConfig(dataset="text_lm", text_path=path,
                        batch_size=16, seq_len=32, vocab_size=256,
                        pack_docs=True),
        model=ModelConfig(name="lm", vit_hidden=64, vit_depth=2,
                          vit_heads=4, dropout_rate=0.0,
                          dtype="float32", vocab_size=256,
                          max_seq_len=32),
        optim=OptimConfig(learning_rate=3e-3),
        mesh=MeshConfig(),
        checkpoint=CheckpointConfig(save_best=False, save_last=False),
    )
    return cfg, text_lm_packed(path, seq_len=32)


def _tree_equal(a, b):
    """Bit-exact pytree equality, computed as a global computation (works
    on cross-process sharded leaves: every controller runs the same
    array_equal, whose scalar result is replicated)."""
    import jax
    import jax.numpy as jnp

    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb:
        return False
    return all(bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))


def _state_data(state):
    """The ARRAY fields of a TrainState: its own treedef also carries
    apply_fn/tx as static aux data, which are different function objects
    in different Trainer instances — comparing those would always
    report inequality."""
    return {"params": state.params, "batch_stats": state.batch_stats,
            "opt_state": state.opt_state, "step": state.step,
            "ema_params": state.ema_params,
            "ema_batch_stats": state.ema_batch_stats}


def _ckpt_roundtrip(trainer, cfg, ds, train1):
    """Multi-host orbax checkpointing under TRUE multi-controller: both
    processes participate in one best-params save + one full-state save
    into a SHARED directory, then a fresh Trainer resumes from it and
    must match bit-exactly. The reference saves from rank 0 only
    (cifar10_mpi_mobilenet_224.py:243-250); orbax instead coordinates
    every host through the same save — the coordination (barrier
    pairing, one consistent directory, no deadlock) is exactly what
    this exercises."""
    import dataclasses

    from tpunet.train.loop import Trainer

    trainer.best_acc = float(train1["accuracy"])
    lay = trainer._pp_layout()
    trainer.ckpt.save_best(
        {"params": trainer.state.params,
         "batch_stats": trainer.state.batch_stats},
        meta={"model": cfg.model.name,
              "pp_schedule": cfg.model.pp_schedule,
              "pp_layout_pipe": int(lay[0]),
              "pp_layout_virtual": int(lay[1])})
    trainer.ckpt.save_state(1, trainer._payload())
    trainer.ckpt.wait()

    cfg2 = cfg.replace(checkpoint=dataclasses.replace(
        cfg.checkpoint, resume=True))
    t2 = Trainer(cfg2, dataset=ds)
    try:
        state_equal = _tree_equal(_state_data(trainer.state),
                                  _state_data(t2.state))
        best = t2.ckpt.restore_best({
            "params": t2.state.params,
            "batch_stats": t2.state.batch_stats})
        best_equal = best is not None and _tree_equal(
            trainer.state.params, best["params"])
        meta = t2.ckpt.best_meta()
        return {
            "resume_epoch": t2.start_epoch,
            "resume_best_acc": t2.best_acc,
            "state_equal": state_equal,
            "best_equal": best_equal,
            "meta_model": meta["model"] if meta else None,
        }
    finally:
        t2.close()


def main():
    coordinator, num_procs, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    mode = sys.argv[4] if len(sys.argv) > 4 else "dp"
    ckpt_dir = sys.argv[5] if len(sys.argv) > 5 else None
    # Cross-process CPU collectives need an explicit implementation on
    # this jax (same fix as tpunet/parallel/dist.py): without gloo the
    # first cross-controller psum raises "Multiprocess computations
    # aren't implemented on the CPU backend".
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_procs,
        process_id=pid,
    )
    assert jax.process_count() == num_procs
    assert jax.device_count() == 4 * num_procs

    from tpunet.config import (CheckpointConfig, DataConfig, MeshConfig,
                               ModelConfig, OptimConfig, TrainConfig)
    from tpunet.data.cifar10 import synthetic_cifar10
    from tpunet.parallel import sync_hosts
    from tpunet.train.loop import Trainer

    if mode == "fsdp_lm":
        cfg, ds = fsdp_lm_case()
    elif mode == "pp_lm":
        cfg, ds = pp_lm_case()
    elif mode == "packed_lm":
        cfg, ds = packed_lm_case()
    else:
        cfg = TrainConfig(
            epochs=1, seed=42,
            data=DataConfig(dataset="synthetic", image_size=32, batch_size=16,
                            rrc_scale=(1.0, 1.0), rrc_ratio=(1.0, 1.0),
                            jitter_brightness=0.0, jitter_contrast=0.0,
                            jitter_saturation=0.0, jitter_hue=0.0,
                            rotation_degrees=0.0),
            model=ModelConfig(dtype="float32", width_mult=0.5),
            optim=OptimConfig(learning_rate=1e-3),
            mesh=MeshConfig(),  # all 8 global devices on the data axis
            checkpoint=CheckpointConfig(save_best=False, save_last=False),
        )
        ds = synthetic_cifar10(n_train=64, n_test=32, seed=7)
    if ckpt_dir:
        # Shared directory from the parent: all controllers join the
        # same multi-host orbax saves (and the round-trip below).
        cfg = cfg.replace(checkpoint=CheckpointConfig(
            directory=ckpt_dir, save_best=True, save_last=True))
    trainer = Trainer(cfg, dataset=ds)
    sync_hosts("start")
    eval0 = trainer.evaluate()
    train1 = trainer.train_one_epoch(0)
    out = {
        "process": pid,
        "world": jax.process_count(),
        "devices": jax.device_count(),
        "eval0": eval0,
        "train1": train1,
    }
    if ckpt_dir:
        out["ckpt"] = _ckpt_roundtrip(trainer, cfg, ds, train1)
    trainer.close()
    print(json.dumps(out), flush=True)
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
