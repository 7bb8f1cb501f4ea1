"""Multi-host bootstrap (replaces mpi4py + torch.distributed rendezvous).

The reference boots with mpirun -> MPI.COMM_WORLD rank discovery
(cifar10_mpi_mobilenet_224.py:24-26) -> env-var TCP rendezvous with a
hardcoded localhost:29500 master (:28-35) -> NCCL process group. The JAX
equivalent is a single :func:`jax.distributed.initialize` call: on TPU
pods the coordinator and process topology come from the platform
metadata, so no addresses are hardcoded; on CPU/GPU clusters they can be
passed explicitly or via standard env vars (JAX_COORDINATOR_ADDRESS,
JAX_NUM_PROCESSES, JAX_PROCESS_ID).

`rank % device_count` device binding (:38-40) has no analogue — JAX owns
local devices automatically. `dist.barrier()` gating the dataset download
(:102) maps to :func:`sync_hosts`.
"""

from __future__ import annotations

import os
from typing import Optional

import jax


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Initialize multi-controller JAX when running as part of a pod/cluster.

    Safe to call unconditionally: a no-op for single-process runs unless
    explicit arguments or JAX_* rendezvous env vars are present.
    """
    env = os.environ
    configured = (coordinator_address or num_processes
                  or env.get("JAX_COORDINATOR_ADDRESS")
                  or env.get("JAX_NUM_PROCESSES"))
    # jax resolves only JAX_COORDINATOR_ADDRESS itself;
    # num_processes/process_id would fall through to cluster
    # auto-detection and fail on a plain CPU gang — resolve the env
    # vars here so the elastic agent's injected world (and the
    # docstring's claim) actually works.
    if num_processes is None and env.get("JAX_NUM_PROCESSES"):
        num_processes = int(env["JAX_NUM_PROCESSES"])
    if process_id is None and env.get("JAX_PROCESS_ID"):
        process_id = int(env["JAX_PROCESS_ID"])
    # Multi-host TPU pod: TPU_WORKER_HOSTNAMES lists >1 worker. (A
    # single-host TPU VM also sets the variable; initialize() is neither
    # needed nor safe there if the backend was already touched.)
    workers = env.get("TPU_WORKER_HOSTNAMES", "")
    on_tpu_pod = ("," in workers
                  or env.get("MEGASCALE_COORDINATOR_ADDRESS"))
    if not (configured or on_tpu_pod):
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def process_index() -> int:
    """This process's rank (reference `rank`, :25)."""
    return jax.process_index()


def process_count() -> int:
    """World size (reference `world_size`, :26)."""
    return jax.process_count()


def sync_hosts(name: str = "barrier") -> None:
    """Cross-host barrier (reference dist.barrier(), :102)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)


# -- collective-free host agreements -----------------------------------
#
# ``process_allgather`` is an XLA computation: when the MAIN thread
# runs one while the async checkpoint WORKER thread is inside one of
# orbax's cross-host barriers (sync_global_devices), the two
# processes' collective sequences interleave differently and the
# transport aborts (observed on CPU gangs as gloo's
# "op.preamble.length <= op.nbytes" hard abort mid-save). Host-side
# agreements that can overlap async checkpointing therefore go
# through the jax coordination-service KV store instead — plain gRPC
# to the coordinator, no XLA, safe from any thread.


def coordination_client():
    """The jax coordination-service client, or None (single process /
    distributed not initialized)."""
    from jax._src import distributed
    return distributed.global_state.client


_AGREE_TIMEOUT_MS = 300_000


def agree_any(tag: str, flag: bool, *,
              timeout_ms: int = _AGREE_TIMEOUT_MS) -> Optional[bool]:
    """Cross-process OR of a host-side flag (the preemption/evict stop
    agreement) without XLA collectives. ``tag`` must be unique per
    agreement round and identical across processes (e.g. the global
    step). Returns None when no coordination client exists — the
    caller falls back to ``process_allgather`` (which is then safe:
    no coordination service means no multi-controller orbax either).
    """
    client = coordination_client()
    if client is None:
        return None
    base = f"tpunet_agree/{tag}"
    # allow_overwrite: re-agreement on a reused tag (a second trainer
    # incarnation in one process) must be idempotent, not a KV error.
    client.key_value_set(f"{base}/{jax.process_index()}",
                         "1" if flag else "0", allow_overwrite=True)
    client.wait_at_barrier(f"{base}/barrier", timeout_ms)
    return any(
        client.blocking_key_value_get(f"{base}/{i}", timeout_ms) == "1"
        for i in range(jax.process_count()))


def kv_live_processes(tag: str, *,
                      timeout_ms: int = _AGREE_TIMEOUT_MS
                      ) -> Optional[int]:
    """Epoch-heartbeat liveness via the KV store: how many processes
    checked in for this ``tag``. A dead peer surfaces as a barrier
    error -> count whoever did check in (bounded short gets) instead
    of hanging in a device collective. None without a client."""
    client = coordination_client()
    if client is None:
        return None
    base = f"tpunet_hb/{tag}"
    client.key_value_set(f"{base}/{jax.process_index()}", "1",
                         allow_overwrite=True)
    try:
        client.wait_at_barrier(f"{base}/barrier", timeout_ms)
        return jax.process_count()
    except Exception:
        live = 0
        for i in range(jax.process_count()):
            try:
                client.blocking_key_value_get(f"{base}/{i}", 1000)
                live += 1
            except Exception:
                continue
        return live
