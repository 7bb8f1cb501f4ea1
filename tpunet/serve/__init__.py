"""Production inference serving (``python -m tpunet.serve``).

The reference ships serving as a single-request Gradio demo
(GROUP03.pdf pp. 22-23; ``tpunet/infer/app.py`` keeps that shape as
the parity artifact). This package is the heavy-traffic path the
ROADMAP north star asks for — on TPU that means ONE resident jitted
decode program amortized across many in-flight requests instead of a
compiled forward per request:

- ``engine``    — continuous batching over a fixed pool of KV-cache
  slots: requests are admitted into free slots, prefilled through a
  bucketed chunked-prefill program, then decoded TOGETHER every
  iteration with per-slot positions and active masks; new requests
  join mid-flight, finished ones free their slot, and the compile
  count is bounded at 1 decode + len(prefill_buckets) programs.
- ``scheduler`` — bounded FIFO admission with backpressure (reject
  with queue-full rather than grow latency), per-request deadlines and
  cooperative cancellation.
- ``classify``  — micro-batched classifier path: concurrent
  ``/v1/classify`` requests coalesce into one jitted batched forward.
- ``frontend``  — stdlib-only threaded HTTP server: ``/v1/generate``
  (optionally streamed as ndjson), ``/v1/classify``, ``/healthz``,
  ``/metrics``; graceful drain on SIGTERM.

SLO metrics (serve_* counters/gauges/histograms, ``obs_serve``
records) flow through the existing ``tpunet/obs`` registry, sinks and
exporters — docs/serving.md and docs/metrics_schema.md document the
contract.
"""

from __future__ import annotations

from tpunet.serve.classify import ClassifyBatcher
from tpunet.serve.engine import Engine, PromptTooLongError
from tpunet.serve.frontend import ServeServer
from tpunet.serve.scheduler import (DrainingError, GenerateRequest,
                                    QueueFullError, RequestQueue)

__all__ = [
    "ClassifyBatcher", "DrainingError", "Engine", "GenerateRequest",
    "PromptTooLongError", "QueueFullError", "RequestQueue",
    "ServeServer",
]
