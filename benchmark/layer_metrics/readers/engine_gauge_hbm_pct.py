"""A gauge of the serve engine's registry, in bytes, as a share (%) of
the device's memory: ``params["gauge"]`` / ``bytes_limit``.

``obs`` carries neither the engine nor the device's memory statistics,
and the runner may not be edited, so this reader takes both from the
frame of ``runners/serve.py``'s ``program`` that called it
(``serve_window_counts.runner_locals``): ``engine`` (its ``registry``)
and ``stats`` (``memory_stats()`` read when the window had closed).
``None`` — the metric is left out — without that frame, without a
``bytes_limit``, or where the program sets no such gauge.
"""

from benchmark import harness


def read(obs: dict, params: dict):
    have = harness.load_reader(obs["cell"],
                               "serve_window_counts").runner_locals()
    if have is None:
        return None
    limit = (have.get("stats") or {}).get("bytes_limit")
    value = have["engine"].registry.snapshot().get(params["gauge"])
    if not limit or value is None:
        return None
    return 100.0 * float(value) / float(limit)
