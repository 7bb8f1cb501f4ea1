"""A gauge of the serve engine's registry, as the engine set it:
``params["gauge"]``.

``obs`` does not carry the engine and the runner may not be edited, so
this reader takes it from the frame of ``runners/serve.py``'s
``program`` that called it (``serve_window_counts.runner_locals``).
``None`` — the metric is left out — without that frame or where the
program sets no such gauge (the parent commit).
"""

from benchmark import harness


def read(obs: dict, params: dict):
    have = harness.load_reader(obs["cell"],
                               "serve_window_counts").runner_locals()
    if have is None:
        return None
    value = have["engine"].registry.snapshot().get(params["gauge"])
    return None if value is None else float(value)
