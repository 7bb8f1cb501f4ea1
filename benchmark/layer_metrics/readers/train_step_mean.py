"""A mean over the run's train steps of a number the step itself
reports beside its loss (``tpunet/train/metrics.py STEP_MEANS``: the two
losses of a multi-token-prediction model, the routing load of its
no-drop expert layers), times ``params["scale"]`` (1 by default):
``STEP_MEAN_TOTALS[params["key"]] / STEP_MEAN_TOTALS["steps"]``, the
process-wide sums the trainer keeps (the reader runs in the program's
process after the trainer is closed, and the runner hands it no
registry). ``None`` — the metric is left out — where the program keeps
no such sums (a checkout older than PR 31) or the steps reported none."""


def step_mean(key: str):
    try:
        from tpunet.train.metrics import STEP_MEAN_TOTALS as totals
    except ImportError:
        return None
    if not totals.get("steps") or key not in totals:
        return None
    return totals[key] / totals["steps"]


def read(obs: dict, params: dict):
    mean = step_mean(params["key"])
    return None if mean is None else params.get("scale", 1.0) * mean
