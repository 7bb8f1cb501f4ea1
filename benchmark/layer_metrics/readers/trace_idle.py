"""Device idle share: 1 - union of device-op intervals / traced window."""

from benchmark import trace_reduce


def read(obs: dict, params: dict):
    if obs.get("trace") is None:
        return None
    return trace_reduce.idle_pct(obs["trace"], obs["window_s"])
