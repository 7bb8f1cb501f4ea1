"""Median duration, in ms, of the host spans named ``params["span"]``."""

from benchmark import harness, trace_reduce


def read(obs: dict, params: dict):
    if obs.get("trace") is None:
        return None
    durations = trace_reduce.span_durations(obs["trace"], params["span"])
    return 1e3 * harness.median(durations) if durations else None
