"""Share of the devices' operation time in operations whose text matches
one of ``params["patterns"]`` (regular expressions, data in the metric's
file)."""

from benchmark import trace_reduce


def read(obs: dict, params: dict):
    if obs.get("trace") is None:
        return None
    return trace_reduce.pattern_share_pct(obs["trace"], params["patterns"])
