"""Roofline share (%) of the width-1 paged-decode kernel over a serve
window, for a model whose layers are sliding-window and full attention
side by side: the least time the chip could take for the keys the
window's decode steps had in sight (``benchmark/opcount_gqa_window.py``,
from shapes) over the device time of the operations the trace names
``params["pattern"]``.

Which keys: a request's token j (j >= 1; token 0 is its prefill's) was
produced by a decode step whose row held ``prompt + j`` keys. The
requests and the window's edges come from the frame of
``runners/serve.py``'s ``program``, the way ``paged_decode_roofline``
takes them. ``None`` — the metric is left out — without a trace, without
that frame, where the configuration names no ``sliding_window``, or
where the trace holds no such operation (a program without the kernel,
the parent commit).
"""

import re

from benchmark import harness, opcount_gqa_window


def kernel_time(trace, pattern: str):
    """``(seconds, operations)`` of the operations the trace names
    ``pattern``, averaged over the devices."""
    rx = re.compile(pattern)
    ops = [d for dev in trace.device_ops.values()
           for name, _, d in dev if rx.search(name)]
    devices = max(1, len(trace.device_ops))
    return sum(ops) / devices, len(ops) / devices


def read(obs: dict, params: dict):
    trace = obs.get("trace")
    config = obs["cell"]["config"]
    if trace is None or "sliding_window" not in config:
        return None
    have = harness.load_reader(obs["cell"],
                               "serve_window_counts").runner_locals()
    if have is None:
        return None
    kernel_s, _ = kernel_time(trace, params["pattern"])
    if kernel_s <= 0:
        return None
    load, t_open, t_close = have["load"], have["t_open"], have["t_close"]
    contexts = [len(load.requests[r["index"]]["prompt"]) + j
                for r in load.records
                for j, t in enumerate(r["token_t"])
                if j and t_open <= t < t_close]
    if not contexts:
        return None
    count = opcount_gqa_window.paged_decode(contexts, config)
    least = opcount_gqa_window.roofline_seconds(
        count, harness.peaks_for(obs["device_kind"]))
    harness.say(f"paged decode (window): {count['rows']} rows, "
                f"{count['keys']} keys in sight over the layers, least "
                f"{least:.4f} s, kernel {kernel_s:.4f} s")
    return 100.0 * least / kernel_s
