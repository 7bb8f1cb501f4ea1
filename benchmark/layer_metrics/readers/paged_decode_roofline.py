"""Roofline share (%) of the width-1 paged-decode kernel over a serve
window: the least time the chip could take for the keys the window's
decode steps attended to (``benchmark/opcount_hybrid.py``, from shapes)
over the device time of the operations the trace names
``params["pattern"]``.

Which keys: a request's token j (j >= 1; token 0 is its prefill's) was
produced by a decode step that attended to ``prompt + j`` keys in each
full-attention layer. The requests and the window's edges are not in
``obs`` and the runner may not be edited, so they come from the frame of
``runners/serve.py``'s ``program``, the way ``serve_window_counts``
takes them. ``None`` — the metric is left out — without a trace, without
that frame, or where the trace holds no such operation (a program
without the kernel, the parent commit).
"""

import re

from benchmark import harness, opcount_hybrid


def read(obs: dict, params: dict):
    trace = obs.get("trace")
    if trace is None:
        return None
    have = harness.load_reader(obs["cell"],
                               "serve_window_counts").runner_locals()
    if have is None:
        return None
    rx = re.compile(params["pattern"])
    kernel_s = sum(d for ops in trace.device_ops.values()
                   for name, _, d in ops if rx.search(name))
    kernel_s /= max(1, len(trace.device_ops))
    if kernel_s <= 0:
        return None
    load, t_open, t_close = have["load"], have["t_open"], have["t_close"]
    live = rows = 0
    for r in load.records:
        prompt = len(load.requests[r["index"]]["prompt"])
        for j, t in enumerate(r["token_t"]):
            if j and t_open <= t < t_close:
                live, rows = live + prompt + j, rows + 1
    if not rows:
        return None
    config = obs["cell"]["config"]
    count = opcount_hybrid.paged_decode_gqa(live, rows, config)
    least = opcount_hybrid.full_attention_layers(config) \
        * opcount_hybrid.roofline_seconds(
            count, harness.peaks_for(obs["device_kind"]))
    harness.say(f"paged decode: {rows} rows over {live} live keys a layer, "
                f"least {least:.4f} s, kernel {kernel_s:.4f} s")
    return 100.0 * least / kernel_s
