"""Roofline share (%) of the flash attention kernels in a train
window, forward and backward, for a model whose layers are
sliding-window and position-free attention side by side: the
multiply-adds the algorithm requires of them a step
(``benchmark/opcount_gqa_train.py``, from the configuration's sizes: the
causal band in the windowed layers and the triangle in the global ones,
the forward once more where blocks are recomputed, the backward's five
products to the forward's two) at the chip's peak rate, over the device
time of the operations the trace names ``params["pattern"]``.

A step launches ``kernels`` such operations over its layers, so the
traced steps are the trace's such operations over that count (a step
cut by the window's edge counts by its share). ``None`` — the metric is
left out — without a trace, where the configuration names no
``sliding_window_layout``, or where the trace holds no such operation.
"""

from benchmark import harness, opcount_gqa_train


def read(obs: dict, params: dict):
    trace, host = obs.get("trace"), obs["host"]
    config = obs["cell"]["config"]
    if trace is None or "sliding_window_layout" not in config:
        return None
    kernel_s, kernels = harness.load_reader(
        obs["cell"], "paged_decode_window_roofline").kernel_time(
            trace, params["pattern"])
    if kernel_s <= 0:
        return None
    count = opcount_gqa_train.flash_train(host["seq_len"], config)
    rows = host["batch"] * kernels / count["kernels"]
    least = rows * opcount_gqa_train.roofline_seconds(
        count, harness.peaks_for(obs["device_kind"], obs["cell"]["root"]))
    harness.say(f"flash train (window): {rows:.2f} rows of "
                f"{host['seq_len']} tokens, least {least:.4f} s, kernels "
                f"{kernel_s:.4f} s")
    return 100.0 * least / kernel_s
