"""Roofline share (%) of the flash forward kernel in a serve window's
bucket-wide (prefill) programs, for a model whose layers are
sliding-window and full attention side by side: the multiply-adds of
causal attention over what each query may see
(``benchmark/opcount_gqa_window.py``, from shapes) at the chip's peak
rate, over the device time of the operations the trace names
``params["pattern"]``.

Only the bucket-wide programs hold that kernel, each execution one
kernel a layer over the whole bucket (a prompt shorter than the bucket
is padded, and the kernel computes the tail: the count is of the bucket,
as the kernel is asked). So the executions are the trace's such
operations over the layers, and the bucket is the cell's one
``prefill_buckets`` entry. ``None`` — the metric is left out — without a
trace, where the configuration names no ``sliding_window``, where the
cell has more than one bucket, or where the trace holds no such
operation (the parent commit on a cell without the kernel).
"""

from benchmark import harness, opcount_gqa_window


def read(obs: dict, params: dict):
    trace = obs.get("trace")
    config = obs["cell"]["config"]
    buckets = obs["cell"]["cell"]["program"]["serve"].get(
        "prefill_buckets", [])
    if trace is None or "sliding_window" not in config or len(buckets) != 1:
        return None
    kernel_s, kernels = harness.load_reader(
        obs["cell"], "paged_decode_window_roofline").kernel_time(
            trace, params["pattern"])
    if kernel_s <= 0:
        return None
    count = opcount_gqa_window.flash_prefill(buckets[0], config)
    calls = kernels / count["kernels"]
    least = calls * opcount_gqa_window.roofline_seconds(
        count, harness.peaks_for(obs["device_kind"]))
    harness.say(f"flash prefill (window): {calls:.2f} calls of "
                f"{buckets[0]} tokens, least {least:.4f} s, kernel "
                f"{kernel_s:.4f} s")
    return 100.0 * least / kernel_s
