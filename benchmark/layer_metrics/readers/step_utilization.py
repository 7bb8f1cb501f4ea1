"""Step-level utilization, not a kernel's roofline share: the measured
items per second over what the chip could reach on the operations and
bytes the algorithm needs (``benchmark/opcount.py``), from the peaks in
``benchmark/peaks.json``. ``model_flops``: FLOPs per item x items/s over
the bf16 peak. ``two_resource_roofline``: items/s over 1 / max(FLOPs
per item / peak FLOP/s, bytes per item / peak bytes/s)."""

from benchmark import harness, opcount


def read(obs: dict, params: dict):
    host, config = obs["host"], obs["cell"]["config"]
    util = config.get("utilization")
    if not util or host.get("items_per_s") is None:
        return None
    peaks = harness.peaks_for(obs["device_kind"], obs["cell"]["root"])
    fn = opcount.OPCOUNTS[util["opcount"]]
    if util["kind"] == "model_flops":
        section = config.get(obs["cell"]["cell"]["section"], {})
        n_layer = section.get("overrides", {}).get("n_layer",
                                                   config["n_layer"])
        need = fn(config, host["batch"], host["seq_len"], n_layer)
        per_item = need["flops"] / (host["batch"] * host["seq_len"])
        return 100.0 * per_item * host["items_per_s"] \
            / peaks["bf16_flops_per_s"]
    if util["kind"] == "two_resource_roofline":
        need = fn(config, host["batch"])
        least = max(need["flops"] / peaks["bf16_flops_per_s"],
                    need["bytes"] / peaks["hbm_bytes_per_s"])
        return 100.0 * host["items_per_s"] * least / host["batch"]
    raise ValueError(f"unknown utilization kind {util['kind']!r}")
