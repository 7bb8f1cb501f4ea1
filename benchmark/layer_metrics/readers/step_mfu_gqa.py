"""Model FLOP/s utilization of a train step of the grouped-query
decoder with windowed and global layers and no-drop experts:
matrix-product FLOPs a token (``benchmark/opcount_gqa_train.py``: what
this chip holds, scores and values at the pairs a query may see, the
held share of the routed pairs as the run's own steps counted it,
forward + 2x backward, recomputation not counted) x the measured tokens
per second over the bf16 peak of ``benchmark/peaks.json``. Step-level,
not a kernel's roofline share. ``None`` where the run counted no
routing load (a program without the counter) or timed nothing."""

from benchmark import harness, opcount_gqa_train


def read(obs: dict, params: dict):
    host, config = obs["host"], obs["cell"]["config"]
    share = harness.load_reader(obs["cell"], "train_step_mean").step_mean(
        "moe_held_pair_share")
    if share is None or host.get("items_per_s") is None:
        return None
    peaks = harness.peaks_for(obs["device_kind"], obs["cell"]["root"])
    per_token = opcount_gqa_train.train_flops_per_token(
        config, host["seq_len"], share)
    return 100.0 * per_token * host["items_per_s"] / peaks["bf16_flops_per_s"]
