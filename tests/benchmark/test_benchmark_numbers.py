"""The arithmetic the yardstick rests on: the worst-leaf and global
gaps, percentiles, the operation counts and the weights' seeding."""

import json
import os

import numpy as np
import pytest

import bench_tiny_root
from benchmark import harness, opcount, weights
from benchmark.reference import _numerics as N

REPO = bench_tiny_root.REPO


def test_worst_leaf_gap_is_a_gap_of_norms_against_leaf_or_median():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    gap, leaf = N.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 0.5}, ref)
    assert leaf == "c" and gap == pytest.approx(0.5 / 1.0)   # median leaf = 1
    gap, leaf = N.worst_leaf_gap({"a": 1.0, "b": 1.0, "c": 1e-9}, ref)
    assert leaf == "b" and gap == pytest.approx(0.5)
    assert N.worst_leaf_gap({"a": 1.0}, ref)[0] == float("inf")
    assert N.worst_leaf_gap({**ref, "a": float("nan")}, ref)[0] == float("inf")


def test_global_gap_and_train_numbers():
    ref = {"losses": [2.0, 2.0, 2.0], "grad_norms": {"a": 3.0, "b": 4.0},
           "delta_norms": {"a": 3.0, "b": 4.0}}
    same = N.train_numbers(ref, ref)
    assert all(v == 0 for k, v in same.items() if not k.endswith("_leaf"))
    zero = {**ref, "delta_norms": {"a": 0.0, "b": 0.0},
            "losses": [2.0, 2.2, float("nan")]}
    out = N.train_numbers(zero, ref)
    assert out["delta_norm_gap_global"] == pytest.approx(1.0)
    assert out["delta_norm_gap"] == pytest.approx(1.0)
    assert out["loss_gap_step2"] == pytest.approx(0.1)
    assert out["loss_gap_step3"] == float("inf")
    assert N.global_gap({"a": 0.0, "b": 5.0}, ref["grad_norms"]) == 0.0


def test_percentile_matches_numpy():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 25, 50, 95, 100):
        assert harness.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)))
    with pytest.raises(ValueError):
        harness.percentile([], 50)


def test_opcounts_match_the_published_models():
    gpt2 = harness.load_json("benchmark", "configs", "gpt2-xl.json", root=REPO)
    per_token = opcount.causal_lm_train_step(gpt2, 8, 1024, 12)["flops"] / 8192
    assert per_token == pytest.approx(2.812e9, rel=1e-3)
    mnv2 = harness.load_json("benchmark", "configs", "mobilenetv2-224.json",
                             root=REPO)
    convs = opcount.mobilenetv2_convs(mnv2)
    assert len(convs) == 52                        # stem + 50 in blocks + head
    fwd = sum(2.0 * k * k * (ci // g) * co * (h // s) ** 2
              for k, ci, co, g, h, s in convs)
    assert fwd == pytest.approx(0.6e9, rel=0.05)   # ~300 M multiply-adds
    need = opcount.mobilenetv2_train_step(mnv2, 128)
    assert need["bytes"] / 128 == pytest.approx(80.8e6, rel=1e-2)


def test_peak_bytes_counts_reserved_program_memory():
    assert harness.peak_bytes({"peak_bytes_in_use": 10, "bytes_in_use": 8,
                               "peak_bytes_reserved": 5}) == 13
    assert harness.peak_bytes({"peak_bytes_in_use": 20, "bytes_in_use": 8,
                               "peak_bytes_reserved": 5}) == 20
    assert harness.peak_bytes({}) == 0


def test_weights_depend_only_on_seed_and_path():
    import jax
    import jax.numpy as jnp

    spec = {"block00/k": ((3, 4), "normal", 0.5),
            "block01/k": ((3, 4), "normal", 0.5),
            "ln/scale": ((4,), "ones", 0.0), "ln/bias": ((4,), "zeros", 0.0)}
    big = 2 ** 31 + 77
    a = weights.flatten(weights.make_tree(spec, big))
    b = weights.flatten(weights.make_tree(dict(reversed(spec.items())), big))
    c = weights.flatten(weights.make_tree(spec, big + 1))
    assert all(np.array_equal(a[p], b[p]) for p in spec)
    assert not np.array_equal(a["block00/k"], c["block00/k"])
    assert not np.array_equal(a["block00/k"], a["block01/k"])
    stacked = jax.jit(lambda k: weights.make_stacked(
        k, "k", 2, (3, 4), "normal", 0.5))(weights.seed_key(big))
    assert np.array_equal(stacked[0], a["block00/k"])
    assert np.array_equal(stacked[1], a["block01/k"])
    assert float(jnp.std(weights.make_leaf(big, "x", (4000,), "normal",
                                           0.5))) == pytest.approx(0.5, rel=0.1)
    assert weights.nest({"a/b": 1, "a/c": 2}) == {"a": {"b": 1, "c": 2}}


def test_the_fp8_control_rounds_forward_and_backward():
    import jax
    import jax.numpy as jnp

    x = jnp.linspace(-1.0, 1.0, 101)
    q = N.quant(x, "fp8")
    assert float(jnp.max(jnp.abs(q - x))) > 1e-3          # coarser than bf16
    assert float(jnp.max(jnp.abs(q - x))) < 0.07
    g = jax.grad(lambda x: jnp.sum(N.quant(x, "fp8") * x))(x)
    exact = jax.grad(lambda x: jnp.sum(x * x))(x)
    assert 1e-3 < float(jnp.max(jnp.abs(g - exact))) < 0.3
    assert N.quant(x, "float32") is x
    with pytest.raises(ValueError):
        N.quant(x, "int3")


def test_config_program_sections_state_the_published_sizes():
    gpt2 = harness.load_json("benchmark", "configs", "gpt2-xl.json", root=REPO)
    m = gpt2["program"]["model"]
    assert (m["vit_hidden"], m["vit_depth"], m["vit_heads"], m["vocab_size"],
            m["max_seq_len"]) == (gpt2["n_embd"], gpt2["n_layer"],
                                  gpt2["n_head"], gpt2["vocab_size"],
                                  gpt2["n_positions"])
    assert (gpt2["n_embd"], gpt2["n_layer"], gpt2["n_head"]) == (1600, 48, 25)
    assert gpt2["program"]["train_model"]["vit_depth"] == \
        gpt2["train"]["overrides"]["n_layer"]
    assert json.dumps(gpt2["reduced"]) == '["n_layer", "layer_norm_epsilon"]'
    mnv2 = harness.load_json("benchmark", "configs", "mobilenetv2-224.json",
                             root=REPO)
    assert mnv2["reduced"] == [] and mnv2["width_mult"] == 1.0
    assert mnv2["program"]["model"]["dropout_rate"] == mnv2["dropout_rate"]
    assert os.path.exists(os.path.join(REPO, "benchmark", "reference",
                                       "mobilenetv2-224.py"))
