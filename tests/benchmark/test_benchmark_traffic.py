"""The traffic generator is a pure function of its file and the seed,
respects its clips, and gives every seed the same amount of work."""

import numpy as np
import pytest

import bench_tiny_root
from benchmark import harness, trafficgen

REPO = bench_tiny_root.REPO
SERVE = harness.load_json("benchmark", "traffic", "serve-closed16.json",
                          root=REPO)
GPT2 = harness.load_json("benchmark", "configs", "gpt2-xl.json", root=REPO)
MNV2 = harness.load_json("benchmark", "configs", "mobilenetv2-224.json",
                         root=REPO)
OPEN = {**SERVE, "arrival": "open", "rate": 5.0}
BIG = 2 ** 31 + 12345


@pytest.mark.parametrize("traffic", [SERVE, OPEN], ids=["closed", "open"])
def test_requests_are_a_pure_function_of_the_seed(traffic):
    a = trafficgen.serve_requests(traffic, GPT2, BIG, 200)
    b = trafficgen.serve_requests(traffic, GPT2, BIG, 200)
    c = trafficgen.serve_requests(traffic, GPT2, BIG + 1, 200)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["max_new_tokens"] == y["max_new_tokens"]
               and x.get("due_s") == y.get("due_s") for x, y in zip(a, b))
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, c))


@pytest.mark.parametrize("traffic", [SERVE, OPEN], ids=["closed", "open"])
def test_lengths_respect_their_clips_and_token_range(traffic):
    reqs = trafficgen.serve_requests(traffic, GPT2, 7, 300)
    p, o = traffic["prompt_len"], traffic["output_len"]
    for r in reqs:
        assert p["min"] <= len(r["prompt"]) <= p["max"]
        assert o["min"] <= r["max_new_tokens"] <= o["max"]
        assert r["prompt"].dtype == np.int32
        assert 0 <= r["prompt"].min() and r["prompt"].max() < 50257
        assert len(r["prompt"]) + r["max_new_tokens"] <= GPT2["n_positions"]


def test_a_fixed_order_seed_leaves_the_seed_only_the_tokens():
    assert "order_seed" in SERVE
    a = trafficgen.serve_requests(SERVE, GPT2, 1, 40)
    b = trafficgen.serve_requests(SERVE, GPT2, BIG, 40)
    assert [(len(x["prompt"]), x["max_new_tokens"]) for x in a] == [
        (len(x["prompt"]), x["max_new_tokens"]) for x in b]
    free = {k: v for k, v in SERVE.items() if k != "order_seed"}
    c = trafficgen.serve_requests(free, GPT2, 1, 40)
    d = trafficgen.serve_requests(free, GPT2, BIG, 40)
    assert [len(x["prompt"]) for x in c] != [len(x["prompt"]) for x in d]


def test_every_seed_gets_the_same_multiset_of_lengths():
    pool = sorted(trafficgen.length_pool(SERVE))
    n = SERVE["pool_prompt"] * SERVE["pool_output"]
    assert len(pool) == n == 16
    prompts = sorted({p for p, _ in pool})
    assert prompts[0] >= 32 and prompts[-1] <= 512
    assert prompts[1] < 192 < prompts[2]                  # median 192 between
    for seed in (1, BIG):
        reqs = trafficgen.serve_requests(SERVE, GPT2, seed, 3 * n)
        for lo in (0, n, 2 * n):
            got = sorted((len(r["prompt"]), r["max_new_tokens"])
                         for r in reqs[lo:lo + n])
            assert got == pool


@pytest.mark.parametrize("process", ["poisson", "bursty"])
def test_open_arrivals_keep_their_rate(process):
    t = {**OPEN, "process": process, "burst_every": 20, "burst_len": 5,
         "burst_factor": 4.0}
    due = trafficgen.arrival_times(t, 11, 4000)
    assert np.all(np.diff(due) >= 0)
    assert abs(4000 / due[-1] - t["rate"]) / t["rate"] < 0.08
    assert np.array_equal(due, trafficgen.arrival_times(t, 11, 4000))
    if process == "bursty":
        gaps = np.diff(np.concatenate([[0.0], due]))
        fast = (np.arange(4000) % 20) < 5
        assert gaps[fast].mean() < 0.5 * gaps[~fast].mean()


@pytest.mark.parametrize("name,config", [("train-b128", MNV2),
                                         ("train-b8-t1024", GPT2)])
def test_train_rows_are_seeded_and_all_differ(name, config):
    traffic = harness.load_json("benchmark", "traffic", f"{name}.json",
                                root=REPO)
    x, y, tx, ty = trafficgen.train_data(traffic, config, BIG)
    x2, y2, _, _ = trafficgen.train_data(traffic, config, BIG)
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    n = traffic["batch"] * traffic["steps_per_chunk"]
    assert len(x) == len(y) == n and len(tx) == traffic["batch"]
    assert len({row.tobytes() for row in x}) == n
    assert trafficgen.items_per_step(traffic) == (
        traffic["batch"] * traffic.get("seq_len", 1))


def test_sample_holds_the_longest_and_is_seeded():
    a = trafficgen.sample_indices(50, 17, 6, BIG)
    assert a[0] == 17 and len(set(a)) == 6
    assert a == trafficgen.sample_indices(50, 17, 6, BIG)
    assert trafficgen.sample_indices(1, 0, 6, 3) == [0]
