"""The one general traffic generator: a pure function of the traffic
file's parameters and ``--seed``.

Training traffic (``kind: train``) is a data set of ``batch x
steps_per_chunk`` rows that all differ: CIFAR-shaped uint8 images with
labels (``data: cifar_uint8``) or rows of uniform random tokens
(``data: lm_tokens``).

Serving traffic (``kind: serve``) is a list of requests. Every seed is
given the SAME multiset of (prompt length, output length) pairs — the
lognormal's stratified quantiles, clipped, crossed into a pool — pool
after pool, each pool shuffled. The shuffle comes from the seed, or,
where the traffic file gives an ``order_seed``, from that: a closed loop
that finishes ~45 requests in a window does 8 % more or less work with
the order alone, so such a cell fixes the order and lets the seed choose
the tokens. ``arrival:
closed`` hands the list to ``clients`` callers that each wait for their
reply; ``arrival: open`` adds due times from the seed at ``rate``
requests a second, Poisson or in bursts.
"""

from __future__ import annotations

import math
import statistics

import numpy as np


def rng_for(seed: int, salt: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(salt)])


def train_data(traffic: dict, config: dict, seed: int):
    """(train_x, train_y, test_x, test_y) as the trainer takes them."""
    r = rng_for(seed, 1)
    n = traffic["batch"] * traffic["steps_per_chunk"]
    n_test = traffic["batch"]
    if traffic["data"] == "cifar_uint8":
        x = r.integers(0, 256, size=(n + n_test, 32, 32, 3), dtype=np.uint8)
        y = r.integers(0, config["num_classes"],
                       size=n + n_test).astype(np.int32)
    elif traffic["data"] == "lm_tokens":
        x = r.integers(0, config["vocab_size"],
                       size=(n + n_test, traffic["seq_len"])).astype(np.int32)
        y = np.zeros(n + n_test, np.int32)
    else:
        raise ValueError(f"unknown train data {traffic['data']!r}")
    return x[:n], y[:n], x[n:], y[n:]


def items_per_step(traffic: dict) -> int:
    return traffic["batch"] * traffic.get("seq_len", 1)


def _quantile_lengths(spec: dict, n: int):
    """``n`` stratified quantiles of the clipped lognormal, as ints."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        v = math.exp(math.log(spec["median"])
                     + spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(v), spec["min"]), spec["max"])))
    return out


def length_pool(traffic: dict):
    """The fixed multiset of (prompt, output) lengths: every prompt
    quantile with every output quantile."""
    ps = _quantile_lengths(traffic["prompt_len"], traffic["pool_prompt"])
    os_ = _quantile_lengths(traffic["output_len"], traffic["pool_output"])
    return [(p, o) for p in ps for o in os_]


def serve_requests(traffic: dict, config: dict, seed: int, n: int):
    """``n`` requests: dicts with ``prompt`` (int32 tokens),
    ``max_new_tokens`` and, for an open loop, ``due_s``."""
    pool = length_pool(traffic)
    r = rng_for(seed, 2)
    order = rng_for(traffic.get("order_seed", seed), 6)
    lengths = []
    while len(lengths) < n:
        lengths.extend(pool[i] for i in order.permutation(len(pool)))
    reqs = [{"prompt": r.integers(0, config["vocab_size"],
                                  size=p).astype(np.int32),
             "max_new_tokens": int(o)} for p, o in lengths[:n]]
    if traffic["arrival"] == "open":
        for req, due in zip(reqs, arrival_times(traffic, seed, n)):
            req["due_s"] = float(due)
    elif traffic["arrival"] != "closed":
        raise ValueError(f"unknown arrival {traffic['arrival']!r}")
    return reqs


def arrival_times(traffic: dict, seed: int, n: int):
    """Due times of an open loop at ``rate`` requests a second:
    ``poisson`` gaps, or ``bursty`` — the same mean rate with the gaps
    of every ``burst_every``-th stretch of ``burst_len`` requests
    divided by ``burst_factor`` and the rest stretched to keep the
    mean."""
    r = rng_for(seed, 3)
    gaps = r.exponential(1.0 / traffic["rate"], size=n)
    if traffic.get("process", "poisson") == "bursty":
        every, length = traffic["burst_every"], traffic["burst_len"]
        fast = (np.arange(n) % every) < length
        f = traffic["burst_factor"]
        slow = (every - length / f) / (every - length)
        gaps = np.where(fast, gaps / f, gaps * slow)
    elif traffic.get("process", "poisson") != "poisson":
        raise ValueError(f"unknown process {traffic['process']!r}")
    return np.cumsum(gaps)


def sample_indices(n_done: int, longest: int, k: int, seed: int):
    """Which finished requests ``correct`` checks: the longest, and
    ``k - 1`` more drawn from the seed."""
    r = rng_for(seed, 4)
    others = [i for i in range(n_done) if i != longest]
    pick = list(r.permutation(len(others))[:max(0, k - 1)])
    return [longest] + [others[i] for i in pick]
