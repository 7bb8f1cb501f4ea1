"""Runner ``train``: the program's ``Trainer`` through its normal path.

The timed path is ``Trainer.train_one_epoch`` itself — the native
loader, its batch iterator, ``shard_host_batch``, ``trainer.train_step``
and the metric accumulation — called on a seeded data set of ``batch x
steps_per_chunk`` rows, so that each call is one chunk of steps closed
by the device fence of its metric fetch. Set-up builds ONE trainer,
hands it the weights made from the seed, and drives it through its
first chunk with the first three steps' inputs and numbers recorded at
the step's boundary; the same trainer then warms one more chunk and
runs the window. The reference stage follows those three steps.
"""

from __future__ import annotations

import math
import os
import time

import numpy as np

from benchmark import harness, trafficgen, weights
from benchmark import runners_common as common

FOLLOWED_STEPS = 3


def build_config(cell: dict, seed: int, workdir: str):
    from tpunet.config import (CheckpointConfig, DataConfig, ModelConfig,
                               OptimConfig, TrainConfig)

    config, traffic = cell["config"], cell["traffic"]
    prog = config["program"]
    model = {**prog["model"], **prog.get(f"{cell['cell']['section']}_model",
                                         {})}
    data = {**prog["data"], "batch_size": traffic["batch"]}
    return TrainConfig(
        seed=int(seed) & 0x7FFFFFFF,
        data=DataConfig(**data), model=ModelConfig(**model),
        optim=OptimConfig(**prog["optim"]),
        checkpoint=CheckpointConfig(
            directory=os.path.join(workdir, "ckpt"), save_best=False,
            save_last=False))


def _adam_mu(opt_state):
    import jax

    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if not found:
        raise SystemExit("the optimizer state holds no first moment (mu)")
    return found[0].mu


def _norms(flat: dict) -> dict:
    import jax.numpy as jnp

    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flat.items()}


def hand_weights(trainer, spec: dict, seed: int) -> None:
    """Replace the program's initial parameters by the seed's."""
    import jax

    old = weights.flatten(trainer.state.params)
    want = {p: tuple(s) for p, (s, _, _) in spec.items()}
    have = {p: tuple(x.shape) for p, x in old.items()}
    if want != have:
        diff = sorted(set(want.items()) ^ set(have.items()))[:4]
        raise SystemExit(f"the reference's parameter tree is not the "
                         f"program's: {diff}")
    shardings = jax.tree_util.tree_map(lambda a: a.sharding,
                                       trainer.state.params)
    kind = type(trainer.state.params)
    made = weights.make_tree(spec, seed)
    made = jax.device_put(kind(made) if kind is not dict else made, shardings)
    trainer.state = trainer.state.replace(params=made)


def follow_first_steps(trainer, spec: dict, seed: int, b1: float) -> dict:
    """Drive the first chunk through the window's own call, recording
    at the step's boundary: the first steps' inputs, each one's loss,
    the gradient norms (Adam's first moment after one step, over
    1 - b1) and the norms of the parameters' change after the last."""
    import jax

    real = trainer.train_step
    cap = {"x": [], "y": [], "key": [], "m": []}
    grad_norms = jax.jit(lambda mu: {
        p: n / (1.0 - b1) for p, n in _norms(weights.flatten(mu)).items()})
    delta_norms = jax.jit(lambda params, key: _norms({
        p: x - weights.make_leaf(key, p, *spec[p])
        for p, x in weights.flatten(params).items()}))

    def recording(state, gx, gy, rng):
        i = len(cap["m"])
        if i < FOLLOWED_STEPS:
            cap["x"].append(np.asarray(gx))
            cap["y"].append(np.asarray(gy))
            cap["key"].append(np.asarray(rng))
        state, m = real(state, gx, gy, rng)
        if i < FOLLOWED_STEPS:
            cap["m"].append(m)
        if i == 0:
            cap["grad_norms"] = grad_norms(_adam_mu(state.opt_state))
            cap["batch_stats"] = {p: np.asarray(v) for p, v in
                                  weights.flatten(state.batch_stats).items()}
        if i == FOLLOWED_STEPS - 1:
            cap["delta_norms"] = delta_norms(state.params,
                                             weights.seed_key(seed))
        return state, m

    trainer.train_step = recording
    try:
        trainer.train_one_epoch(0)
    finally:
        trainer.train_step = real
    if len(cap["m"]) < FOLLOWED_STEPS:
        raise SystemExit("the first chunk ran fewer steps than are followed")
    return {
        "x": np.stack(cap["x"]), "y": np.stack(cap["y"]),
        "key": np.stack(cap["key"]), "batch_stats": cap["batch_stats"],
        "numbers": {
            "losses": [float(m["loss_sum"]) / float(m["count"])
                       for m in cap["m"]],
            "grad_norms": {p: float(v)
                           for p, v in cap["grad_norms"].items()},
            "delta_norms": {p: float(v)
                            for p, v in cap["delta_norms"].items()}}}


def rows_not_in_dataset(cap_x, cap_y, data_x, data_y) -> int:
    """Rows the loader delivered that are not rows of the seeded data
    set with their own label, plus rows delivered twice."""
    have = {}
    for x, y in zip(data_x, data_y):
        have[x.tobytes()] = int(y)
    bad, seen = 0, set()
    for x, y in zip(cap_x.reshape(-1, *cap_x.shape[2:]), cap_y.reshape(-1)):
        key = x.tobytes()
        if have.get(key) != int(y) or key in seen:
            bad += 1
        seen.add(key)
    return bad


def program(ctx: dict) -> dict:
    import jax

    cell = ctx["cell"]
    config, traffic = cell["config"], cell["traffic"]
    harness.enable_cache(program=True)
    harness.require_chips(cell["entry"]["chips"], ctx["rehearse"])
    from tpunet.train.loop import Trainer
    from tpunet.utils.cache import _COMPILES

    seed = ctx["seed"]
    marks = [("imports", time.time() - ctx["t0"])]
    mark = lambda what: marks.append((what, time.time() - ctx["t0"]))  # noqa: E731
    data = trafficgen.train_data(traffic, config, seed)
    trainer = Trainer(build_config(cell, seed, ctx["workdir"]), dataset=data)
    mark("trainer")
    try:
        ref = harness.load_reference(cell)
        spec = ref.param_spec(config, cell["cell"]["section"])
        hand_weights(trainer, spec, seed)
        mark("weights")
        cap = follow_first_steps(trainer, spec, seed,
                                 config["optimizer"]["b1"])
        np.savez(os.path.join(ctx["workdir"], "capture.npz"),
                 x=cap["x"], y=cap["y"], key=cap["key"],
                 **{"stats:" + p: v for p, v in cap["batch_stats"].items()})
        mark("first chunk")
        numbers = cap["numbers"]
        numbers["rows_not_in_dataset"] = rows_not_in_dataset(
            cap["x"], cap["y"], data[0], data[1])
        epoch = 1
        for _ in range(traffic.get("warm_chunks", 1)):
            trainer.train_one_epoch(epoch)
            epoch += 1
        jax.block_until_ready(trainer.state)
        mark("warm chunks")
        from tpunet.utils.cache import compile_stats_line
        harness.say("set-up seconds at", [(w, round(t, 1)) for w, t in marks],
                    compile_stats_line())

        steps = traffic["steps_per_chunk"]
        items = trafficgen.items_per_step(traffic)
        window = common.window_seconds(ctx)
        wait_hist = trainer.obs.registry.histogram("data_wait_s")
        compiles0 = _COMPILES["programs"]
        chunks, data_wait = [], 0.0
        setup_s = time.time() - ctx["t0"]
        with common.tracing(ctx) as tracer:
            t_open = time.perf_counter()
            while True:
                t1 = time.perf_counter()
                summary = trainer.train_one_epoch(epoch)  # ends in the fence
                t2 = time.perf_counter()
                chunks.append((t2 - t1, summary["loss"], summary["count"]))
                data_wait += wait_hist.total
                epoch += 1
                if t2 - t_open >= window:
                    break
            elapsed = t2 - t_open
        compiles = _COMPILES["programs"] - compiles0
        stats = jax.devices()[0].memory_stats() or {}
        harness.say("memory_stats", stats)
        device = harness.device_record()
    finally:
        trainer.close()

    bad = sum(1 for _, loss, _ in chunks if not math.isfinite(loss))
    numbers["nonfinite_window_losses"] = bad
    chips = cell["entry"]["chips"]
    host = {
        "items_per_s": len(chunks) * steps * items / elapsed / chips,
        "step_ms": 1e3 * harness.median([c[0] / steps for c in chunks]),
        "data_wait_pct": 100.0 * data_wait / elapsed,
        "compiles_in_window": compiles,
        "peak_hbm_pct": common.hbm_pct(stats),
        "batch": traffic["batch"], "seq_len": traffic.get("seq_len", 1),
    }
    metrics = {"train_items_per_s": host["items_per_s"], "setup_s": setup_s}
    result = {"attempted": len(chunks) * steps, "failed": bad * steps,
              "numbers": numbers, "device": device, "metrics": metrics,
              "window_s": elapsed, "chunks": len(chunks)}
    common.close_result(ctx, result, host, tracer, elapsed)
    harness.say(f"window {elapsed:.3f} s, {len(chunks)} chunks of {steps} "
                f"steps, setup {setup_s:.1f} s, compiles in window "
                f"{compiles}, metrics {result['metrics']}")
    return result


def reference(ctx: dict, prog: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark.reference import _numerics as N

    cell = ctx["cell"]
    config, section = cell["config"], cell["cell"]["section"]
    harness.enable_cache(program=False)
    harness.require_chips(cell["entry"]["chips"], ctx["rehearse"])
    ref = harness.load_reference(cell)
    cap = np.load(os.path.join(ctx["workdir"], "capture.npz"))
    xs, ys = jnp.asarray(cap["x"]), jnp.asarray(cap["y"])
    keys = jnp.asarray(cap["key"])
    t = time.time()
    with jax.default_matmul_precision("highest"):
        params = ref.make_params(config, section, ctx["seed"])
        want = N.three_steps(
            ref.loss_and_grads_fn(config, section, "float32"), params,
            xs, ys, keys, config["optimizer"])
        numbers = N.train_numbers(prog["numbers"], want)
        stats = {k[len("stats:"):]: cap[k] for k in cap.files
                 if k.startswith("stats:")}
        tables = {}
        if stats:
            ref_stats = {k: (np.asarray(m), np.asarray(v)) for k, (m, v) in
                         ref.batch_stats_fn(config, section, "float32")(
                             params, xs[0], keys[0]).items()}
            tables["bn_var_gaps"] = N.batch_var_gaps(
                N.batch_var_after_one_step(stats, config["batch_norm"]),
                ref_stats)
            numbers["bn_var_gap_shallow"] = max(
                tables["bn_var_gaps"][k]
                for k in cell["cell"]["shallow_bn_layers"])
        for extra in ("rows_not_in_dataset", "nonfinite_window_losses"):
            numbers[extra] = prog["numbers"][extra]
        harness.say(f"reference: {FOLLOWED_STEPS} steps in "
                    f"{time.time() - t:.1f} s, losses {want['losses']} "
                    f"program {prog['numbers']['losses']}")
        correct = common.compare(numbers, cell["cell"].get("limits", {}))
        out = {"correct": correct, "numbers": numbers, **tables,
               "leaf_gaps": N.leaf_gap_table(prog["numbers"], want)}
        if ctx["control"]:
            low = N.three_steps(
                ref.loss_and_grads_fn(config, section, ctx["control"]),
                params, xs, ys, keys, config["optimizer"])
            out["control"] = N.train_numbers(low, want)
            if stats:
                low_stats = {k: (None, np.asarray(v)) for k, (m, v) in
                             ref.batch_stats_fn(config, section,
                                                ctx["control"])(
                                 params, xs[0], keys[0]).items()}
                out["control_bn_var_gaps"] = N.batch_var_gaps(
                    {k: v for k, (_, v) in low_stats.items()}, ref_stats)
                out["control"]["bn_var_gap_shallow"] = max(
                    out["control_bn_var_gaps"][k]
                    for k in cell["cell"]["shallow_bn_layers"])
            out["control_leaf_gaps"] = N.leaf_gap_table(low, want)
            for k, v in out["control"].items():
                harness.say(f"control {ctx['control']} {k} = {v!r}")
    return out
