"""MoE MLP: routing invariants, aux loss, trainer integration, and
expert-parallel parity on the 8-device CPU mesh — including the GShard
all_to_all capacity-buffer dispatch vs the replicated-routing psum
lowering."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from tpunet.config import (CheckpointConfig, DataConfig, MeshConfig,
                           ModelConfig, OptimConfig, TrainConfig)
from tpunet.models import create_model, init_variables
from tpunet.models.moe import MoeMlp, moe_apply, resolve_moe_dispatch
from tpunet.train.loop import Trainer

MOE_CFG = ModelConfig(name="vit", vit_patch=4, vit_hidden=64, vit_depth=2,
                      vit_heads=4, dropout_rate=0.0, dtype="float32",
                      moe_experts=4, moe_every=2)


def _moe(experts=4, top_k=2, cap=1.25, dtype=jnp.float32):
    m = MoeMlp(experts, 128, top_k=top_k, capacity_factor=cap, dtype=dtype)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, 32)),
                    dtype)
    variables = m.init(jax.random.PRNGKey(0), x)
    return m, {"params": variables["params"]}, x


@pytest.mark.slow
def test_output_shape_and_dtype():
    m, variables, x = _moe()
    y = m.apply(variables, x)
    assert y.shape == x.shape and y.dtype == x.dtype


@pytest.mark.slow
def test_output_finite_with_ample_capacity():
    m, variables, x = _moe(cap=4.0)
    y = m.apply(variables, x)
    assert np.isfinite(np.asarray(y)).all()


def test_aux_loss_sown_and_bounded():
    m, variables, x = _moe(cap=4.0)
    y, mutated = m.apply(variables, x, mutable=["losses"])
    (aux,) = jax.tree_util.tree_leaves(mutated["losses"])
    # Perfectly balanced routing gives exactly 1.0; anything else > 1.
    assert float(aux) >= 1.0 - 1e-5
    assert float(aux) < m.num_experts + 1e-5


@pytest.mark.slow
def test_single_expert_topk1_is_dense_mlp_through_router():
    """One expert, ample capacity: every token goes to expert 0 with
    gate 1.0, so the MoE output is a plain (batched) MLP of its single
    expert's weights."""
    m, variables, x = _moe(experts=1, top_k=1, cap=8.0)
    y = m.apply(variables, x)
    p = variables["params"]
    h = jax.nn.gelu(x @ p["wi"][0] + p["bi"][0])
    ref = h @ p["wo"][0] + p["bo"][0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_capacity_drops_tokens_but_stays_finite():
    m, variables, x = _moe(cap=0.1)  # tiny capacity -> heavy drops
    y = m.apply(variables, x)
    assert np.isfinite(np.asarray(y)).all()


def _cfg(mesh_cfg, **model_kw):
    return TrainConfig(
        epochs=1,
        data=DataConfig(dataset="synthetic", image_size=32, batch_size=32,
                        synthetic_train_size=64, synthetic_test_size=32),
        model=dataclasses.replace(MOE_CFG, **model_kw),
        optim=OptimConfig(learning_rate=1e-3),
        mesh=mesh_cfg,
        checkpoint=CheckpointConfig(save_best=False, save_last=False),
    )


@pytest.mark.slow
def test_moe_vit_params_and_trainer():
    model = create_model(MOE_CFG)
    variables = init_variables(model, jax.random.PRNGKey(0), image_size=32)
    # block00 dense mlp, block01 moe (every 2nd block)
    assert "mlp" in variables["params"]["block00"]
    assert "moe" in variables["params"]["block01"]
    assert variables["params"]["block01"]["moe"]["wi"].shape[0] == 4

    trainer = Trainer(_cfg(MeshConfig(data=2)))
    try:
        m = trainer.train_one_epoch(1)
        e = trainer.evaluate()
    finally:
        trainer.close()
    assert np.isfinite(m["loss"]) and np.isfinite(e["loss"])


@pytest.mark.slow
def test_expert_parallel_training_parity():
    """Experts sharded over 'model' (EP) == unsharded run, same math."""
    def run(mesh_cfg):
        tr = Trainer(_cfg(mesh_cfg))
        try:
            return tr.train_one_epoch(1)
        finally:
            tr.close()

    base = run(MeshConfig(data=2))
    ep = run(MeshConfig(data=2, model=2))
    # 5e-4 abs (~2e-4 relative on a ~2.3 CE): EP's all_to_all dispatch
    # legitimately reorders float32 sums relative to the unsharded
    # einsum, and the reorder differs across jax's shard_map lowerings
    # (measured 1.6e-4 on jax 0.4.37, under 1e-4 on newer jax).
    assert abs(base["loss"] - ep["loss"]) < 5e-4
    # Accuracy at this near-chance, 1-epoch scale is argmax over
    # near-tied logits: bit-stable on modern jax (native jax.shard_map
    # lowering), but the older experimental lowering's float reorder
    # flips a few of the 64 eval ties — there the aligned loss above is
    # the parity evidence and accuracy only gets a coarse bound.
    acc_tol = 1e-6 if hasattr(jax, "shard_map") else 0.1
    assert abs(base["accuracy"] - ep["accuracy"]) < acc_tol


def _ep_args(E=4, D=16, H=32, N=64, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(N, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(N, E)), jnp.float32),
            jnp.asarray(rng.normal(0, 0.1, (E, D, H)), jnp.float32),
            jnp.zeros((E, H)),
            jnp.asarray(rng.normal(0, 0.1, (E, H, D)), jnp.float32),
            jnp.zeros((E, D)))


def _ep_grads(impl, args, ep, cap=8.0):
    """value+grads of a scalar loss through moe_apply under shard_map
    with an ``ep``-wide expert axis (tokens replicated, experts
    sharded); impl=None runs the unsharded single-device reference."""
    def core(*a):
        return moe_apply(*a, top_k=2, capacity_factor=cap,
                         dtype=jnp.float32,
                         ep_axis="model" if impl else None,
                         ep_impl=impl or "replicated")

    if impl is None:
        fn = core
    else:
        mesh = Mesh(np.array(jax.devices()[:ep]), ("model",))
        fn = shard_map(
            core, mesh=mesh,
            in_specs=(P(), P(), P("model"), P("model"), P("model"),
                      P("model")),
            out_specs=(P(), P()), check_vma=False)

    def loss(a):
        y, aux = fn(*a)
        return jnp.sum(y ** 2) + 0.01 * aux

    return jax.value_and_grad(loss)(args)


@pytest.mark.parametrize("ep", [2, 4])
def test_alltoall_dispatch_matches_replicated_and_unsharded(ep):
    """The GShard a2a capacity-buffer dispatch == the replicated psum
    lowering == the unsharded reference, values AND all six input
    grads (ample capacity, so per-slice routing selects identically
    and only the exchange mechanics differ)."""
    args = _ep_args()
    v_ref, g_ref = _ep_grads(None, args, ep)
    for impl in ("replicated", "alltoall"):
        v, g = _ep_grads(impl, args, ep)
        np.testing.assert_allclose(float(v), float(v_ref), rtol=1e-5,
                                   err_msg=impl)
        for (pth, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(g),
                jax.tree_util.tree_leaves_with_path(g_ref)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
                err_msg=f"{impl}: arg {jax.tree_util.keystr(pth)}")


def test_alltoall_overflow_stays_finite():
    """Tiny capacity under the a2a path: per-slice drops, still finite
    output and a bounded aux."""
    args = _ep_args()
    v, g = _ep_grads("alltoall", args, 2, cap=0.25)
    assert np.isfinite(float(v))
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(g))


def test_resolve_moe_dispatch():
    r = resolve_moe_dispatch
    assert r("auto", ep=1, n_tokens=64, n_experts=4) == "replicated"
    assert r("auto", ep=2, n_tokens=64, n_experts=4) == "alltoall"
    assert r("auto", ep=2, n_tokens=63, n_experts=4) == "replicated"
    assert r("auto", ep=4, n_tokens=64, n_experts=6) == "replicated"
    assert r("replicated", ep=4, n_tokens=64, n_experts=4) == "replicated"
    with pytest.raises(ValueError, match="divisible"):
        r("alltoall", ep=2, n_tokens=63, n_experts=4)
    with pytest.raises(ValueError, match="expert axis"):
        r("alltoall", ep=1, n_tokens=64, n_experts=4)
    with pytest.raises(ValueError, match="unknown"):
        r("nope", ep=2, n_tokens=64, n_experts=4)


@pytest.mark.slow
def test_moemlp_a2a_lowering_matches_gspmd():
    """MoeMlp's shard_map a2a lowering (tokens data/seq-sharded,
    experts 'model'-sharded, GShard exchange between them) matches the
    GSPMD global-routing path — forward, aux, and grads — with ample
    capacity on a dp2 x ep2 mesh."""
    from tpunet.parallel import make_mesh
    mesh = make_mesh(MeshConfig(data=2, model=2))
    x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 8, 32)),
                    jnp.float32)

    def build(dispatch, use_mesh):
        m = MoeMlp(4, 64, capacity_factor=8.0, dtype=jnp.float32,
                   dispatch=dispatch, mesh=mesh if use_mesh else None)
        variables = m.init(jax.random.PRNGKey(0), x)
        return m, {"params": variables["params"]}

    def val_and_grads(m, variables):
        def loss(p):
            y, mut = m.apply({"params": p}, x, mutable=["losses"])
            aux = sum(jax.tree_util.tree_leaves(mut["losses"]))
            return jnp.sum(y ** 2) + 0.01 * aux
        with mesh:
            return jax.value_and_grad(loss)(variables["params"])

    m_ref, v_ref = build("replicated", use_mesh=False)
    m_a2a, v_a2a = build("alltoall", use_mesh=True)
    # identical init: the lowering must not change the param tree
    assert (jax.tree_util.tree_structure(v_ref)
            == jax.tree_util.tree_structure(v_a2a))
    val_ref, g_ref = val_and_grads(m_ref, v_ref)
    val, g = val_and_grads(m_a2a, v_a2a)
    np.testing.assert_allclose(float(val), float(val_ref), rtol=1e-5)
    for (pth, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(g),
                                jax.tree_util.tree_leaves_with_path(g_ref)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(pth))


@pytest.mark.slow
def test_ep_shardings_applied():
    from jax.sharding import PartitionSpec as P

    from tpunet.parallel import make_mesh
    mesh = make_mesh(MeshConfig(data=2, model=2))
    tr = Trainer(_cfg(MeshConfig(data=2, model=2)), mesh=mesh)
    try:
        wi = tr.state.params["block01"]["moe"]["wi"]
        assert wi.sharding.spec == P("model", None, None)
        router = tr.state.params["block01"]["moe"]["router"]["kernel"]
        assert router.sharding.spec == P()
    finally:
        tr.close()


# -- the no-drop share: grouped products over the held pairs only -------------

E_ALL, D_IN, F_MID, TOP_K, CHUNK = 8, 16, 12, 2, 16


def _share_case(n, held, n_held, *, softmax=False, seed=0):
    """Inputs whose routing is chosen here: ``u`` carries two large
    features a token (the router is the identity on the first
    ``E_ALL`` features), so token ``i`` picks exactly the two experts
    written into it — the first ``n_held`` of the ``2 n`` pairs (in
    pair order; of a token's two only one where one expert is held) on
    held experts, the rest on others. ``n_held`` None:
    random features, routing as it falls."""
    rng = np.random.default_rng(seed)
    u = rng.normal(size=(n, D_IN)) * 0.3
    router = rng.normal(size=(D_IN, E_ALL)) * 0.3
    if n_held is not None:
        others = [e for e in range(E_ALL) if e not in held] or list(held)
        router = np.eye(D_IN, E_ALL)
        u[:, :E_ALL] *= 0.1
        per = min(TOP_K, len(held))          # held pairs a token can have
        for i in range(n):
            picks = []
            for j in range(TOP_K):
                pool = held if per * i + j < min(n_held, per * (i + 1)) \
                    else others
                picks.append(next(e for e in (pool[(i + j + s) % len(pool)]
                                              for s in range(len(pool)))
                                  if e not in picks))
            u[i, picks] += 4.0
    h = len(held)
    ws = [rng.normal(size=s) * 0.3 for s in ((h, D_IN, F_MID),
                                             (h, D_IN, F_MID),
                                             (h, F_MID, D_IN))]
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    bias = None if softmax else f32(rng.normal(size=E_ALL) * 0.01)
    return f32(u), f32(router), bias, [f32(w) for w in ws]


def _plain_share(u, router, bias, gate, up, down, held, scaling):
    """The layer by a loop over the held experts: every token through
    every held expert as a dense product, weighted by what the router
    gave that expert (0 where the token did not choose it)."""
    hp = jax.lax.Precision.HIGHEST
    logits = jnp.dot(u, router, precision=hp)
    if bias is None:
        p = jax.nn.softmax(logits, -1)
        chosen, idx = jax.lax.top_k(p, TOP_K)
        weight = chosen / jnp.sum(chosen, -1, keepdims=True)
    else:
        p = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(p + bias, TOP_K)
        chosen = jnp.take_along_axis(p, idx, -1)
        weight = scaling * chosen / jnp.sum(chosen, -1, keepdims=True)
    y = jnp.zeros_like(u)
    for s, expert in enumerate(held):
        w_tok = jnp.sum(jnp.where(idx == expert, weight, 0.0), -1)
        out = jnp.dot(jax.nn.silu(jnp.dot(u, gate[s], precision=hp))
                      * jnp.dot(u, up[s], precision=hp), down[s],
                      precision=hp)
        y = y + w_tok[:, None] * out
    return y


def _share_before_chunks(u, router, bias, gate, up, down, held, *, top_k,
                         scaling=1.0, dtype=jnp.bfloat16):
    """``routed_share`` as it was before it walked the sorted pairs in
    chunks (PR 35's formula): one call over all of them."""
    from flax import linen as nn

    from tpunet.models import moe
    n, d = u.shape
    h = len(held)
    if bias is None:
        idx, weight = moe.route_softmax(u, router, top_k)
    else:
        idx, weight = moe.route_sigmoid(u, router, bias, top_k, scaling)
    slot_of = jnp.full((router.shape[-1],), h, jnp.int32).at[
        jnp.asarray(held)].set(jnp.arange(h, dtype=jnp.int32))
    slot = slot_of[idx].reshape(-1)
    order = jnp.argsort(slot, stable=True)
    sizes = jnp.sum(slot[:, None] == jnp.arange(h)[None, :], axis=0,
                    dtype=jnp.int32)
    on_held = (jnp.take(slot, order) < h)[:, None]
    rows = moe._held_rows(jnp.take(u.astype(dtype), order // top_k, axis=0),
                          on_held)
    a = moe._held_rows(jax.lax.ragged_dot(rows, gate.astype(dtype), sizes),
                       on_held)
    b = moe._held_rows(jax.lax.ragged_dot(rows, up.astype(dtype), sizes),
                       on_held)
    y = jax.lax.ragged_dot(nn.silu(a) * b, down.astype(dtype), sizes)
    y = jnp.where(on_held, y, 0)
    y = jnp.take(y, jnp.argsort(order), axis=0).reshape(n, top_k, d)
    return jnp.sum(y.astype(jnp.float32) * weight[:, :, None],
                   axis=1).astype(dtype)


def _unwritten_ragged_dot(real):
    """``jax.lax.ragged_dot`` as the TPU leaves it: the rows outside
    every group hold NaN, going forward and coming back."""
    def unwritten(lhs, rhs, group_sizes):
        inside = lambda m: (jnp.arange(m)[:, None]  # noqa: E731
                            < jnp.sum(group_sizes))

        def clean(l_, r_):
            return real(jnp.where(inside(l_.shape[0]), l_, 0), r_,
                        group_sizes)

        @jax.custom_vjp
        def f(l_, r_):
            out = clean(l_, r_)
            return jnp.where(inside(out.shape[0]), out, jnp.nan)

        def bwd(res, g):
            d_l, d_r = jax.vjp(clean, *res)[1](
                jnp.where(inside(g.shape[0]), g, 0))
            return jnp.where(inside(d_l.shape[0]), d_l, jnp.nan), d_r

        f.defvjp(lambda l_, r_: (f(l_, r_), (l_, r_)), bwd)
        return f(lhs, rhs)
    return unwritten


# name: tokens, held experts, pairs on them (None: as the routing
# falls), rows a chunk, softmax routing?, chunks run / chunks
SHARE_CASES = {
    "no_pair_held": (40, (1, 4, 6), 0, CHUNK, False, (0, 5)),
    "held_ends_on_a_chunk": (40, (1, 4, 6), 32, CHUNK, False, (2, 5)),
    "one_pair_past_a_chunk": (40, (1, 4, 6), 33, CHUNK, False, (3, 5)),
    "every_pair_held": (40, tuple(range(E_ALL)), 80, CHUNK, False, (5, 5)),
    "pairs_short_of_a_chunk": (41, (1, 4, 6), 70, CHUNK, False, (5, 6)),
    "one_call_takes_all": (40, (1, 4, 6), 33, 2048, False, (1, 1)),
    "one_expert_held": (40, (3,), 20, CHUNK, False, (2, 5)),
    "softmax_routing": (40, (0, 2, 5, 7), 20, CHUNK, True, (2, 5)),
    "sigmoid_routing_as_it_falls": (48, (0, 1, 2), None, CHUNK, False, None),
}


@pytest.mark.parametrize("case", list(SHARE_CASES))
def test_routed_share_runs_the_held_pairs_only(case, monkeypatch):
    """``routed_share`` walking its sorted pairs in chunks against a
    plain loop over the held experts (forward and the gradients in the
    input, the router and the experts' weights), against its own
    formula from before the chunks (forward: the same numbers), with
    the count of chunks it ran, and with rows outside every group
    poisoned as the TPU leaves them (gradients finite and unmoved)."""
    from tpunet.models import moe

    n, held, n_held, chunk, softmax, run = SHARE_CASES[case]
    monkeypatch.setattr(moe, "PAIR_CHUNK", chunk)
    u, router, bias, ws = _share_case(n, held, n_held, softmax=softmax)
    cot = jnp.asarray(np.random.default_rng(9).normal(size=(n, D_IN)),
                      jnp.float32)

    def layer(dtype):
        return lambda u_, router_, *ws_: moe.routed_share(
            u_, router_, bias, *ws_, held, top_k=TOP_K, scaling=1.8,
            dtype=dtype)

    def value_and_grads(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * cot), argnums=(0, 1, 2, 3, 4))(
                u, router, *ws)

    y, stats = layer(jnp.float32)(u, router, *ws)
    held_pairs = round(float(stats["held_pair_share"]) * n * TOP_K)
    chunks = -(-n * TOP_K // chunk)
    if n_held is not None:
        assert held_pairs == n_held
        assert (-(-held_pairs // chunk) if chunks > 1 else 1, chunks) == run
    want_run = -(-held_pairs // chunk) / chunks if chunks > 1 else 1.0
    assert float(stats["held_chunks_run_share"]) == pytest.approx(
        want_run, abs=1e-7)

    # forward: the plain loop, and the formula before the chunks. In
    # bfloat16, what every configuration computes in, the same numbers;
    # in float32 XLA's CPU dot sums a [16, K] and an [80, K] product in
    # different orders, so the last bit may differ.
    np.testing.assert_allclose(
        y, _plain_share(u, router, bias, *ws, held, 1.8), rtol=1e-5,
        atol=1e-6)
    before = lambda dtype: _share_before_chunks(  # noqa: E731
        u, router, bias, *ws, held, top_k=TOP_K, scaling=1.8, dtype=dtype)
    np.testing.assert_allclose(y, before(jnp.float32), rtol=0, atol=1e-5)
    got16 = layer(jnp.bfloat16)(u, router, *ws)[0]
    assert got16.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got16, np.float32),
        np.asarray(before(jnp.bfloat16), np.float32))

    # gradients against the plain loop's
    val, grads = value_and_grads(lambda *a: layer(jnp.float32)(*a)[0])
    val_ref, grads_ref = value_and_grads(
        lambda u_, router_, *ws_: _plain_share(u_, router_, bias, *ws_,
                                               held, 1.8))
    np.testing.assert_allclose(float(val), float(val_ref), rtol=1e-5,
                               atol=1e-5)
    for name, a, b in zip(("u", "router", "gate", "up", "down"), grads,
                          grads_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=name)

    # ... and unmoved when the rows no group holds come back as NaN
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _unwritten_ragged_dot(jax.lax.ragged_dot))
    _, poisoned = value_and_grads(lambda *a: layer(jnp.float32)(*a)[0])
    for name, a, b in zip(("u", "router", "gate", "up", "down"), poisoned,
                          grads):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6, err_msg=name)


def _primitives(jaxpr, inside=()):
    """(primitive name, names of the loops and branches around it) of
    every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(
                        sub, inside + (eqn.primitive.name,))


@pytest.mark.parametrize("shape", ["decode", "wide", "wide_backward"])
def test_routed_share_loops_only_where_pairs_outnumber_a_chunk(shape):
    """A decode step's pairs (32 tokens x top-10 = 320 <= ``PAIR_CHUNK``)
    lower to the three grouped products alone, no loop and no branch; a
    wide call is one loop whose body holds the three once (not a copy
    a chunk), and its backward one more loop."""
    from tpunet.models import moe

    n = 32 if shape == "decode" else 4 * moe.PAIR_CHUNK // 10
    held = tuple(range(4))
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    args = (sds(n, D_IN), sds(D_IN, 16), sds(4, D_IN, F_MID),
            sds(4, D_IN, F_MID), sds(4, F_MID, D_IN))

    def fwd(u, router, *ws):
        return moe.routed_share(u, router, None, *ws, held, top_k=10)[0]

    fn = fwd if shape != "wide_backward" else jax.grad(
        lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))
    found = list(_primitives(jax.make_jaxpr(fn)(*args).jaxpr))
    control = [p for p, _ in found if p in ("while", "cond", "scan")]
    products = [around for p, around in found if p.startswith("ragged_dot")]
    if shape == "decode":
        assert not control and len(products) == 3
        return
    assert n * 10 > moe.PAIR_CHUNK
    assert control == ["while"] * (1 if shape == "wide" else 2)
    # forward: gate, up, down; backward: those recomputed, and each one's
    # two transposes
    assert len(products) == (3 if shape == "wide" else 3 + 3 + 6)
    assert all("while" in around for around in products)
