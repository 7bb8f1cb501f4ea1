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
