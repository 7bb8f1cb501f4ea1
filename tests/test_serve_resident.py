"""The serve engine's resident parameter tree (PR 30,
tpunet/serve/resident.py): each weight held in the type the step
converts it to, chosen from the model's own equations.

On the CPU the same program run on the given and on the resident tree
gives the same bits — logits, sampled tokens, pool contents — because
the rounding only moved from inside the step to before it; the tree
with EVERY leaf rounded does not (the head, the positions and the
norms are float32 work by design), which is how these tests can tell.
Which leaves are taken follows from the equations alone: the Dense
kernels and biases of a ``TransformerLM`` that computes below its
parameters' type, nothing of one that does not, never a leaf a gather
reads or whose conversion widens.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpunet.config import ModelConfig, ServeConfig
from tpunet.models import create_model, init_variables
from tpunet.models.lm import generate
from tpunet.serve import Engine
from tpunet.serve.resident import (held_types, resident_params,
                                   tree_bytes)

MIXED = ModelConfig(name="lm", vit_hidden=32, vit_depth=2, vit_heads=2,
                    dropout_rate=0.0, dtype="bfloat16",
                    param_dtype="float32", vocab_size=31, max_seq_len=48)
PRODUCT_LEAVES = {
    f"block{b:02d}/{part}/{leaf}"
    for b in range(MIXED.vit_depth)
    for part in ("attn/qkv", "attn/out", "mlp/fc1", "mlp/fc2")
    for leaf in ("kernel", "bias")}


def _paths(tree) -> dict:
    return {"/".join(str(k.key) for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def _perturbed(model, seed=0):
    """Initial parameters with every leaf moved off its initial value:
    zero biases and unit norms would hide a rounded bias or scale."""
    variables = init_variables(model, jax.random.PRNGKey(seed), seq_len=8)
    leaves, treedef = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    moved = [leaf + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
             for leaf, k in zip(leaves, keys)]
    return {"params": jax.tree_util.tree_unflatten(treedef, moved)}


@pytest.fixture(scope="module")
def mixed_lm():
    model = create_model(MIXED)
    return model, _perturbed(model)


def make_engine(lm, mesh=None, **cfg_kw):
    model, variables = lm
    cfg_kw.setdefault("slots", 4)
    cfg_kw.setdefault("queue_max", 16)
    cfg_kw.setdefault("prefill_buckets", (8, 16))
    cfg_kw.setdefault("default_max_new_tokens", 6)
    cfg_kw.setdefault("emit_every_s", 0.0)
    return Engine(model, variables, ServeConfig(**cfg_kw), mesh=mesh)


def make_mesh_engine(lm, **cfg_kw):
    from tpunet.config import MeshConfig
    from tpunet.infer.generate import load_lm
    from tpunet.parallel import make_mesh
    mesh = make_mesh(MeshConfig(data=1, model=2))
    return make_engine(load_lm(MIXED, variables=lm[1], mesh=mesh),
                       mesh=mesh, **cfg_kw)


def _bits(tree):
    return [np.asarray(a).view(np.uint8).tobytes()
            for a in jax.tree_util.tree_leaves(tree)]


def _step_inputs(eng, width, seed):
    """One call of ``_masked_step`` at token width ``width``: every row
    live, each on its own pages, greedy."""
    rows = eng._rows_at(width)
    r = np.random.default_rng(seed)
    table = np.zeros((rows, eng.pages_per_slot), np.int32)
    table[:] = 1 + np.arange(rows * eng.pages_per_slot).reshape(rows, -1)
    return (r.integers(0, MIXED.vocab_size, (rows, width)).astype(np.int32),
            np.zeros(rows, np.int32), np.ones(rows, bool), table,
            np.full(rows, width - 1, np.int32), np.zeros(rows, np.float32),
            np.zeros(rows, np.int32), np.zeros(rows, np.float32),
            np.zeros(rows, np.int32), np.zeros(rows, np.int32),
            np.zeros(rows, np.int32), np.zeros(rows, bool),
            np.arange(rows, dtype=np.int32))


def _every_leaf_rounded(params):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)


# ---------------------------------------------------------------------------
# the same bits from the given and from the resident tree
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", [1, 16])
def test_masked_step_is_bit_equal_on_given_and_resident_tree(mixed_lm,
                                                             width):
    """``_masked_step`` on the tree the engine was given and on the tree
    it holds: the same sampled tokens and the same pool, bit for bit,
    at the decode width and at a bucket."""
    eng = make_engine(mixed_lm)
    given, held = mixed_lm[1]["params"], eng.variables["params"]
    assert _paths(held)["block00/mlp/fc1/kernel"].dtype == jnp.bfloat16
    args = _step_inputs(eng, width, seed=width)
    cache_g, toks_g = eng._step(given, eng._make_cache(), *args)
    cache_h, toks_h = eng._step(held, eng._make_cache(), *args)
    assert np.array_equal(np.asarray(toks_g), np.asarray(toks_h))
    assert _bits(cache_g) == _bits(cache_h)
    assert any(np.asarray(a, np.float32).any()
               for a in jax.tree_util.tree_leaves(cache_h))


@pytest.mark.parametrize("tree,equal", [("resident", True),
                                        ("every_leaf", False)])
def test_logits_bit_equal_only_for_the_resident_tree(mixed_lm, tree, equal):
    """The control that lets these tests tell: the model's logits on
    the resident tree are the given tree's to the bit; with every leaf
    rounded (head, positions and norms too) they are not."""
    model, variables = mixed_lm
    eng = make_engine(mixed_lm)
    params = (eng.variables["params"] if tree == "resident"
              else _every_leaf_rounded(variables["params"]))
    tokens = np.random.default_rng(3).integers(
        0, MIXED.vocab_size, (2, 12)).astype(np.int32)
    want = np.asarray(model.apply(variables, tokens, train=False))
    got = np.asarray(model.apply({"params": params}, tokens, train=False))
    assert got.dtype == want.dtype == np.float32
    assert np.array_equal(got, want) == equal
    if not equal:
        assert np.abs(got - want).max() > 1e-3


def _prompts(n, seed=0):
    r = np.random.default_rng(seed)
    return [r.integers(0, MIXED.vocab_size,
                       size=int(r.integers(2, 9))).astype(np.int32)
            for _ in range(n)]


ENGINES = pytest.mark.parametrize("build,cfg", [
    pytest.param(make_engine, {"kv_dtype": "bf16"}, id="bf16_pool"),
    pytest.param(make_engine, {"kv_dtype": "int8"}, id="int8_pool"),
    pytest.param(make_engine, {"spec_decode": True, "spec_k": 3,
                               "spec_draft_width_mult": 1.0}, id="spec"),
    pytest.param(make_mesh_engine, {}, id="model2"),
])


@ENGINES
def test_engine_serves_solo_generates_tokens_of_the_given_tree(
        mixed_lm, build, cfg):
    """Through ``Engine.submit``: requests served from the resident
    tree carry the tokens solo ``generate`` makes from the GIVEN tree —
    over a bfloat16 pool, an int8 pool, with self-speculation (the
    drafter reads the same resident tree) and over a ``model=2`` mesh
    (the resident leaves keep the given ones' shardings)."""
    model, variables = mixed_lm
    eng = build(mixed_lm, **cfg).start()
    try:
        reqs = [(p, eng.submit(p, max_new_tokens=6, temperature=0.0))
                for p in _prompts(5, seed=11)]
        for p, req in reqs:
            req.result(timeout=300.0)
            assert req.finish_reason == "length" and not req.error
            solo = np.asarray(generate(model, variables, p[None],
                                       n_new=6))[0, len(p):]
            assert req.tokens == solo.tolist()
        snap = eng.registry.snapshot()
    finally:
        eng.stop()
    assert snap["serve_weight_leaves_precast"] == len(PRODUCT_LEAVES)
    if cfg.get("spec_decode"):
        assert eng._drafter_params is eng.variables["params"]
        assert snap["serve_spec_acceptance_rate"] == 1.0
    if eng.mesh is not None:
        kernel = _paths(eng.variables["params"])["block00/mlp/fc1/kernel"]
        assert kernel.dtype == jnp.bfloat16
        assert len(kernel.sharding.device_set) == 2
        assert not kernel.sharding.is_fully_replicated


# ---------------------------------------------------------------------------
# which leaves, and what the gauges read
# ---------------------------------------------------------------------------

def test_transformer_lm_holds_its_product_weights_and_nothing_else(
        mixed_lm):
    """Exactly the Dense kernels and biases under ``block*/`` are held
    in bfloat16; ``embed`` (a gather and the float32 tied head),
    ``pos_embed`` and every LayerNorm stay the caller's own arrays. The
    caller's tree is untouched and still serves solo ``generate``; the
    gauges and the ``obs_serve`` record read the arithmetic."""
    from tpunet.serve.engine import build_serve_record

    model, variables = mixed_lm
    before = _bits(variables["params"])
    eng = make_engine(mixed_lm)
    given, held = _paths(variables["params"]), \
        _paths(eng.variables["params"])
    assert set(given) == set(held)
    taken = {p for p in held if held[p].dtype != given[p].dtype}
    assert taken == PRODUCT_LEAVES
    for path in taken:
        assert held[path].dtype == jnp.bfloat16
        assert np.array_equal(
            np.asarray(held[path]),
            np.asarray(given[path].astype(jnp.bfloat16)))
    for path in set(held) - taken:
        assert held[path] is given[path], path
    # the caller's tree: same arrays, same bits, still usable
    assert all(a.dtype == jnp.float32 and not a.is_deleted()
               for a in given.values())
    assert _bits(variables["params"]) == before
    prompt = np.arange(5, dtype=np.int32)
    assert generate(model, variables, prompt[None], n_new=3).shape == (1, 8)

    n_taken = sum(given[p].size for p in taken)
    snap = eng.registry.snapshot()
    assert snap["serve_weight_bytes_given"] == \
        4 * sum(a.size for a in given.values()) == \
        tree_bytes(variables["params"])
    assert snap["serve_weight_bytes_resident"] == \
        snap["serve_weight_bytes_given"] - 2 * n_taken == \
        tree_bytes(eng.variables["params"])
    assert snap["serve_weight_leaves_precast"] == 16
    rec = build_serve_record(eng.registry, queue_depth=0, active_slots=0,
                             slots=eng.slots, uptime_s=1.0, window_s=1.0)
    assert (rec["weight_bytes_given"], rec["weight_bytes_resident"],
            rec["weight_leaves_precast"]) == (
        snap["serve_weight_bytes_given"],
        snap["serve_weight_bytes_resident"], 16)


def test_gpt2_xl_arithmetic_from_shapes_alone():
    """The benchmark's configuration, abstractly (no weight is made):
    384 leaves, 1,475.3 M of 1,557.6 M parameters, 6.230 GB given and
    3.280 GB resident — and the decode-width and the bucket-wide trace
    each pick the same set alone."""
    import json
    import os

    from tpunet.models.vit import PagedKV
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "gpt2-xl.json")) as f:
        model = create_model(ModelConfig(**json.load(f)["program"]["model"]))
    slots, page_tokens = 16, 16
    per_slot = model.max_len // page_tokens
    paged = PagedKV(pages=slots * per_slot + 1, page_tokens=page_tokens)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, model.max_len), jnp.int32),
        decode=True, paged_kv=paged,
        page_table=jnp.zeros((slots, per_slot), jnp.int32)))

    def apply(params, cache, tokens, positions, active, page_table):
        return model.apply(
            {"params": params, "cache": cache}, tokens, train=False,
            decode=True, pos_offset=positions, decode_active=active,
            paged_kv=paged, page_table=page_table, mutable=["cache"])

    sds = jax.ShapeDtypeStruct
    leaves = jax.tree_util.tree_leaves(shapes["params"])
    picks = []
    for rows, width in ((slots, 1), (1, 512)):
        types = held_types(apply, shapes["params"], [(
            shapes["cache"], sds((rows, width), np.int32),
            sds((rows,), np.int32), sds((rows,), bool),
            sds((rows, per_slot), np.int32))])
        picks.append({i: to for i, to in enumerate(types)
                      if to is not None})
    assert picks[0] == picks[1]
    assert set(picks[0].values()) == {np.dtype(jnp.bfloat16)}
    assert len(picks[0]) == 384
    taken = sum(leaves[i].size for i in picks[0])
    total = sum(a.size for a in leaves)
    assert (taken, total) == (1_475_251_200, 1_557_611_200)
    assert (4 * total, 4 * total - 2 * taken) == \
        (6_230_444_800, 3_279_942_400)
    paths = list(_paths(shapes["params"]))
    assert all(paths[i].startswith("block") and "/ln" not in paths[i]
               for i in picks[0])


def test_a_model_that_computes_in_its_parameters_type_is_left_alone():
    """float32 compute over float32 parameters: nothing narrows, the
    held tree is the given tree's own arrays, the bytes are equal."""
    cfg = dataclasses.replace(MIXED, dtype="float32")
    model = create_model(cfg)
    variables = _perturbed(model)
    eng = make_engine((model, variables))
    for given, held in zip(
            jax.tree_util.tree_leaves(variables["params"]),
            jax.tree_util.tree_leaves(eng.variables["params"])):
        assert held is given
    snap = eng.registry.snapshot()
    assert snap["serve_weight_leaves_precast"] == 0
    assert snap["serve_weight_bytes_given"] == \
        snap["serve_weight_bytes_resident"]


@pytest.mark.parametrize("param_dtype", ["bfloat16", "float32"])
def test_latent_lm_never_gives_up_a_widened_or_gathered_leaf(param_dtype):
    """The second decoder. At bfloat16 parameters (the 4k cell) no
    conversion narrows — its float32 router and indexer paths widen —
    so nothing is taken and the engine holds the given arrays. At
    float32 parameters under bfloat16 compute only leaves whose one
    use is the narrowing are taken (the attention's projections and
    the head; the indexer's weights too, which the model itself rounds
    to bfloat16 before its float32 product): never the embedding (a
    gather), a norm, or the router and experts (float32 products)."""
    import chip_smoke
    from benchmark import harness, weights
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ref = harness.load_module(
        os.path.join(repo, "benchmark", "reference", "dots3-note-prev.py"),
        "reference_dots3_for_resident_test")
    held_experts = (2, 3, 4, 5)
    cfg = dict(
        chip_smoke.LATENT_TINY, num_hidden_layers=3,
        layer_types=["full_attention", "full_attention",
                     "sliding_attention"],
        first_k_dense_replace=1, rms_norm_eps=1e-5, rope_theta=8e7,
        swa_rope_theta=5e4, apply_mla_qkv_lora_rescale=True,
        n_routed_experts=len(held_experts), n_routed_experts_published=8,
        routed_scaling_factor=1.0, held_experts=list(held_experts),
        vocab_size=50, param_dtype=param_dtype)
    arch = {k: v for k, v in cfg.items()
            if k not in ("n_routed_experts_published", "vocab_size",
                         "param_dtype")}
    arch["n_routed_experts"] = cfg["n_routed_experts_published"]
    model = create_model(ModelConfig(
        name="latent_lm", vocab_size=50, max_seq_len=48, dtype="bfloat16",
        param_dtype=param_dtype, latent=arch))
    params = weights.make_tree(ref.param_spec(cfg, "serve"), 2000000011,
                               dtype=param_dtype)
    eng = Engine(model, {"params": params}, ServeConfig(
        slots=3, queue_max=8, prefill_buckets=(8, 24), kv_page_tokens=4,
        emit_every_s=0.0))
    given, held = _paths(params), _paths(eng.variables["params"])
    taken = {p for p in held if held[p] is not given[p]}
    snap = eng.registry.snapshot()
    assert snap["serve_weight_leaves_precast"] == len(taken)
    if param_dtype == "bfloat16":
        assert not taken
        assert snap["serve_weight_bytes_given"] == \
            snap["serve_weight_bytes_resident"]
        return
    assert taken
    for path in taken:
        assert given[path].dtype == jnp.float32
        assert held[path].dtype == jnp.bfloat16
        assert not any(word in path for word in
                       ("embed", "norm", "ln", "moe")), path


# ---------------------------------------------------------------------------
# the step's equations: the given tree's, less the conversions
# ---------------------------------------------------------------------------

def _equations(jaxpr, n_params):
    """The equations of ``jaxpr`` as comparable rows, and which rows
    convert one of the first ``n_params`` inputs."""
    params = set(jaxpr.invars[:n_params])
    rows, casts = [], []
    for eqn in jaxpr.eqns:
        rows.append((eqn.primitive.name,
                     tuple(str(v.aval) for v in eqn.outvars)))
        casts.append(eqn.primitive.name == "convert_element_type"
                     and eqn.invars[0] in params)
    return rows, casts


@pytest.mark.parametrize("width", [1, 16])
def test_step_equations_are_the_given_trees_less_the_conversions(
        mixed_lm, width):
    """On every platform: ``_masked_step`` traced for the resident
    tree is the trace for the given tree with the 16 narrowing
    ``convert_element_type`` equations gone — no equation added, none
    changed, the same order."""
    eng = make_engine(mixed_lm)
    avals = eng._step_avals(width)
    given_s = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        mixed_lm[1]["params"])
    n = len(jax.tree_util.tree_leaves(given_s))
    step = eng._step.__wrapped__
    rows_g, casts_g = _equations(
        jax.make_jaxpr(step)(given_s, *avals[1:]).jaxpr, n)
    rows_h, casts_h = _equations(jax.make_jaxpr(step)(*avals).jaxpr, n)
    assert sum(casts_g) == len(PRODUCT_LEAVES) and not any(casts_h)
    assert [r for r, c in zip(rows_g, casts_g) if not c] == rows_h
    assert len(rows_h) == len(rows_g) - len(PRODUCT_LEAVES)


def test_a_leaf_with_any_other_use_stays_as_given():
    """The rule itself, on a function small enough to read: taken only
    when every use is a conversion to ONE narrower floating type."""
    bf16, f32 = jnp.bfloat16, jnp.float32

    def apply(p, x):
        y = x.astype(bf16) @ p["only_cast"].astype(bf16)
        y = y + p["cast_twice"].astype(bf16) + p["cast_twice"].astype(bf16)
        y = y + p["also_used_wide"].astype(bf16)
        z = y.astype(f32) * p["also_used_wide"]
        z = z + p["two_types"].astype(bf16).astype(f32) \
            + p["two_types"].astype(jnp.float16).astype(f32)
        z = z + p["widened"].astype(f32)
        z = z + jnp.take(p["gathered"].astype(bf16), jnp.arange(4), axis=0)
        z = z + jax.jit(lambda w: w.astype(bf16))(p["in_a_call"])
        return z, p["returned"], p["ints"].astype(jnp.int8)

    params = {
        "only_cast": jnp.ones((4, 4), f32), "cast_twice": jnp.ones(4, f32),
        "also_used_wide": jnp.ones(4, f32), "two_types": jnp.ones(4, f32),
        "widened": jnp.ones(4, bf16), "gathered": jnp.ones((4, 4), f32),
        "in_a_call": jnp.ones(4, f32), "returned": jnp.ones(4, f32),
        "ints": jnp.ones(4, jnp.int32)}
    x = jax.ShapeDtypeStruct((4, 4), f32)
    tree, n = resident_params(apply, params, [(x,)])
    taken = {k for k in params if tree[k] is not params[k]}
    # `gathered` IS only ever converted (the gather reads the
    # converted copy), so the rule takes it — as it takes a Dense bias
    assert taken == {"only_cast", "cast_twice", "gathered"} and n == 3
    assert all(tree[k].dtype == bf16 for k in taken)
    # a second shape that uses one of them otherwise takes it back
    def apply_wide(p, x):
        return apply(p, x)[0] + p["cast_twice"]
    tree, n = resident_params(lambda p, x: (apply(p, x), apply_wide(p, x)),
                              params, [(x,)])
    assert n == 2 and tree["cast_twice"] is params["cast_twice"]


# ---------------------------------------------------------------------------
# the AOT store
# ---------------------------------------------------------------------------

def test_aot_store_written_for_the_given_trees_types_is_a_clean_miss(
        tmp_path, mixed_lm):
    """A store keyed as before this PR — its executables take float32
    product weights — shares no entry with today's: the engine loads
    nothing from it, compiles once for the resident tree and saves;
    the boot after that loads, and serves the same tokens."""
    import os

    from tpunet.serve.engine import build_aot_store
    from tpunet.utils.cache import AotProgramStore, serializable_compile

    model, variables = mixed_lm
    cfg = ServeConfig(slots=2, queue_max=4, prefill_buckets=(16,),
                      default_max_new_tokens=8, emit_every_s=0.0,
                      kv_pages=12, kv_page_tokens=8)
    old = AotProgramStore(str(tmp_path), AotProgramStore.digest({
        "model": dataclasses.asdict(MIXED), "slots": cfg.slots,
        "prefill_buckets": list(cfg.prefill_buckets),
        "kv_pages": cfg.kv_pages, "kv_page_tokens": cfg.kv_page_tokens,
        "kv_dtype": cfg.kv_dtype, "spec_decode": False, "spec_k": 4,
        "spec_draft_width_mult": 0.5}))
    store = build_aot_store(str(tmp_path), MIXED, cfg)
    assert store.config_digest != old.config_digest
    # what such a store holds: the programs of the given tree's types
    writer = Engine(model, variables, cfg)
    given_s = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        variables["params"])
    for width in (1, 16):
        with serializable_compile():
            program = writer._step.lower(
                given_s, *writer._step_avals(width)[1:]).compile()
        assert old.save("masked_step", f"w{width}", program)
    before = {f for f in os.listdir(tmp_path) if f.endswith(".aotx")}
    assert len(before) == 2

    def answer(engine):
        engine.start()
        try:
            req = engine.submit(np.arange(5, dtype=np.int32),
                                max_new_tokens=5, temperature=0.0)
            req.result(timeout=300.0)
            return req.tokens
        finally:
            engine.stop()

    first = Engine(model, variables, cfg, aot_store=store)
    assert first.aot_status == {"w1": "compiled+saved",
                                "k4w16": "compiled+saved"}
    added = {f for f in os.listdir(tmp_path)
             if f.endswith(".aotx")} - before
    assert len(added) == 2 and all(store.config_digest in f for f in added)
    second = Engine(model, variables, cfg, aot_store=store)
    assert second.aot_status == {"w1": "loaded", "k4w16": "loaded"}
    assert answer(first) == answer(second)
