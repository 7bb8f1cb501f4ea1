"""The main path's Pallas kernels, compiled for the real chip.

The TPU compiler is installed wherever the tests run, and compiles for
a chip that is described and not attached (a ``v5e:2x2`` topology), so
what Mosaic refuses — a slice off the tiling, too much VMEM, a shape
cast it cannot lay out — fails here at no chip time. Interpret mode,
which every other kernel test uses, shows none of that: a backward
kernel passed all of them and was refused on the chip (CHANGES.md
PR 21).

Shapes are the ones the smoke (chip_smoke.py) checks for parity on the
chip: attention at [4, 2048, 16, D], the serve cell's width-1 decode
over a paged pool (16 slots, 25 heads of 64, 16-token pages).
Nothing runs, so nothing here says anything about results or times.

The topology is described inside a fixture (never at import: only one
process may load libtpu, and every xdist worker imports this file), and
all these tests stay in this one file so one worker owns the library.
"""

import jax
import jax.numpy as jnp
import re

import pytest
from jax.sharding import SingleDeviceSharding

from tpunet.ops import paged_decode
from tpunet.ops.flash import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip (the next one warns
    and compiles again): keep the cache out of these."""
    from tpunet.utils.cache import reset_compilation_cache_latch
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    reset_compilation_cache_latch()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    reset_compilation_cache_latch()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


BF16 = jnp.bfloat16


@pytest.mark.parametrize("d", [128, 64])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles(one_chip, no_compile_cache, d,
                                  direction):
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def bwd(q, k, v):
        return jax.grad(lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)),
                        argnums=(0, 1, 2))(q, k, v)

    qkv = [((4, 2048, 16, d), BF16)] * 3
    _compile(fwd if direction == "fwd" else bwd, *qkv,
             sharding=one_chip)


def test_latent_attention_trains_through_flash(one_chip, no_compile_cache,
                                               monkeypatch):
    """One latent-attention layer at glm-4.7-flash's widths (20 heads,
    queries, keys and values all 256 wide) on 1 x 8192 tokens, forward
    and backward as the Trainer's step takes it: the three flash
    kernels at D = 256, and no [heads, T, T] tensor."""
    from tpunet.models.latent_lm import LatentArch, LatentAttention

    arch = LatentArch(
        hidden_size=2048, num_hidden_layers=1, intermediate_size=10240,
        layer_types=("full_attention",), num_attention_heads=20,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, rope_theta=1e6,
        attention_gate_type=None)
    attn = LatentAttention(arch, "full_attention", dtype=BF16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # dispatch
    shapes = jax.eval_shape(lambda: attn.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 2048), jnp.float32)))
    assert set(shapes["params"]) == {"dq", "q_norm", "uq", "dkv", "kv_norm",
                                     "ukv", "out"}

    def loss(params, u):
        y = attn.apply({"params": params}, u, train=True)
        return jnp.sum(y.astype(jnp.float32))

    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        on_chip(shapes["params"]), jax.ShapeDtypeStruct(
            (1, 8192, 2048), jnp.float32, sharding=one_chip)
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    assert "8192,8192" not in text


def test_no_drop_expert_layer_compiles_forward_and_backward(
        one_chip, no_compile_cache):
    """``routed_share`` at glm-4.7-flash's share — 8,192 tokens, top-4
    of a router 64 wide, 8 held experts of width 1536 — under
    ``jax.grad``: the grouped products and their transposes (a ragged
    contracting dimension for the weights' gradient) are taken by the
    TPU compiler as such."""
    from tpunet.models.moe import routed_share

    n, d, f, e, held = 8192, 2048, 1536, 64, 8

    def loss(u, router, bias, gate, up, down):
        y, _ = routed_share(u, router, bias, gate, up, down,
                            tuple(range(held)), top_k=4, scaling=1.8)
        return jnp.sum(y.astype(jnp.float32))

    sds = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
        sds(n, d), sds(d, e), sds(e), sds(held, d, f), sds(held, d, f),
        sds(held, f, d)).compile().as_text()
    assert len(set(re.findall(r"ragged-dot-none[.\d]*", text))) >= 9


def test_segmented_flash_attention_compiles(one_chip, no_compile_cache):
    def fwd(q, k, v, seg):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               segment_ids=(seg, seg))

    _compile(fwd, *[((4, 2048, 16, 128), BF16)] * 3,
             ((4, 2048), jnp.int32), sharding=one_chip)


# The serve cell's decode geometry (benchmark/configs/gpt2-xl.json,
# slots 16, ServeConfig's 16-token pages over max_seq_len 1024).
SLOTS, HEADS, HEAD_DIM, PAGE_TOKENS, PAGES_PER_SLOT = 16, 25, 64, 16, 64
POOL_ROWS = (SLOTS * PAGES_PER_SLOT + 1) * PAGE_TOKENS


@pytest.mark.parametrize("dtype", [BF16, jnp.float32])
def test_paged_decode_compiles(one_chip, no_compile_cache, dtype):
    def attend(q, k_pool, v_pool, table, lengths):
        return paged_decode.paged_decode_attention(
            q, k_pool, v_pool, table, lengths, page_tokens=PAGE_TOKENS,
            interpret=False)

    pool = ((POOL_ROWS, paged_decode.pool_width(HEADS, HEAD_DIM)), dtype)
    text = _compile(attend, ((SLOTS, HEADS, HEAD_DIM), dtype), pool, pool,
                    ((SLOTS, PAGES_PER_SLOT), jnp.int32),
                    ((SLOTS,), jnp.int32), sharding=one_chip)
    assert "tpunet_paged_decode" in text


@pytest.mark.parametrize("dtype", [BF16, jnp.float32])
def test_grouped_paged_decode_compiles(one_chip, no_compile_cache, dtype):
    """16 query heads over 2 KV heads of 256 (qwen3-next-80b-a3b's full
    layers) at the cell's geometry: 32 slots of 640 16-token pages, pool
    rows 512 wide."""
    slots, per_slot, heads, kv_heads, d = 32, 640, 16, 2, 256

    def attend(q, k_pool, v_pool, table, lengths):
        return paged_decode.paged_decode_attention(
            q, k_pool, v_pool, table, lengths, page_tokens=PAGE_TOKENS,
            interpret=False, kv_heads=kv_heads)

    pool = (((slots * per_slot + 1) * PAGE_TOKENS,
             paged_decode.pool_width(kv_heads, d)), dtype)
    text = _compile(attend, ((slots, heads, d), dtype), pool, pool,
                    ((slots, per_slot), jnp.int32), ((slots,), jnp.int32),
                    sharding=one_chip)
    assert "tpunet_paged_decode" in text


@pytest.mark.parametrize("rows,width",
                         [(SLOTS, 1), (SLOTS, 128), (1, 512)])
def test_paged_attention_layer_never_converts_the_pool(
        one_chip, no_compile_cache, monkeypatch, rows, width):
    """One attention layer's decode program over the donated pool, as
    the engine compiles it: the new rows are scattered into the
    parameter in place and nothing copies a whole pool buffer. (With
    the pool stored ``[rows, 25, 64]`` or ``[rows, 1600]`` the TPU lays
    it out rows-minor and every program converts it in and out — 38 ms
    of a 140 ms GPT-2 XL decode step, PERF.md section 6, PR 26.) The
    width-1 program holds the kernel, the bucket-wide ones do not —
    neither the ``[slots, bucket]`` one nor the one-row ``[1, bucket]``
    call a paged engine on one device prefills through (PR 28), which
    reaches the same whole pool through one row of the page table."""
    import re

    from tpunet.models.vit import Attention, PagedKV

    monkeypatch.setattr(paged_decode, "_on_tpu", lambda: True)
    monkeypatch.setattr(paged_decode, "_interpret", lambda: False)
    paged = PagedKV(pages=SLOTS * PAGES_PER_SLOT + 1,
                    page_tokens=PAGE_TOKENS)
    attn = Attention(HEADS, dtype=BF16)
    hidden = HEADS * HEAD_DIM
    table = jnp.zeros((SLOTS, PAGES_PER_SLOT), jnp.int32)
    shapes = jax.eval_shape(lambda: attn.init(
        jax.random.PRNGKey(0), jnp.zeros((SLOTS, 8, hidden), BF16),
        decode=True, paged_kv=paged, page_table=table))

    def step(params, cache, x, positions, active, table):
        y, mutated = attn.apply(
            {"params": params, "cache": cache}, x, decode=True,
            positions=positions, active=active, paged_kv=paged,
            page_table=table, mutable=["cache"])
        return mutated["cache"], y

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    text = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(shapes["params"]), on_chip(shapes["cache"]),
        sds((rows, width, hidden), BF16), sds((rows,), jnp.int32),
        sds((rows,), bool),
        sds((rows, PAGES_PER_SLOT), jnp.int32)).compile().as_text()
    pool = rf"bf16\[{POOL_ROWS},{paged_decode.pool_width(HEADS, HEAD_DIM)}\]"
    assert re.search(pool + r"\{1,0[:}]", text)       # row-major
    assert not re.search(pool + r"\S* copy\(", text)
    assert ("tpu_custom_call" in text) == (width == 1)


@pytest.mark.parametrize("tree", ["resident", "given"])
def test_block_decode_reads_no_float32_product_weight(
        one_chip, no_compile_cache, monkeypatch, tree):
    """One ``EncoderBlock``'s width-1 decode program over the paged
    pool at GPT-2 XL's widths, compiled from the avals of the tree the
    engine holds (tpunet/serve/resident.py): no float32 product weight
    anywhere in it and no conversion of a ``params`` argument —
    the step reads each weight once, at two bytes. From the avals of
    the tree the engine is GIVEN it has both (what this guards: 5.9 GB
    of float32 read and rounded on every GPT-2 XL decode step, PERF.md
    section 6, PR 30)."""
    import re

    from tpunet.models.vit import EncoderBlock, PagedKV
    from tpunet.serve.resident import held_types

    monkeypatch.setattr(paged_decode, "_on_tpu", lambda: True)
    monkeypatch.setattr(paged_decode, "_interpret", lambda: False)
    paged = PagedKV(pages=SLOTS * PAGES_PER_SLOT + 1,
                    page_tokens=PAGE_TOKENS)
    hidden = HEADS * HEAD_DIM
    block = EncoderBlock(HEADS, 4 * hidden, dtype=BF16)
    shapes = jax.eval_shape(lambda: block.init(
        jax.random.PRNGKey(0), jnp.zeros((SLOTS, 8, hidden), BF16),
        decode=True, paged_kv=paged,
        page_table=jnp.zeros((SLOTS, PAGES_PER_SLOT), jnp.int32)))

    def apply(params, cache, x, positions, active, table):
        return block.apply(
            {"params": params, "cache": cache}, x, False, True, None,
            positions, active, paged, table, mutable=["cache"])

    def step(params, cache, x, positions, active, table):
        y, mutated = apply(params, cache, x, positions, active, table)
        return mutated["cache"], y

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    cache = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                   shapes["cache"])
    inputs = (sds((SLOTS, 1, hidden), BF16), sds((SLOTS,), jnp.int32),
              sds((SLOTS,), bool), sds((SLOTS, PAGES_PER_SLOT), jnp.int32))
    leaves, treedef = jax.tree_util.tree_flatten(shapes["params"])
    types = held_types(apply, shapes["params"], [(cache,) + inputs])
    assert sum(t is not None for t in types) == 8
    if tree == "given":
        types = [None] * len(leaves)
    params = jax.tree_util.tree_unflatten(treedef, [
        sds(a.shape, a.dtype if t is None else t)
        for a, t in zip(leaves, types)])
    lowered = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, *inputs)
    # as traced: conversions of a ``params`` argument in the entry
    # function (the private ones number their own arguments)
    traced = lowered.as_text().split("func.func private")[0]
    converted = {int(i) for i in re.findall(
        r"stablehlo\.convert %arg(\d+)\b", traced) if int(i) < len(leaves)}
    # as the TPU compiler leaves it: float32 weight matrices anywhere
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    wide = [w for w in ("f32[1600,6400]", "f32[6400,1600]",
                        "f32[1600,4800]", "f32[1600,1600]") if w in text]
    if tree == "resident":
        assert not converted and not wide
        assert re.search(r"bf16\[1600,6400\]\S* parameter\(", text)
    else:
        assert len(converted) == 8 and len(wide) == 4


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_latent_attention_decode_never_converts_its_pools(
        one_chip, no_compile_cache, kind):
    """One latent-attention layer's width-1 (absorbed) decode program
    at dots3-note-prev's widths over its donated pools, as the engine
    compiles it: the lane-rounded pools (576 -> 640 and a float32 128 for a full
    layer, 1,088 -> 1,152 for a sliding one) are laid out row-major and
    nothing copies a whole pool (the lesson of PR 26, for the second
    decoder's cache)."""
    import re

    from tpunet.models.latent_lm import LatentArch, LatentAttention
    from tpunet.models.vit import PagedKV

    slots, per_slot, pt = 8, 384, 16
    arch = LatentArch(hidden_size=5120, num_hidden_layers=1,
                      layer_types=(kind,), intermediate_size=13824,
                      index_topk=2048, apply_mla_qkv_lora_rescale=True)
    paged = PagedKV(pages=slots * per_slot + 1, page_tokens=pt)
    attn = LatentAttention(arch, kind, dtype=BF16, param_dtype=BF16)
    table = jnp.zeros((slots, per_slot), jnp.int32)
    shapes = jax.eval_shape(lambda: attn.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, 8, 5120), BF16),
        decode=True, paged_kv=paged, page_table=table))

    def step(params, cache, u, positions, active, table):
        y, mutated = attn.apply(
            {"params": params, "cache": cache}, u, True, positions, active,
            paged, table, mutable=["cache"])
        return mutated["cache"], y

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    text = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(shapes["params"]), on_chip(shapes["cache"]),
        sds((slots, 1, 5120), BF16), sds((slots,), jnp.int32),
        sds((slots,), bool),
        sds((slots, per_slot), jnp.int32)).compile().as_text()
    rows = (slots * per_slot + 1) * pt
    pools = ({640: "bf16", 128: "f32"} if kind == "full_attention"
             else {1152: "bf16"})          # the index keys stay float32
    assert {leaf.shape for leaf in jax.tree_util.tree_leaves(
        shapes["cache"])} == {(rows, w) for w in pools}
    for w, dtype in pools.items():
        pool = rf"{dtype}\[{rows},{w}\]"
        assert re.search(pool + r"\{1,0[:}]", text)       # row-major
        assert not re.search(pool + r"\S* copy\(", text)


# -- the hybrid decoder's two programs at published widths ---------------------

V5E_BYTES_LIMIT = 16_909_336_064     # memory_stats()["bytes_limit"], one v5e


@pytest.fixture(scope="module")
def hybrid_engine():
    """The serve engine of ``qwen3-next-80b-a3b.serve-closed32-ctx8k`` as
    the benchmark's runner builds it, never run: the 3.67 B parameters
    are zero-stride views of one zero (their shapes and bytes are real,
    their memory is not), the page and state pools real zeros."""
    import numpy as np

    from benchmark import harness, weights
    from tpunet.config import ModelConfig, ServeConfig
    from tpunet.models import create_model
    from tpunet.serve.engine import Engine

    cell = harness.load_cell("qwen3-next-80b-a3b.serve-closed32-ctx8k")
    config = cell["config"]
    spec = harness.load_reference(cell).param_spec(config, "serve")
    zero = np.zeros((), jnp.bfloat16)
    params = weights.nest({path: np.broadcast_to(zero, shape)
                           for path, (shape, _, _) in spec.items()})
    serve = dict(cell["cell"]["program"]["serve"])
    serve["prefill_buckets"] = tuple(serve["prefill_buckets"])
    return Engine(create_model(ModelConfig(**config["program"]["model"])),
                  {"params": params}, ServeConfig(**serve))


# A bucket-wide call is handed the table columns its positions reach
# (``Engine._reach``): 640 = the whole row, the worst program a continued
# row can run; 512 = a fresh admission of the 8,192 bucket, what a cell runs.
WIDTH_AND_COLUMNS = pytest.mark.parametrize(
    "width, columns", [(1, 640), (8192, 640), (8192, 512)],
    ids=["1", "8192-whole-row", "8192-fresh"])


@WIDTH_AND_COLUMNS
def test_hybrid_masked_step_fits_the_chip(one_chip, no_compile_cache,
                                          monkeypatch, hybrid_engine, width,
                                          columns):
    """The engine's own masked step, ``[32, 1]`` and ``[1, 8192]`` (over
    the whole row's keys and over a fresh admission's), over 7.33 GB of
    weights, 32 x 10,240 tokens of K/V pages and 32 state rows: compiled
    for the chip with at least 1 GiB to spare; the width-1 program
    attends through ``tpunet_paged_decode`` (grouped), the bucket-wide
    one through the flash kernel; neither copies a page pool or the
    state pool."""
    import re

    monkeypatch.setattr(paged_decode, "_on_tpu", lambda: True)
    monkeypatch.setattr(paged_decode, "_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # dispatch
    eng = hybrid_engine
    assert eng.state_pool_bytes() == 32 * 6 * (32 * 128 * 128 + 3 * 8192) * 4
    assert eng.kv_pool_bytes() == 2 * 2 * (32 * 640 + 1) * 16 * 512 * 2
    assert (eng.pages_per_slot, eng._reach(1), eng._reach(8192)) == \
        (640, 640, 512)
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._step_avals(width, columns))
    compiled = eng._step.lower(*avals).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"hybrid masked step width {width} columns {columns}: "
          f"temporaries {m.temp_size_in_bytes} total {total}")
    assert total + (1 << 30) <= V5E_BYTES_LIMIT, total
    assert m.alias_size_in_bytes >= eng.kv_pool_bytes() \
        + eng.state_pool_bytes()                  # the pools are donated
    text = compiled.as_text()
    assert ("tpunet_paged_decode" in text) == (width == 1)
    assert ("tpunet_flash_fwd" in text) == (width > 1)
    for pool in (r"bf16\[327696,512\]", r"f32\[32,32,128,128\]",
                 r"f32\[32,3,8192\]"):
        assert re.search(pool, text)
        assert not re.search(pool + r"\S* copy\(", text)


# -- the parallel decoder's two programs at published widths -------------------

@pytest.fixture(scope="module")
def parallel_engine():
    """The serve engine of ``command-a-plus-05-2026.serve-closed16-ctx8k``
    as the benchmark's runner builds it, never run: the 4.73 B parameters
    are zero-stride views of one zero, the page pools real zeros."""
    import numpy as np

    from benchmark import harness, weights
    from tpunet.config import ModelConfig, ServeConfig
    from tpunet.models import create_model
    from tpunet.serve.engine import Engine

    cell = harness.load_cell("command-a-plus-05-2026.serve-closed16-ctx8k")
    config = cell["config"]
    spec = harness.load_reference(cell).param_spec(config, "serve")
    zero = np.zeros((), jnp.bfloat16)
    params = weights.nest({path: np.broadcast_to(zero, shape)
                           for path, (shape, _, _) in spec.items()})
    serve = dict(cell["cell"]["program"]["serve"])
    serve["prefill_buckets"] = tuple(serve["prefill_buckets"])
    return Engine(create_model(ModelConfig(**config["program"]["model"])),
                  {"params": params}, ServeConfig(**serve))


@WIDTH_AND_COLUMNS
def test_parallel_masked_step_fits_the_chip(one_chip, no_compile_cache,
                                            monkeypatch, parallel_engine,
                                            width, columns):
    """The engine's own masked step, ``[16, 1]`` and ``[1, 8192]`` (over
    the whole row's keys and over a fresh admission's), over 9.47 GB of
    weights and 16 x 10,240 tokens of K/V pages in four layers: compiled
    for the chip with at least 1 GiB to spare; the width-1 program
    attends through ``tpunet_paged_decode`` (grouped, windowed in three
    layers), the bucket-wide one through the flash kernel; neither
    copies a page pool."""
    import re

    monkeypatch.setattr(paged_decode, "_on_tpu", lambda: True)
    monkeypatch.setattr(paged_decode, "_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # dispatch
    eng = parallel_engine
    assert eng.state_pool_bytes() == 0 and eng._prefix is not None
    assert eng.kv_pool_bytes() == 4 * 2 * (16 * 640 + 1) * 16 * 1024 * 2
    assert (eng._window_tokens, eng._window_bytes_share) == (4096, 0.75)
    assert (eng.pages_per_slot, eng._reach(1), eng._reach(8192)) == \
        (640, 640, 512)
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._step_avals(width, columns))
    compiled = eng._step.lower(*avals).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"parallel masked step width {width} columns {columns}: arguments "
          f"{m.argument_size_in_bytes} outputs {m.output_size_in_bytes} "
          f"aliased {m.alias_size_in_bytes} temporaries "
          f"{m.temp_size_in_bytes} total {total}")
    assert total + (1 << 30) <= V5E_BYTES_LIMIT, total
    assert 4 * total >= V5E_BYTES_LIMIT             # the driver's 25 % floor
    assert m.alias_size_in_bytes >= eng.kv_pool_bytes()   # pools donated
    text = compiled.as_text()
    assert ("tpunet_paged_decode" in text) == (width == 1)
    assert ("tpunet_flash_fwd" in text) == (width > 1)
    pool = r"bf16\[163856,1024\]"
    assert re.search(pool, text)
    assert not re.search(pool + r"\S* copy\(", text)


# -- the grouped-query decoder that trains: kernels and the whole step ---------

@pytest.mark.parametrize("window", [4096, 0], ids=["window", "global"])
def test_grouped_windowed_flash_compiles_forward_and_backward(
        one_chip, no_compile_cache, window):
    """``flash_prefill`` differentiated at smallthinker-21ba3b's train
    shape — one row of 8192 tokens, 28 query heads over 4 KV heads of
    128 — under the 4096-key window and without one: the forward that
    keeps its log-sum-exp, dQ on the band, dK/dV by KV head over the
    group and the band transposed; no [T, T] tensor."""
    from tpunet.ops.flash import flash_prefill

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(flash_prefill(
            *a, window=window, interpret=False).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    text = _compile(grads, ((1, 8192, 28, 128), BF16),
                    ((1, 8192, 4, 128), BF16), ((1, 8192, 4, 128), BF16),
                    sharding=one_chip)
    assert text.count("tpu_custom_call") == 3
    assert "8192,8192" not in text
    # dK and dV leave their kernel by KV head: no sum over query heads after
    assert re.search(r"= \(bf16\[1,4,8192,128\]\S*, bf16\[1,4,8192,128\]\S*\) "
                     r"custom-call", text)


@pytest.mark.parametrize("shape", [(8, 1024, 25, 64), (1, 8192, 20, 256)],
                         ids=["gpt2-xl", "glm-4.7-flash"])
def test_the_accepted_train_cells_flash_calls_lower_as_before(
        one_chip, no_compile_cache, shape):
    """One head count and no window — the attention of
    ``gpt2-xl.train-b8-t1024`` and of ``glm-4.7-flash.train-b1-t8192`` —
    still lowers to the three kernels on the triangular grids: the
    group and the band are static Python branches the shared entry does
    not take."""
    from tpunet.ops import flash

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, interpret=False).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    text = _compile(grads, *[(shape, BF16)] * 3, sharding=one_chip)
    assert text.count("tpu_custom_call") == 3
    assert flash._use_tri(True, shape[1], shape[1], 512, 512)


@pytest.fixture(scope="module")
def smallthinker_step():
    """The ``Trainer``'s LM train step of
    ``smallthinker-21ba3b.train-b1-t8192`` as shapes: the state from
    ``create_train_state`` under ``jax.eval_shape`` (656.5 M float32
    parameters with Adam's two moments), one row of 8192 tokens."""
    from benchmark import harness
    from tpunet.config import ModelConfig, OptimConfig
    from tpunet.train.state import create_train_state
    from tpunet.train.steps import make_lm_train_step

    config = harness.load_cell("smallthinker-21ba3b.train-b1-t8192")["config"]
    model_cfg = ModelConfig(**config["program"]["model"])
    optim_cfg = OptimConfig(**config["program"]["optim"])
    state = jax.eval_shape(lambda: create_train_state(
        model_cfg, optim_cfg, jax.random.PRNGKey(0), image_size=0,
        steps_per_epoch=8, epochs=1, seq_len=8))
    return make_lm_train_step(optim_cfg, model_cfg, None), state


def test_smallthinker_train_step_fits_the_chip(one_chip, no_compile_cache,
                                               monkeypatch,
                                               smallthinker_step):
    """The whole step compiled for the chip with at least 1 GiB to
    spare, per-block recomputation on: 16 flash kernels (a layer:
    forward, recomputed forward, dQ, dK/dV) and no [T, T] tensor."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # dispatch
    step, state = smallthinker_step
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(state.params))
    assert n_params == 656_529_920
    compiled = jax.jit(step, donate_argnums=0).lower(
        on_chip(state),
        jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    text = compiled.as_text()
    print(f"smallthinker train step: arguments {m.argument_size_in_bytes} "
          f"outputs {m.output_size_in_bytes} aliased "
          f"{m.alias_size_in_bytes} temporaries {m.temp_size_in_bytes} "
          f"total {total} generated code "
          f"{m.generated_code_size_in_bytes} custom calls "
          f"{text.count('tpu_custom_call')}")
    assert m.argument_size_in_bytes >= 12 * n_params
    assert total + (1 << 30) <= V5E_BYTES_LIMIT, total
    assert 4 * total >= V5E_BYTES_LIMIT             # the driver's 25 % floor
    kernels = re.findall(r"%(tpunet_flash_\w+?)(?:\.\d+)? = .*custom-call", text)
    assert (kernels.count("tpunet_flash_fwd"),
            kernels.count("tpunet_flash_bwd")) == (8, 8)
    assert "8192,8192" not in text
