"""A wide call's key range is the call's own (PR 40): the engine hands
a call of more than one token the page-table columns its positions can
reach — ``window(ceil((start + width) / page_tokens))`` of the doubling
window buckets the spec programs always used — and the width-1 step the
whole table.

Equivalence, over the three served families (``lm``; ``latent_lm`` with
an indexer and window layers; the parallel grouped-query decoder) at a
tiny size on the CPU: the greedy tokens of an engine that cuts the table
are those of the same engine handed the whole row, and its page pools
the same to float32 rounding (the test replaces ``_window_buckets`` by
the one bucket ``pages_per_slot``: the program has no switch) — for
fresh admissions of each bucket, a prefix-cache hit, a preempted
request's resume, and the ``[slots, bucket]`` group call with rows of
different ``start``.

The program set: which table a fresh admission is dispatched with, that
a second one compiles nothing, that ``_step_avals``, ``_programs``, the
AOT store's tags and ``program_texts()`` describe the programs that
run, and what ``serve_prefill_key_reach_pct`` reads.

128 positions over 4-token pages: 32 columns, window buckets 4, 8, 16,
32; prefill buckets 16 and 32 tokens (4 and 8 columns from position 0).
"""

import functools
import re

import numpy as np
import pytest

import jax

from benchmark import weights
from tpunet.config import ModelConfig, ServeConfig
from tpunet.models import create_model, init_variables
from tpunet.serve import Engine

from _serve_script import drive

PT, MAX_LEN, BUCKETS = 4, 128, (16, 32)
WIDE = re.compile(r"/w(?!1$)\d+$")      # the benchmark readers' pattern


LM = ModelConfig(name="lm", vit_hidden=32, vit_depth=2, vit_heads=2,
                 dropout_rate=0.0, dtype="float32", vocab_size=31,
                 max_seq_len=MAX_LEN)


def _lm():
    model = create_model(LM)
    return (model, init_variables(model, jax.random.PRNGKey(0), seq_len=8),
            LM.vocab_size)


def _latent_family(module_name):
    """The tiny configuration of another test module (its reference's
    parameter spec, its seed) at this file's ``MAX_LEN``."""
    import importlib
    mod = importlib.import_module(module_name)
    model = create_model(ModelConfig(
        name="latent_lm", vocab_size=mod.VOCAB, max_seq_len=MAX_LEN,
        dtype="float32", param_dtype="float32",
        latent=mod.arch_keys(mod.CFG)))
    params = weights.make_tree(mod.REF.param_spec(mod.CFG, "serve"),
                               mod.SEED)
    return model, {"params": params}, mod.VOCAB


@functools.lru_cache(maxsize=None)
def built(name):
    """``(model, variables, vocabulary)``, built once a family."""
    return {"lm": _lm,
            "latent_lm": lambda: _latent_family("test_latent_lm"),
            "gqa": lambda: _latent_family("test_parallel_gqa_lm")}[name]()


@pytest.fixture(params=["lm", "latent_lm", "gqa"])
def family(request):
    return built(request.param)


def new_engine(family, whole_row=False, group=False, **kw):
    """An engine over ``family``; ``whole_row`` hands every call the
    whole table (the control), ``group`` sends prefill through the
    ``[slots, bucket]`` group call a mesh engine takes."""
    model, variables, _ = family
    kw = {"slots": 3, "queue_max": 8, "prefill_buckets": BUCKETS,
          "kv_page_tokens": PT, "emit_every_s": 0.0, **kw}
    eng = Engine(model, variables, ServeConfig(**kw))
    assert eng._window_buckets == (4, 8, 16, 32)
    if whole_row:
        eng._window_buckets = (eng.pages_per_slot,)
    if group:
        eng._prefill_rows = eng.slots
    return eng


def toks(vocab, n, seed):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def greedy(n):
    return dict(max_new_tokens=n, temperature=0.0)


# -- the four ways a wide call comes about ------------------------------------

def _fresh(vocab):
    """One admission of each bucket, from position 0."""
    return ([(0, toks(vocab, 9, 1), greedy(6)),
             (0, toks(vocab, 21, 2), greedy(5))],
            {(16, 4), (32, 8)}, None)


def _prefix_hit(vocab):
    """25 tokens, then 29 behind the same 20: five pages adopted, the
    suffix of 9 embedded from position 20 in the 16-bucket — 36
    positions in reach, 9 columns, the 16-column program."""
    shared = toks(vocab, 20, 3)
    return ([(0, np.concatenate([shared, toks(vocab, 5, 4)]), greedy(4)),
             (2, np.concatenate([shared, toks(vocab, 9, 5)]), greedy(6))],
            {(32, 8), (16, 16)}, None)


def _resume(vocab):
    """21 tokens through the 32-bucket (8 columns); preempted after
    iteration 6, the request comes back as prompt + generated behind
    its own five adopted pages: the suffix goes through the 16-bucket
    from position 20, two window buckets up from a fresh one's."""
    def preempt(eng, k, reqs):
        if k != 6:
            return
        eng._drain_decode()
        (i,) = [i for i, s in enumerate(eng._active)
                if s is not None and s.req is reqs[0]]
        eng._preempt_slot(i)
    return ([(0, toks(vocab, 21, 6), greedy(14))],
            {(32, 8), (16, 16)}, preempt)


def _group_rows(vocab):
    """Admitted together into the 16-bucket: a prefix hit that starts
    at 20 and a fresh prompt that starts at 0 — one group call whose
    reach is the further row's."""
    shared = toks(vocab, 20, 7)
    return ([(0, np.concatenate([shared, toks(vocab, 5, 8)]), greedy(3)),
             (3, np.concatenate([shared, toks(vocab, 9, 9)]), greedy(5)),
             (3, toks(vocab, 11, 10), greedy(5))],
            {(32, 8), (16, 16)}, None)


CASES = {"fresh": _fresh, "prefix_hit": _prefix_hit, "resume": _resume,
         "group_rows": _group_rows}


def serve(family, case, whole_row, build=new_engine):
    script, _, hook = CASES[case](family[2])
    eng = build(family, whole_row=whole_row, group=case == "group_rows")
    starts = []
    dispatch = eng._dispatch_step

    def spy(tokens, positions, active, *rest, **kw):
        if tokens.shape[1] > 1:
            starts.append(sorted(int(p) for p, a in zip(positions, active)
                                 if a))
        return dispatch(tokens, positions, active, *rest, **kw)
    eng._dispatch_step = spy
    reqs = drive(eng, script,
                 after=hook and (lambda k, reqs: hook(eng, k, reqs)))
    assert all(r.finish_reason == "length" and not r.error for r in reqs)
    return eng, [list(r.tokens) for r in reqs], starts


def assert_same_pools(got, want):
    """Every leaf of the two engines' cache trees, page by page (the
    allocators ran the same script, so a page holds the same tokens;
    page 0 takes the padded tails' rows in both) — to the last place or
    two of float32: the dropped keys weigh exactly 0, but a sum over
    fewer zeros adds in another order."""
    got, want = (jax.tree_util.tree_leaves(e._cache) for e in (got, want))
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=5e-6)


@pytest.mark.parametrize("case", list(CASES))
def test_the_reached_columns_serve_what_the_whole_row_serves(family, case):
    """Tokens and pools of the engine that cuts the table are the
    whole-row engine's; the cut engine ran exactly the programs the
    case names, the control only whole rows."""
    _, programs, _ = CASES[case](family[2])
    eng, tokens, starts = serve(family, case, whole_row=False)
    control, want, _ = serve(family, case, whole_row=True)
    assert tokens == want
    assert_same_pools(eng, control)
    assert eng._wide_run == programs
    assert {columns for _, columns in control._wide_run} == {32}
    if case == "fresh":
        assert starts == [[0], [0]]
    elif case == "group_rows":
        assert starts == [[0], [0, 20]]     # one call, two starts
        assert eng.registry.snapshot()["serve_prefix_hits_total"] == 1
    else:
        assert starts == [[0], [20]]
        assert eng.registry.snapshot()["serve_prefix_hits_total"] == 1
    if case == "resume":
        assert eng.registry.snapshot()["serve_kv_preemptions_total"] == 1


def test_the_meshs_group_call_is_cut_to_its_furthest_row():
    """The real thing: ``lm`` tensor-parallel over two CPU devices, a
    prefix hit and a fresh prompt in one ``[slots, 16]`` call."""
    from tpunet.config import MeshConfig
    from tpunet.infer.generate import load_lm
    from tpunet.parallel import make_mesh
    family = built("lm")
    mesh = make_mesh(MeshConfig(data=1, model=2))
    sharded = load_lm(LM, variables=family[1], mesh=mesh)

    def build(_, whole_row, group):
        model, variables = sharded
        eng = Engine(model, variables, ServeConfig(
            slots=3, queue_max=8, prefill_buckets=BUCKETS,
            kv_page_tokens=PT, emit_every_s=0.0), mesh=mesh)
        if whole_row:
            eng._window_buckets = (eng.pages_per_slot,)
        return eng
    eng, tokens, starts = serve(family, "group_rows", False, build)
    _, want, _ = serve(family, "group_rows", True, build)
    assert eng._prefill_rows == eng.slots
    assert tokens == want and starts == [[0], [0, 20]]
    assert eng._wide_run == {(32, 8), (16, 16)}
    # the same tokens as the engine on one device
    assert tokens == serve(family, "group_rows", False)[1]


# -- the program set ----------------------------------------------------------

class _Seen:
    """``eng._step`` with the tables it was called with kept."""

    def __init__(self, step):
        self.step, self.tables = step, []

    def __call__(self, *args):
        self.tables.append(np.asarray(args[5]).shape)
        return self.step(*args)

    def __getattr__(self, name):
        return getattr(self.step, name)


@pytest.fixture(scope="module")
def lm_family():
    return built("lm")


@pytest.mark.parametrize("bucket, n", [(16, 9), (32, 21)])
def test_a_fresh_admission_is_handed_the_window_of_its_bucket(lm_family,
                                                              bucket, n):
    """``window(ceil(bucket / page_tokens))`` columns for the prefill
    call, the whole row for every decode step; a second admission of
    the same bucket and reach compiles nothing."""
    eng = new_engine(lm_family)
    seen = eng._step = _Seen(eng._step)
    drive(eng, [(0, toks(31, n, 11), greedy(3))])
    want = eng._window(-(-bucket // PT))
    assert want == bucket // PT and eng._reach(bucket) == want
    assert seen.tables[0] == (1, want)
    assert set(seen.tables[1:]) == {(eng.slots, eng.pages_per_slot)}
    compiled = seen.step._cache_size()
    assert compiled == 2                     # the bucket's, the step's
    drive(eng, [(0, toks(31, n - 2, 12), greedy(3))])
    assert seen.step._cache_size() == compiled
    assert seen.tables.count((1, want)) == 2
    assert eng._wide_run == {(bucket, want)}


def _table_columns(text, rows):
    """Columns of the page-table parameter (the one ``[rows, n]`` int32
    entry parameter that is not the tokens') in a program's text."""
    (entry,) = re.findall(r"^ENTRY [^\n]*\n(.*?)^}", text, re.M | re.S)
    found = {int(n) for n in re.findall(
        rf"s32\[{rows},(\d+)\]\S* parameter\(", entry)}
    return found


def test_avals_programs_tags_and_texts_name_the_programs_that_run(
        lm_family, tmp_path):
    """One statement of the closed set: ``_step_avals`` (a fresh
    admission's reach unless given), ``_programs()``, the AOT store's
    tags and ``program_texts()`` agree before any call and after a
    continued row has added a program."""
    from tpunet.serve.engine import build_aot_store
    model, variables, _ = lm_family
    cfg = ServeConfig(slots=3, queue_max=8, prefill_buckets=BUCKETS,
                      kv_page_tokens=PT, emit_every_s=0.0)
    store = build_aot_store(str(tmp_path), LM, cfg)
    eng = Engine(model, variables, cfg, aot_store=store)
    fresh = [(1, 32), (16, 4), (32, 8)]
    assert eng._programs() == fresh
    assert eng.aot_status == {"w1": "compiled+saved",
                              "k4w16": "compiled+saved",
                              "k8w32": "compiled+saved"}
    assert sorted(eng._aot) == fresh
    for width, columns in fresh:
        rows = eng.slots if width == 1 else 1
        assert eng._step_avals(width)[5].shape == (rows, columns)
        assert eng._aot[(width, columns)].in_avals[0][5].shape == \
            (rows, columns)
    assert eng._step_avals(16, 16)[5].shape == (1, 16)
    texts = eng.program_texts()
    assert sorted(texts) == ["jit__masked_step/w1", "jit__masked_step/w16",
                             "jit__masked_step/w32"]
    # a continued row: the suffix of a prefix hit, from position 20
    script, _, _ = _prefix_hit(31)
    drive(eng, script)
    assert eng._programs() == [(1, 32), (16, 4), (16, 16), (32, 8)]
    assert set(eng.aot_status) == {"w1", "k4w16", "k8w32"}     # boot's
    texts = eng.program_texts()
    assert sorted(texts) == [
        "jit__masked_step/k16/w16", "jit__masked_step/w1",
        "jit__masked_step/w16", "jit__masked_step/w32"]
    wide = {label: WIDE.search(label) is not None for label in texts}
    assert wide == {"jit__masked_step/k16/w16": True,
                    "jit__masked_step/w1": False,
                    "jit__masked_step/w16": True,
                    "jit__masked_step/w32": True}
    assert re.search("/w1$", "jit__masked_step/w1")
    assert not any(re.search("/w1$", label) for label, is_wide
                   in wide.items() if is_wide)
    for label, columns in (("jit__masked_step/w16", 4),
                           ("jit__masked_step/k16/w16", 16),
                           ("jit__masked_step/w32", 8)):
        assert columns in _table_columns(texts[label], 1), label
    assert 32 in _table_columns(texts["jit__masked_step/w1"], eng.slots)
    # the next boot loads what this one saved, under the same tags
    again = Engine(model, variables, cfg, aot_store=store)
    assert again.aot_status == {"w1": "loaded", "k4w16": "loaded",
                                "k8w32": "loaded"}


def test_the_residents_two_paths_are_the_fresh_programs(lm_family):
    """``_resident`` judges the tree on the width-1 step and the widest
    bucket's fresh program — the shapes ``_step_avals`` states."""
    eng = new_engine(lm_family)
    assert [a.shape for a in eng._step_avals(32)[2:6]] == \
        [(1, 32), (1,), (1,), (1, 8)]
    assert [a.shape for a in eng._step_avals(1)[2:6]] == \
        [(3, 1), (3,), (3,), (3, 32)]


def test_the_reach_gauge_is_what_the_calls_were_handed(lm_family):
    """``serve_prefill_key_reach_pct`` = 100 × Σ (start + width) ÷ Σ
    columns × page_tokens over the wide calls: 100 after fresh
    admissions of buckets that fill their window; after a continued
    row, the sum by hand; absent before any wide call."""
    eng = new_engine(lm_family)
    eng._emit_record()
    assert "serve_prefill_key_reach_pct" not in eng.registry.snapshot()
    script, _, _ = _fresh(31)
    drive(eng, script)
    eng._emit_record()
    assert eng._prefill_keys == [16 + 32, 16 + 32]
    assert eng.registry.snapshot()["serve_prefill_key_reach_pct"] == 100.0
    script, _, _ = _prefix_hit(31)
    drive(eng, script)
    eng._emit_record()
    # + a fresh [1, 32] call (32 of 32) and a [1, 16] call from
    # position 20: 36 positions in reach, 16 columns = 64 keys handed
    assert eng._prefill_keys == [48 + 32 + 36, 48 + 32 + 64]
    assert eng.registry.snapshot()["serve_prefill_key_reach_pct"] == \
        pytest.approx(100.0 * 116 / 144, abs=1e-3)


@pytest.mark.parametrize("width, start, columns", [
    (1, 0, 32), (1, 77, 32), (16, 0, 4), (32, 0, 8), (16, 1, 8),
    (16, 20, 16), (32, 33, 32), (32, 120, 32), (5, 59, 16), (5, 60, 32)])
def test_reach_is_the_window_over_the_calls_last_position(lm_family, width,
                                                          start, columns):
    """``_reach``: the smallest window bucket whose keys cover
    ``start + width``, the whole row past the largest and for the
    width-1 step; every position written lies inside it."""
    eng = new_engine(lm_family)
    assert eng._reach(width, start) == columns
    if width > 1:
        assert min(start + width, MAX_LEN) <= columns * PT
