"""The phases that tile the serve engine's thread (PR 37): one span
class for trainer and engine (``tpunet/obs/spans.py`` ``Span``), the
engine's ``_phase`` names and counts on a fixed script, the seconds
they add up (``PhaseClock``) against the wall time of the iterations,
the operator's copy in the ``obs_serve`` record, and the trainer's two
epoch-edge spans."""

import contextlib
import os
import sys
import time

import jax
import pytest

from tpunet.config import ModelConfig, ServeConfig
from tpunet.models import create_model, init_variables
from tpunet.obs import spans as spans_mod
from tpunet.obs.registry import MemorySink
from tpunet.serve import Engine
from tpunet.serve import engine as engine_mod
from tpunet.serve.engine import HOST_PHASES, build_serve_record

from _serve_script import SAMPLING, drive, staggered_script

TINY = ModelConfig(name="lm", vit_hidden=32, vit_depth=2, vit_heads=2,
                   dropout_rate=0.0, dtype="float32", vocab_size=31,
                   max_seq_len=48)
P = "tpunet/serve_"


@pytest.fixture(scope="module")
def tiny_lm():
    model = create_model(TINY)
    return model, init_variables(model, jax.random.PRNGKey(0), seq_len=8)


def make_engine(tiny_lm, **cfg_kw):
    model, variables = tiny_lm
    cfg_kw.setdefault("slots", 3)
    cfg_kw.setdefault("queue_max", 16)
    cfg_kw.setdefault("prefill_buckets", (8, 16))
    cfg_kw.setdefault("emit_every_s", 0.0)
    return Engine(model, variables, ServeConfig(**cfg_kw))


def script():
    return staggered_script(SAMPLING["greedy"], TINY.vocab_size)


class _Ring:
    def __init__(self):
        self.events = []

    def record(self, kind, message):
        self.events.append((kind, message))


# -- the one span class ---------------------------------------------------


def test_span_adds_self_time_by_name_and_children_take_their_part():
    clock = spans_mod.PhaseClock()
    with spans_mod.Span(contextlib.nullcontext(), "outer", clock=clock):
        time.sleep(0.02)
        for _ in range(2):
            with spans_mod.Span(contextlib.nullcontext(), "inner",
                                clock=clock):
                time.sleep(0.03)
    assert clock.open is None
    outer, inner = clock.totals["outer"], clock.totals["inner"]
    assert (outer.count, inner.count) == (1, 2)
    assert 0.06 <= inner.seconds < 0.2
    assert 0.03 <= inner.longest < inner.seconds
    # its children's 0.06 s are left out of the parent's own time
    assert 0.02 <= outer.seconds == outer.longest < 0.06


def test_span_rings_begin_and_end_even_when_the_body_raises():
    ring, clock = _Ring(), spans_mod.PhaseClock()
    with pytest.raises(RuntimeError):
        with spans_mod.Span(spans_mod.span("tpunet/x", bucket=8), "tpunet/x",
                            ring, clock):
            raise RuntimeError("device call failed")
    assert ring.events == [("span", "tpunet/x"), ("span_end", "tpunet/x")]
    assert clock.open is None and clock.totals["tpunet/x"].count == 1
    quiet = spans_mod.Span(spans_mod.span("tpunet/y"), "tpunet/y")
    with quiet:                                 # no ring, no clock: a
        pass                                    # trace annotation alone


def test_trainer_and_engine_share_the_span_class(tiny_lm):
    import tpunet.obs as obs_mod
    assert not hasattr(obs_mod, "_RecordedSpan")
    assert not hasattr(engine_mod, "_ring_span")
    obs = obs_mod.Observability.__new__(obs_mod.Observability)
    obs.enabled, obs.flightrec = True, _Ring()
    assert isinstance(obs.span("tpunet/eval"), spans_mod.Span)
    assert isinstance(obs.step_span(3), spans_mod.Span)
    with obs.step_span(3):
        pass
    assert obs.flightrec.events == [("span", "step 3"),
                                    ("span_end", "step 3")]
    eng = make_engine(tiny_lm)
    assert isinstance(eng._phase(P + "publish"), spans_mod.Span)
    assert not hasattr(ServeConfig(), "host_phases")     # no switch


# -- names, counts, nesting -----------------------------------------------


def served(tiny_lm, monkeypatch, **cfg_kw):
    """The staggered script on a fresh engine: every phase opened, as
    ``(name, the phases open around it, its trace arguments)``, the
    span events the ring was handed, the engine and its requests."""
    opened, depth, ringed = [], [], []

    @contextlib.contextmanager
    def recording(name, **args):
        opened.append((name, tuple(depth), args))
        depth.append(name)
        try:
            yield
        finally:
            depth.pop()

    monkeypatch.setattr(engine_mod, "span", recording)
    monkeypatch.setattr(
        engine_mod.flightrec, "record",
        lambda kind, message: ringed.append((kind, message)))
    eng = make_engine(tiny_lm, **cfg_kw)
    reqs = drive(eng, script())
    return opened, ringed, eng, reqs


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_every_phase_occurs_with_the_counts_expected(tiny_lm, monkeypatch,
                                                     prefix_cache):
    opened, _, eng, reqs = served(tiny_lm, monkeypatch,
                                  prefix_cache=prefix_cache)
    snap = eng.registry.snapshot()
    steps = int(snap["serve_decode_steps_total"])
    calls = int(snap["serve_prefills_total"])
    assert calls == 4 and steps > 10
    count = {p: sum(1 for n, _, _ in opened if n == P + p)
             for p in HOST_PHASES}
    assert count["decode_args"] == count["decode"] == steps
    assert count["decode_wait"] == steps          # every step is read once
    assert count["prefill_args"] == count["prefill"] == calls
    # a publish for each step read and one for each call's first tokens
    assert count["publish"] == steps + calls
    assert count["prefix_adopt"] == (calls if prefix_cache else 0)
    # admissions at iterations 0 (two requests, one pop), 3 and 7: the
    # fit and the gauge tail of each; nobody was cancelled or expired
    assert count["admit"] == 2 * 3
    assert count["idle"] == 0                     # _run's, not _iterate's
    assert count["spec_prefill"] == count["spec_draft"] == \
        count["spec_verify"] == 0
    assert {n for n, _, _ in opened} <= {P + p for p in HOST_PHASES}
    # the clock counted the same spans
    totals = eng._host_clock.totals
    assert {n: t.count for n, t in totals.items()} == {
        P + p: c for p, c in count.items() if c}
    assert sum(len(r.tokens) for r in reqs) == int(snap["serve_tokens_total"])


def test_new_phases_are_siblings_or_children_never_a_parent(tiny_lm,
                                                            monkeypatch):
    opened, ringed, _, _ = served(tiny_lm, monkeypatch)
    under = {}
    for name, around, _ in opened:
        under.setdefault(name[len(P):], set()).add(
            around[-1][len(P):] if around else "")
    # what existed keeps its place ...
    assert under["prefill"] == {""} and under["decode"] == {""}
    assert under["decode_wait"] <= {"decode", "prefill", ""}
    # ... the new ones sit between or inside, and hold no other phase
    for phase in ("admit", "prefill_args", "prefix_adopt", "decode_args"):
        assert under[phase] == {""}
    assert under["publish"] <= {"decode", "prefill", ""}
    parents = {p for around in under.values() for p in around}
    assert parents <= {"", "prefill", "decode"}
    # only the device calls' three spans reach the flight recorder's ring
    assert {m for k, m in ringed if k in ("span", "span_end")} == {
        P + "prefill", P + "decode", P + "decode_wait"}


def test_prefill_and_adoption_carry_bucket_and_prompt(tiny_lm, monkeypatch):
    opened, _, _, reqs = served(tiny_lm, monkeypatch)
    seen = [args for n, _, args in opened if n == P + "prefill"]
    assert seen == [args for n, _, args in opened
                    if n == P + "prefix_adopt"]
    assert [a["prompt_tokens"] for a in seen] == \
        [int(r.prompt.size) for r in reqs]
    assert [a["bucket"] for a in seen] == [8, 16, 8, 8]
    assert all(not args for n, _, args in opened
               if n not in (P + "prefill", P + "prefix_adopt"))


# -- the tiling, by the clock ----------------------------------------------


SLOWED = ("_fit", "_adopt_prefix_pages", "_ensure_page_capacity",
          "_update_kv_gauges", "_dispatch_step", "_sampling_args",
          "_slot_maybe_finish", "_release_pages", "_account_finish")


def test_the_phases_cover_the_iterations_wall_time(tiny_lm, monkeypatch):
    """Nothing long runs under no phase: with 3 ms added to each piece
    of host work the table names, the phases' seconds (self time, so no
    span counts twice) are at least 95 % of the wall time of the
    scripted iterations."""
    eng = make_engine(tiny_lm)
    drive(eng, script())                          # compile first

    def slowed(fn):
        def wrapper(*a, **kw):
            time.sleep(0.003)
            return fn(*a, **kw)
        return wrapper

    for name in SLOWED:
        monkeypatch.setattr(eng, name, slowed(getattr(eng, name)))
    before = {n: t.seconds for n, t in eng._host_clock.totals.items()}
    wall = [0.0]
    real = eng._iterate

    def timed():
        t0 = time.perf_counter()
        try:
            return real()
        finally:
            wall[0] += time.perf_counter() - t0

    monkeypatch.setattr(eng, "_iterate", timed)
    drive(eng, script())
    covered = sum(t.seconds - before[n]
                  for n, t in eng._host_clock.totals.items())
    assert wall[0] > 0.3                          # the sleeps were taken
    assert covered <= wall[0]
    assert covered >= 0.95 * wall[0]
    assert eng._host_clock.open is None


# -- the operator's copy ---------------------------------------------------


def test_the_record_carries_seconds_and_longest_span_by_phase(tiny_lm):
    eng = make_engine(tiny_lm)
    sink = MemorySink()
    eng.registry.add_sink(sink)
    drive(eng, script())
    assert "serve_host_s_decode" not in eng.registry.snapshot()
    eng._emit_record()
    snap = eng.registry.snapshot()
    first = sink.by_kind("obs_serve")[-1]
    ran = {n[len(P):] for n in eng._host_clock.totals}
    assert ran == {"admit", "prefill_args", "prefill", "prefix_adopt",
                   "publish", "decode_args", "decode", "decode_wait"}
    assert set(first["host_s"]) == set(first["host_max_s"]) == ran
    for phase in ran:
        total = eng._host_clock.totals[P + phase]
        assert first["host_s"][phase] == snap["serve_host_s_" + phase] \
            == round(total.seconds, 6)
        assert 0 < first["host_max_s"][phase] <= first["host_s"][phase] + 1e-6
        assert total.longest == 0.0               # handed over, then reset
    # the compiling calls were the longest spans of their phases
    assert first["host_max_s"]["prefill"] > 10 * first["host_max_s"]["publish"]
    drive(eng, script())                          # warm: no span that long
    eng._emit_record(final=True)
    last = sink.by_kind("obs_serve")[-1]
    assert last["final"] is True
    for phase in ran:
        assert last["host_s"][phase] >= first["host_s"][phase]
    assert last["host_max_s"]["prefill"] < first["host_max_s"]["prefill"]
    assert sum(last["host_s"].values()) <= last["uptime_s"]


def test_build_serve_record_reads_the_gauges_and_the_schema_accepts_it():
    from tpunet.obs.registry import Registry
    reg = Registry()
    bare = build_serve_record(reg, queue_depth=0, active_slots=0, slots=2,
                              uptime_s=1.0, window_s=1.0)
    assert bare["host_s"] == {} and bare["host_max_s"] == {}
    reg.gauge("serve_host_s_prefix_adopt").set(1.25)
    reg.gauge("serve_host_max_s_prefix_adopt").set(0.1325)
    reg.gauge("serve_host_max_s_decode_wait").set(2.0)
    rec = build_serve_record(reg, queue_depth=0, active_slots=0, slots=2,
                             uptime_s=1.0, window_s=1.0)
    assert rec["host_s"] == {"prefix_adopt": 1.25}
    assert rec["host_max_s"] == {"prefix_adopt": 0.1325, "decode_wait": 2.0}
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    try:
        checker = __import__("check_metrics_schema")
    finally:
        sys.path.pop(0)
    _, fields, _ = checker.parse_schema()
    assert {"host_s", "host_max_s"} <= fields["obs_serve"]
    emitted = [r for r in checker.collect_serve_records()
               if r["kind"] == "obs_serve"]
    assert emitted and all("host_s" in r and "host_max_s" in r
                           for r in emitted)
    assert any(r["host_s"] for r in emitted)


def test_an_engine_with_no_traffic_waits_under_serve_idle(tiny_lm):
    eng = make_engine(tiny_lm)
    sink = MemorySink()
    eng.registry.add_sink(sink)
    eng.start()
    try:
        time.sleep(0.15)
    finally:
        eng.stop()
    idle = eng._host_clock.totals[P + "idle"]
    assert idle.count >= 3 and 0.05 < idle.seconds < 1.0
    final = sink.by_kind("obs_serve")[-1]
    assert final["final"] is True and set(final["host_s"]) == {"idle"}
    assert final["host_max_s"]["idle"] <= 0.05    # one wait is 20 ms


def test_spec_decode_goes_through_the_same_helper(tiny_lm, monkeypatch):
    opened, ringed, eng, _ = served(
        tiny_lm, monkeypatch, spec_decode=True, spec_k=3,
        spec_draft_width_mult=1.0, prefix_cache=False)
    names = [n[len(P):] for n, _, _ in opened]
    verifies = int(eng.registry.snapshot()["serve_spec_verify_steps_total"])
    assert verifies > 0
    assert names.count("spec_draft") == names.count("spec_verify") > 0
    assert names.count("spec_prefill") >= 1
    # each burst publishes once; a width-1 tail step has its own read
    assert names.count("publish") == 4 + names.count("spec_verify") \
        + names.count("decode_wait")
    assert {m for k, m in ringed if k == "span"} >= {
        P + "spec_prefill", P + "spec_draft", P + "spec_verify"}


# -- the trainer's epoch edges --------------------------------------------


def test_train_one_epoch_names_its_two_edges(monkeypatch):
    from tpunet.config import (CheckpointConfig, DataConfig, MeshConfig,
                               OptimConfig, TrainConfig)
    from tpunet.train.loop import Trainer

    cfg = TrainConfig(
        epochs=1,
        data=DataConfig(dataset="synthetic_lm", batch_size=16,
                        synthetic_train_size=48, synthetic_test_size=16,
                        seq_len=32, vocab_size=32),
        model=ModelConfig(name="lm", vit_hidden=32, vit_depth=1,
                          vit_heads=2, dropout_rate=0.0, dtype="float32",
                          vocab_size=32, max_seq_len=32),
        optim=OptimConfig(learning_rate=3e-3), mesh=MeshConfig(),
        checkpoint=CheckpointConfig(save_best=False, save_last=False))
    trainer = Trainer(cfg)
    try:
        events = []
        real = trainer.obs.span

        def recording(name):
            events.append(name)
            return real(name)

        monkeypatch.setattr(trainer.obs, "span", recording)
        fetched = []
        real_summarize = sys.modules["tpunet.train.loop"].M.summarize
        monkeypatch.setattr(
            sys.modules["tpunet.train.loop"].M, "summarize",
            lambda acc: fetched.append(len(events)) or real_summarize(acc))
        summary = trainer.train_one_epoch(1)
        mark = len(events)                # close() has spans of its own
    finally:
        trainer.close()
    assert summary["count"] > 0
    events = events[:mark]
    # once an epoch each, the first before any fetch, the last around
    # the fence; nothing per step but the data waits that were there
    assert events[0] == "tpunet/train_epoch_start"
    assert events[-1] == "tpunet/train_summarize"
    assert events.count("tpunet/train_epoch_start") == 1
    assert events.count("tpunet/train_summarize") == 1
    assert set(events[1:-1]) == {"tpunet/data_wait"}
    assert len(events) - 2 == 3 + 1               # three batches, then the end
    assert fetched == [len(events)]               # the fence is inside it
