"""Host spans of the serving loop (``serve.trace``) and the paged
kernel's page-visit counters, at smoke size on the CPU: spans nest as
the loop does and their commit lists add up to the served tokens;
switched off, no profiler call is made; the counters match a hand
count; the draft and verify programs carry their names."""
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import run, trace_reduce
from repro.models.transformer import LMConfig, init_lm
from repro.serve import trace
from repro.serve.engine import CollaborativeServingEngine

jax.config.update("jax_platform_name", "cpu")

ROOT = Path(__file__).resolve().parents[1]

CFG = LMConfig(name="trace-tiny", n_layers=3, d_model=32, n_heads=4, n_kv=2,
               d_ff=64, vocab=64, max_seq=64, remat=False)


@pytest.fixture(scope="module")
def params():
    return init_lm(jax.random.PRNGKey(0), CFG)


def _prompts(lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, CFG.vocab, n).astype(np.int32) for n in lens]


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    """A traced run of 5 requests over 2 slots: queueing, slot reuse and
    rounds of 1-3 committed tokens."""
    eng = CollaborativeServingEngine(params, CFG, cut_layer=1, max_batch=2,
                                     max_len=48, spec_k=3)
    eng.generate(_prompts([6, 9]), max_new_tokens=4)        # compile
    d = str(tmp_path_factory.mktemp("trace"))
    eng.stats = type(eng.stats)()
    trace.enable(True)
    try:
        with jax.profiler.trace(d):
            eng.generate(_prompts([6, 9, 4, 12, 7], seed=1),
                         max_new_tokens=9)
    finally:
        trace.enable(False)
    return eng, trace.read(d)


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_spans_nest_as_the_loop_does(traced):
    eng, spans = traced
    by = {n: [s for s in spans if s[0] == n] for n in {s[0] for s in spans}}
    calls = by["serve.call"]
    assert len(calls) == 1 and calls[0][3] == {"requests": 5}
    nests = {"sched.turn": "serve.call", "sched.finalize": "serve.call",
             "sched.policy": "sched.turn", "sched.admit": "sched.turn",
             "sched.page": "sched.turn", "sched.round": "sched.turn",
             "sched.commit": "sched.turn", "engine.dispatch": "sched.round",
             "engine.sync": "sched.round"}
    for child, parent in nests.items():
        assert by[child], child
        for c in by[child]:
            assert any(_inside(c, p) for p in by[parent]), (child, c)
    # one round span per round, each with one dispatch and one sync
    rounds = by["sched.round"]
    assert len(rounds) == eng.stats.decode_steps == eng.stats.spec_rounds
    for r in rounds:
        assert r[3]["k"] == 3 and r[3]["width"] >= 1
        for child in ("engine.dispatch", "engine.sync"):
            assert sum(_inside(c, r) for c in by[child]) == 1
    assert [t[3]["turn"] for t in by["sched.turn"]] == \
        list(range(len(by["sched.turn"])))
    assert sum(a[3]["group"] for a in by["sched.admit"]) == 5


def test_commit_lists_add_up_to_the_served_tokens(traced):
    eng, spans = traced
    got = {}
    for name, _, _, meta in spans:
        if name == "sched.commit":
            for item in str(meta["uids"]).split("_"):
                uid, n = item.split(":")
                got[int(uid)] = got.get(int(uid), 0) + int(n)
    assert sum(got.values()) == eng.stats.decode_tokens
    # each request: its prefill token, then 8 committed over rounds
    assert got == {uid: 8 for uid in range(5)}


def test_off_makes_no_profiler_call(params, monkeypatch):
    null = trace.span("sched.round")
    assert trace.span("serve.call", requests=1) is null
    with null as sp:
        assert sp is None

    def refuse(*a, **kw):
        raise AssertionError("TraceAnnotation made with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    eng = CollaborativeServingEngine(params, CFG, cut_layer=0, max_batch=2,
                                     max_len=32, spec_k=2)
    outs = eng.generate(_prompts([5, 7, 3]), max_new_tokens=3)
    assert [len(o) for o in outs] == [3, 3, 3]


@pytest.mark.parametrize("page,visited,live", [(4, 96, 45), (8, 48, 24)])
def test_page_visit_counters_match_a_hand_count(params, page, visited, live):
    """Prompts of 5 and 11 tokens, 2 new tokens each, k=2, 3 slots, cut
    0 (1 edge layer, 2 cloud layers): one round, whatever the drafts.

    Pages claimed (prompt + budget + k-1 headroom, at least the bucket):
    8 and 16 positions, so 2 and 4 pages of 4 (table width 4), 1 and 2
    of 8 (width 2).  Calls: 2 draft passes over 3 paged layers and one
    verify over 2, each visiting 3 rows x width pages: 8 x 3 x width.
    Live keys: draft pass i reads pos + i + 1 (pos 5 and 11), the verify
    pos + 2.  Page 4: drafts (2 + 3) + (2 + 4) = 11 pages a layer x 3,
    verify (2 + 4) x 2: 45.  Page 8: (1 + 2) x 2 x 3 + 3 x 2: 24."""
    eng = CollaborativeServingEngine(params, CFG, cut_layer=0, max_batch=3,
                                     max_len=32, spec_k=2, page_size=page)
    eng.generate(_prompts([5, 11]), max_new_tokens=2)
    assert eng.stats.spec_rounds == 1
    assert eng.stats.kv_pages_visited == visited
    assert eng.stats.kv_pages_live == live
    report = eng.stats.report()
    assert (report["kv_pages_visited"], report["kv_pages_live"]) == \
        (visited, live)


def test_draft_and_verify_programs_carry_their_names(params, tmp_path):
    """The draft and verify programs compile under their methods' names,
    not as ``jit__unknown``, and ``phase.round_ms`` still counts them
    as round programs, on a recorded CPU trace."""
    eng = CollaborativeServingEngine(params, CFG, cut_layer=0, max_batch=2,
                                     max_len=32, spec_k=2)
    prompts = _prompts([5, 7])
    eng.generate(prompts, max_new_tokens=4)                 # compile
    rounds = eng.stats.spec_rounds
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            eng.generate(prompts, max_new_tokens=4)
    r = trace_reduce.reduce_dir(str(tmp_path))
    assert {"jit__spec_draft_impl", "jit__verify_impl"} <= set(r.module_ns)
    assert not any("unknown" in m for m in r.module_ns)
    w = run.Window(calls=[], compiles=0, vocab=CFG.vocab, model=None,
                   stats={"spec_rounds": eng.stats.spec_rounds - rounds},
                   peaks={}, n_devices=1, trace=r)
    assert run.load_reader(ROOT, "phase.round_ms")(w) > 0
