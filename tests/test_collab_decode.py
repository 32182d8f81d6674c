"""Incremental collaborative decode: split-KV-cache equivalence against
the seed recompute-from-scratch path, and O(1) per-token wire traffic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.costmodel import Channel
from repro.models import layers as ML
from repro.models import transformer as TF
from repro.models.transformer import LMConfig, forward, init_lm
from repro.serve import trace
from repro.serve.engine import CollaborativeServingEngine, ServingEngine

jax.config.update("jax_platform_name", "cpu")

CFG = LMConfig(name="collab-tiny", n_layers=3, d_model=32, n_heads=4, n_kv=2,
               d_ff=64, vocab=64, max_seq=64, remat=False)


@pytest.fixture(scope="module")
def params():
    return init_lm(jax.random.PRNGKey(0), CFG)


def _prompts(n, plen=6, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, CFG.vocab, plen).astype(np.int32)
            for _ in range(n)]


@pytest.mark.parametrize("cut", [0, 1, 2])
def test_split_cache_logits_match_monolithic(params, cut):
    """Cut-aware prefill + decode over *two* caches (edge prefix, cloud
    suffix sub-ranges) must reproduce the monolithic forward's logits —
    no quantization, pure cache math."""
    b, s = 2, 8
    toks = jnp.asarray(np.stack(_prompts(b, plen=s + 1, seed=4)))
    ref, _ = forward(params, toks, CFG)

    edge, cloud = TF.split_blocks(params, CFG, cut)
    n_edge = cut + 1
    ce = TF.init_cache(CFG, b, max_len=16, layers=n_edge)
    cc = TF.init_cache(CFG, b, max_len=16, layers=CFG.n_layers - n_edge)
    rope = ML.rope_table(16, CFG.hd, base=CFG.rope_base, dtype=CFG.dtype)

    x = ML.embed(params["embed"], toks[:, :s]).astype(CFG.dtype)
    h, ce = TF.run_blocks(edge, x, CFG, rope=rope, cache=ce,
                          cache_index=jnp.int32(0))
    h, cc = TF.run_blocks(cloud, h, CFG, rope=rope, cache=cc,
                          cache_index=jnp.int32(0))
    pre = TF.lm_head(params, h[:, -1:])[:, 0]
    np.testing.assert_allclose(np.asarray(pre), np.asarray(ref[:, s - 1]),
                               rtol=2e-4, atol=2e-4)

    # one incremental step with per-slot position vector
    pos = jnp.full((b,), s, jnp.int32)
    x = ML.embed(params["embed"], toks[:, s:s + 1]).astype(CFG.dtype)
    h, ce = TF.run_blocks(edge, x, CFG, rope=rope, cache=ce, cache_index=pos)
    h, cc = TF.run_blocks(cloud, h, CFG, rope=rope, cache=cc,
                          cache_index=pos)
    step = TF.lm_head(params, h)[:, 0]
    np.testing.assert_allclose(np.asarray(step), np.asarray(ref[:, s]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cut", [0, 1, 2])
def test_incremental_decode_matches_recompute(params, cut):
    """With quantization noise out of the way (16-bit lattice, fp dense
    edge cache — the INT8 paged default is covered with quant tolerance
    in test_paged_attention), the incremental split-cache decode must
    emit exactly the seed recompute path's greedy tokens — the cache
    refactor is lossless."""
    prompts = _prompts(3)
    inc = CollaborativeServingEngine(params, CFG, cut_layer=cut,
                                     max_batch=3, max_len=32, a_bits=16,
                                     edge_paged=False, edge_int8=False,
                                     cloud_paged=False, cloud_int8=False)
    got = inc.generate(prompts, max_new_tokens=8)
    rec = CollaborativeServingEngine(params, CFG, cut_layer=cut,
                                     max_batch=3, max_len=32, a_bits=16)
    ref = rec.generate_recompute(prompts, max_new_tokens=8)
    assert got == ref


def test_incremental_int8_tracks_recompute(params):
    """At INT8 the two paths see different dynamic-quant granularities
    (per-token delta vs whole-sequence blob), so we require the prefill
    tokens to agree exactly and the streams to mostly agree after."""
    prompts = _prompts(3, seed=2)
    inc = CollaborativeServingEngine(params, CFG, cut_layer=1,
                                     max_batch=3, max_len=32)
    got = inc.generate(prompts, max_new_tokens=6)
    rec = CollaborativeServingEngine(params, CFG, cut_layer=1,
                                     max_batch=3, max_len=32)
    ref = rec.generate_recompute(prompts, max_new_tokens=6)
    assert [g[0] for g in got] == [r[0] for r in ref]
    agree = sum(a == b for r, g in zip(ref, got) for a, b in zip(r, g))
    assert agree / sum(len(r) for r in ref) >= 0.5


@pytest.mark.parametrize("plen", [6, 12])
def test_decode_bytes_per_token_are_O1(params, plen):
    """Every decode step ships the same per-request [1, D] delta (plus
    its Eq.(1) scale/zero-point and one message header) — transmitted
    bytes per generated token do not grow with sequence length, while
    the one-time prefill blob is O(S)."""
    from repro.serve.engine import _MSG_BYTES

    b = 3
    eng = CollaborativeServingEngine(params, CFG, cut_layer=1, max_batch=b,
                                     max_len=32,
                                     channel=Channel.from_kbps(100))
    eng.generate(_prompts(b, plen=plen), max_new_tokens=8)
    per_step = b * (CFG.d_model + 8) + _MSG_BYTES
    # 8 tokens = 1 from prefill + 7 decode steps, each the same delta
    assert eng.stats.decode_bytes_log == [per_step] * 7
    assert eng.stats.prefill_bytes == b * (plen * CFG.d_model + 8) \
        + _MSG_BYTES
    assert eng.stats.bytes_per_decode_token() == \
        pytest.approx(per_step / b)
    # and the recompute path really is O(S) per token, for contrast
    rec = CollaborativeServingEngine(params, CFG, cut_layer=1, max_batch=b,
                                     max_len=32)
    rec.generate_recompute(_prompts(b, plen=plen), max_new_tokens=8)
    assert rec.stats.transmitted_bytes > eng.stats.transmitted_bytes


def test_continuous_batching_mixed_lengths(params):
    """Slot scheduler: different prompt lengths join mid-flight as slots
    free up; every request still matches the naive uncached greedy."""
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, CFG.vocab, l).astype(np.int32)
               for l in (5, 8, 5, 11, 8)]
    eng = ServingEngine(params, CFG, max_batch=2, max_len=32)
    outs = eng.generate(prompts, max_new_tokens=4)
    for p, got in zip(prompts, outs):
        toks = list(p)
        for _ in range(4):
            logits, _ = forward(params, jnp.asarray([toks], jnp.int32), CFG)
            toks.append(int(jnp.argmax(logits[0, -1])))
        assert toks[len(p):] == got


def test_collab_continuous_batching_frees_slots(params):
    """The collaborative engine rides the same scheduler: more requests
    than slots drain through with split caches intact."""
    prompts = _prompts(5, seed=6)
    eng = CollaborativeServingEngine(params, CFG, cut_layer=1, max_batch=2,
                                     max_len=32, a_bits=16,
                                     edge_paged=False, edge_int8=False,
                                     cloud_paged=False, cloud_int8=False)
    outs = eng.generate(prompts, max_new_tokens=3)
    rec = CollaborativeServingEngine(params, CFG, cut_layer=1, max_batch=5,
                                     max_len=32, a_bits=16)
    ref = rec.generate_recompute(prompts, max_new_tokens=3)
    assert len(outs) == 5 and all(len(o) == 3 for o in outs)
    assert eng.stats.prefill_calls == 3          # 2 + 2 + 1 admissions
    assert outs == ref
    # idle slots are never charged to the wire: the last request decodes
    # alone, and its rounds' uplinks carry exactly one per-request delta
    # (int16 lattice at a_bits=16) + the message header
    from repro.serve.engine import _MSG_BYTES
    assert eng.stats.decode_bytes_log[-1] == (2 * CFG.d_model + 8) \
        + _MSG_BYTES


def test_timed_mode_populates_phase_latency(params, tmp_path):
    """Phases are timed by host spans on the profiler's clock
    (``serve.trace``), which block nothing: a traced run records every
    admission and every round with a positive duration."""
    eng = CollaborativeServingEngine(params, CFG, cut_layer=1, max_batch=2,
                                     max_len=32)
    trace.enable(True)
    try:
        with jax.profiler.trace(str(tmp_path)):
            eng.generate(_prompts(2), max_new_tokens=3)
    finally:
        trace.enable(False)
    spans = trace.read(str(tmp_path))
    for name, n in (("sched.admit", eng.stats.prefill_calls),
                    ("sched.round", eng.stats.decode_steps)):
        took = [b - a for s, a, b, _ in spans if s == name]
        assert len(took) == n and min(took) > 0
    # 2 requests x (3 tokens = 1 prefill + 2 decode steps)
    assert eng.stats.prefill_tokens == 12 and eng.stats.decode_tokens == 4
