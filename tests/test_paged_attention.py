"""Paged flash-decode attention + block-table page allocator.

Covers: Pallas kernel (interpret) vs XLA oracle parity, paged INT8-KV
decode tracking dense fp greedy tokens on a tiny LM, allocator
invariants (no double allocation, reclamation on retire, block-table
bounds), and the bucketed-prefill compile bound."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import paged_attention as PA
from repro.kernels.paged_attention import (paged_attention_mq_ref,
                                           paged_attention_ref,
                                           paged_flash_decode,
                                           paged_flash_mq)
from repro.models import transformer as TF
from repro.models.transformer import LMConfig, init_lm
from repro.serve.engine import (CollaborativeServingEngine, PageAllocator,
                                ServingEngine, _bucket_len)

jax.config.update("jax_platform_name", "cpu")

CFG = LMConfig(name="paged-tiny", n_layers=3, d_model=32, n_heads=4, n_kv=2,
               d_ff=64, vocab=64, max_seq=64, remat=False)


@pytest.fixture(scope="module")
def params():
    return init_lm(jax.random.PRNGKey(0), CFG)


def _prompts(n, plen=6, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, CFG.vocab, plen).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# Kernel parity
# ---------------------------------------------------------------------------


def _rand_paged(seed, *, b=3, n_heads=8, n_kv=4, hd=16, page=8, n_pages=14,
                pages_per=4, int8=True):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, n_heads, hd).astype(np.float32))
    if int8:
        kp = jnp.asarray(
            rng.randint(-127, 128, (n_pages, n_kv, page, hd)).astype(np.int8))
        vp = jnp.asarray(
            rng.randint(-127, 128, (n_pages, n_kv, page, hd)).astype(np.int8))
        ks = jnp.asarray(rng.uniform(0.01, 0.05, (b, n_kv)).astype(np.float32))
        vs = jnp.asarray(rng.uniform(0.01, 0.05, (b, n_kv)).astype(np.float32))
    else:
        kp = jnp.asarray(rng.randn(n_pages, n_kv, page, hd).astype(np.float32))
        vp = jnp.asarray(rng.randn(n_pages, n_kv, page, hd).astype(np.float32))
        ks = vs = None
    # each row gets its own permutation of physical pages (never page 0)
    bt = jnp.asarray(np.stack([
        rng.choice(np.arange(1, n_pages), pages_per, replace=False)
        for _ in range(b)]).astype(np.int32))
    lens = jnp.asarray(rng.randint(1, pages_per * page + 1, b), jnp.int32)
    return q, kp, vp, bt, lens, ks, vs


# rows of length 1, ending on a page boundary and mid-page; a table of 6
# pages of 8, of which at most 4 are live
_LENS = (1, 8, 13, 29)
_WIDTH = 6


def _block_case(seed, *, s, n_heads, n_kv, int8):
    """An S-query block over ``_LENS``: queries end at each row's last
    key (q_start = length - S, at least 0, so short rows hold query rows
    past their length, as a padded prefill bucket does)."""
    _, kp, vp, bt, _, ks, vs = _rand_paged(
        seed, b=len(_LENS), n_heads=n_heads, n_kv=n_kv, n_pages=26,
        pages_per=_WIDTH, int8=int8)
    rng = np.random.RandomState(seed + 1)
    q = jnp.asarray(rng.randn(len(_LENS), s, n_heads, 16).astype(np.float32))
    lens = jnp.asarray(_LENS, jnp.int32)
    return q, kp, vp, bt, lens, jnp.maximum(lens - s, 0), ks, vs


def _run_kernel(hb, q, kp, vp, bt, lens, q0, ks, vs):
    """The kernel in interpret mode: decode through ``paged_flash_decode``
    at S=1, ``paged_flash_mq`` otherwise, or ``hb`` heads a step forced."""
    if hb is not None:
        fn = jax.jit(functools.partial(PA._flash_mq, hb=hb, interpret=True))
        return fn(q, kp, vp, bt, lens, q0, ks, vs)
    if q.shape[1] == 1:
        return paged_flash_decode(q[:, 0], kp, vp, bt, lens, ks, vs,
                                  interpret=True)[:, None]
    return paged_flash_mq(q, kp, vp, bt, lens, q0, ks, vs, interpret=True)


@pytest.mark.parametrize("int8", [True, False])
@pytest.mark.parametrize("n_heads,n_kv,hb", [
    (8, 4, None), (8, 8, None), (8, 2, None),     # every head in one step
    (8, 8, 2), (8, 2, 1)])                        # forced below n_kv
@pytest.mark.parametrize("s", [1, 4, 16])
def test_kernel_matches_ref(s, n_heads, n_kv, hb, int8):
    """Pallas online-softmax over block-table pages == gather oracle."""
    args = _block_case(0, s=s, n_heads=n_heads, n_kv=n_kv, int8=int8)
    if hb is None:
        assert PA._heads_per_step(n_kv, s * n_heads // n_kv) == n_kv
    ref = paged_attention_mq_ref(*args)
    out = _run_kernel(hb, *args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("s", [1, 4, 16])
def test_kernel_never_reads_dead_pages(s):
    """Every page that no row reaches below its length is NaN: the
    output stays finite and equal to the reference on the clean pool,
    so the kernel neither DMAs nor multiplies a page past a row's last
    live one (``0 * NaN`` would poison the AV sum)."""
    q, kp, vp, bt, lens, q0, ks, vs = _block_case(
        2, s=s, n_heads=8, n_kv=2, int8=False)
    ref = paged_attention_mq_ref(q, kp, vp, bt, lens, q0)
    table = np.asarray(bt)
    live = {int(table[r, p]) for r, n in enumerate(_LENS)
            for p in range(-(-n // kp.shape[2]))}
    dead = np.array([pg not in live for pg in range(kp.shape[0])])
    assert dead[table].any()               # the table points at dead pages
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    kp2[dead] = np.nan
    vp2[dead] = np.nan
    out = np.asarray(_run_kernel(None, q, jnp.asarray(kp2), jnp.asarray(vp2),
                                 bt, lens, q0, ks, vs))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_kernel_respects_lengths_and_table():
    """Entries past each row's length — and pages not in its table row —
    must not influence the output."""
    q, kp, vp, bt, lens, ks, vs = _rand_paged(1)
    ref = paged_attention_ref(q, kp, vp, bt, lens, ks, vs)
    # poison everything outside the valid region of row 0's pages
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    flat_pages = set(np.asarray(bt).reshape(-1).tolist())
    for pg in range(kp2.shape[0]):
        if pg not in flat_pages:
            kp2[pg] = 127
            vp2[pg] = 127
    out = paged_flash_decode(q, jnp.asarray(kp2), jnp.asarray(vp2), bt,
                             lens, ks, vs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ref_matches_dense_sdpa():
    """Gathering the pages back into a dense cache and running the
    reference einsum softmax reproduces the paged oracle (fp path)."""
    from repro.models.layers import _sdpa

    q, kp, vp, bt, lens, _, _ = _rand_paged(2, int8=False, b=2, n_heads=4,
                                            n_kv=2)
    ref = paged_attention_ref(q, kp, vp, bt, lens)
    b, n_heads, hd = q.shape
    span = bt.shape[1] * kp.shape[2]
    k = kp[bt].transpose(0, 1, 3, 2, 4).reshape(b, span, 2, hd)
    v = vp[bt].transpose(0, 1, 3, 2, 4).reshape(b, span, 2, hd)
    k = jnp.repeat(k, 2, axis=2)
    v = jnp.repeat(v, 2, axis=2)
    dense = _sdpa(q[:, None], k, v, causal=True, q_offset=lens - 1)[:, 0]
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kernel_through_model_stack(params):
    """Force the Pallas kernel (interpret) through attention/run_blocks
    and compare against the default XLA-ref dispatch."""
    prompts = _prompts(2, plen=7, seed=3)
    ref_eng = ServingEngine(params, CFG, max_batch=2, max_len=32,
                            paged=True, page_size=8)
    ref_out = ref_eng.generate(prompts, max_new_tokens=4)
    old = PA._DEFAULT_IMPL
    PA._DEFAULT_IMPL = "pallas_interpret"
    try:
        pal_eng = ServingEngine(params, CFG, max_batch=2, max_len=32,
                                paged=True, page_size=8)
        pal_out = pal_eng.generate(prompts, max_new_tokens=4)
    finally:
        PA._DEFAULT_IMPL = old
    assert pal_out == ref_out


# ---------------------------------------------------------------------------
# Paged / INT8 engines vs dense fp greedy
# ---------------------------------------------------------------------------


def test_paged_fp_engine_matches_dense_engine(params):
    """fp page pool is a pure layout change — greedy tokens match the
    dense engine's."""
    prompts = _prompts(3, plen=6, seed=1)
    dense = ServingEngine(params, CFG, max_batch=3, max_len=32)
    paged = ServingEngine(params, CFG, max_batch=3, max_len=32, paged=True,
                          page_size=8)
    assert paged.generate(prompts, max_new_tokens=6) == \
        dense.generate(prompts, max_new_tokens=6)


def test_paged_int8_engine_tracks_dense_fp(params):
    """INT8 pages + per-slot prefill-calibrated scales reproduce dense
    fp greedy tokens within quant tolerance on the tiny LM."""
    prompts = _prompts(4, plen=8, seed=5)
    dense = ServingEngine(params, CFG, max_batch=4, max_len=32)
    q8 = ServingEngine(params, CFG, max_batch=4, max_len=32, paged=True,
                       page_size=8, int8_kv=True)
    ref = dense.generate(prompts, max_new_tokens=6)
    got = q8.generate(prompts, max_new_tokens=6)
    assert q8._cache["k_pages"].dtype == jnp.int8
    agree = sum(a == b for r, g in zip(ref, got) for a, b in zip(r, g))
    assert agree / sum(len(r) for r in ref) >= 0.6, (ref, got)
    # and the footprint really is ~1 B/elem on live pages only
    assert q8.cache_bytes(live_only=True) < dense.cache_bytes() / 3


def test_collab_default_quantized_edge_tracks_fp_edge(params):
    """The collaborative engine's default (paged INT8 edge cache with
    per-slot prefill calibration) stays within quant tolerance of the
    fp-edge-cache configuration."""
    prompts = _prompts(3, plen=6, seed=2)
    fp = CollaborativeServingEngine(params, CFG, cut_layer=1, max_batch=3,
                                    max_len=32, edge_paged=False,
                                    edge_int8=False, cloud_paged=False,
                                    cloud_int8=False)
    q8 = CollaborativeServingEngine(params, CFG, cut_layer=1, max_batch=3,
                                    max_len=32)
    assert q8.edge_paged and q8.edge_int8          # the default layout
    assert q8._edge_cache["k_pages"].dtype == jnp.int8
    ref = fp.generate(prompts, max_new_tokens=6)
    got = q8.generate(prompts, max_new_tokens=6)
    agree = sum(a == b for r, g in zip(ref, got) for a, b in zip(r, g))
    assert agree / sum(len(r) for r in ref) >= 0.6, (ref, got)


# ---------------------------------------------------------------------------
# Page allocator invariants
# ---------------------------------------------------------------------------


def test_allocator_no_double_allocation_and_reclaim():
    rng = np.random.RandomState(0)
    alloc = PageAllocator(64)
    held = {}
    for step in range(300):
        if held and (rng.rand() < 0.4 or alloc.num_free < 4):
            key = list(held)[rng.randint(len(held))]
            alloc.free(held.pop(key))
        else:
            n = int(rng.randint(1, 5))
            if n > alloc.num_free:
                continue
            pages = alloc.alloc(n)
            # bounds: physical ids stay inside the pool, never page 0
            assert all(1 <= p < 64 for p in pages)
            held[step] = pages
        # no page is ever held twice
        flat = [p for ps in held.values() for p in ps]
        assert len(flat) == len(set(flat))
        assert set(flat) == set(alloc.live)
        assert alloc.num_free == 63 - len(flat)
    for ps in held.values():
        alloc.free(ps)
    assert alloc.num_free == 63 and not alloc.live


def test_calibration_ignores_bucket_padding(params):
    """Per-slot INT8 scales calibrated from a bucket-padded prefill must
    equal the scales from the exact-length prompt: padding K/V (pad
    embeddings at tail RoPE phases) must not set a request's range."""
    import repro.models.layers as ML

    rng = np.random.RandomState(4)
    toks = rng.randint(1, CFG.vocab, (2, 9)).astype(np.int32)
    bt = jnp.asarray(np.array([[1, 2], [3, 4]], np.int32))

    def scales(tokens, last_pos):
        cache = TF.init_cache(CFG, 2, max_len=16, paged=True, page_size=8,
                              quantized=True, num_pages=5)
        _, c = TF.prefill(params, jnp.asarray(tokens), CFG, cache=cache,
                          block_tables=bt, last_pos=last_pos)
        return np.asarray(c["k_scale"]), np.asarray(c["v_scale"])

    exact_k, exact_v = scales(toks, jnp.full((2,), 8, jnp.int32))
    padded = np.zeros((2, 16), np.int32)
    padded[:, :9] = toks
    pad_k, pad_v = scales(padded, jnp.full((2,), 8, jnp.int32))
    np.testing.assert_allclose(pad_k, exact_k, rtol=1e-6)
    np.testing.assert_allclose(pad_v, exact_v, rtol=1e-6)


def test_undersized_pool_backpressures_admission(params):
    """A deliberately small page pool serializes admission instead of
    crashing: the second request waits for the first one's pages."""
    # each request needs 3 pages (6+12 tokens, page 8); pool has 4 usable
    eng = ServingEngine(params, CFG, max_batch=2, max_len=32, paged=True,
                        page_size=8, num_pages=5)
    ref = ServingEngine(params, CFG, max_batch=2, max_len=32)
    prompts = _prompts(2, plen=6, seed=9)
    got = eng.generate(prompts, max_new_tokens=12)
    assert got == ref.generate(prompts, max_new_tokens=12)
    assert eng.stats.prefill_calls == 2       # serialized, not batched
    assert eng._pool.allocator.num_free == 4  # fully reclaimed

    # a pool that can never hold even one max-length slot is a config
    # error, rejected at construction (intentional undersizing only
    # bounds concurrency, never feasibility)
    with pytest.raises(ValueError, match="page pool"):
        ServingEngine(params, CFG, max_batch=2, max_len=32, paged=True,
                      page_size=8, num_pages=2)


def test_allocator_exhaustion_and_double_free_raise():
    alloc = PageAllocator(4)
    pages = alloc.alloc(3)
    with pytest.raises(RuntimeError):
        alloc.alloc(1)
    alloc.free(pages[:1])
    with pytest.raises(ValueError):
        alloc.free(pages[:1])


def test_engine_returns_pages_on_retire(params):
    """More requests than slots: pages recycle through the free list and
    the pool is fully reclaimed after the run."""
    eng = ServingEngine(params, CFG, max_batch=2, max_len=32, paged=True,
                        page_size=8)
    pool = eng._pool.allocator
    n0 = pool.num_free
    outs = eng.generate(_prompts(5, plen=6, seed=7), max_new_tokens=4)
    assert len(outs) == 5 and all(len(o) == 4 for o in outs)
    assert pool.num_free == n0 and not pool.live
    assert np.all(eng._pool.bt == 0)


def test_paged_block_tables_stay_in_bounds(params):
    eng = CollaborativeServingEngine(params, CFG, cut_layer=1, max_batch=2,
                                     max_len=32, page_size=8)
    eng.generate(_prompts(4, plen=9, seed=8), max_new_tokens=4)
    n_pages = eng._edge_cache["k_pages"].shape[1]
    assert int(eng._pool.bt.max()) < n_pages
    assert int(eng._pool.bt.min()) >= 0


# ---------------------------------------------------------------------------
# Bucketed prefill
# ---------------------------------------------------------------------------


def test_bucket_len():
    assert [_bucket_len(p, 64) for p in (1, 5, 8, 9, 16, 17, 40)] == \
        [8, 8, 8, 16, 16, 32, 64]
    assert _bucket_len(40, 48) == 48          # capped at max_len


def test_prefill_compiles_bounded_by_buckets(params):
    """Five distinct prompt lengths, two buckets → exactly two prefill
    traces (the seed engine retraced per unique length)."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, CFG.vocab, l).astype(np.int32)
               for l in (5, 6, 7, 9, 11)]
    eng = ServingEngine(params, CFG, max_batch=1, max_len=32)
    outs = eng.generate(prompts, max_new_tokens=3)
    assert len(outs) == 5
    assert eng.stats.prefill_calls == 5
    assert eng.trace_counts["prefill"] == 2    # buckets {8, 16}
    assert eng.trace_counts["decode"] == 1


def test_bucketed_prefill_tokens_match_unbucketed(params):
    """Right-padding prompts to the bucket must not change greedy
    output: padded K/V beyond the true length are masked/overwritten."""
    from repro.models.transformer import forward

    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, CFG.vocab, l).astype(np.int32)
               for l in (5, 9, 13)]
    eng = ServingEngine(params, CFG, max_batch=3, max_len=32)
    for p, got in zip(prompts, eng.generate(prompts, max_new_tokens=4)):
        toks = list(p)
        for _ in range(4):
            logits, _ = forward(params, jnp.asarray([toks], jnp.int32), CFG)
            toks.append(int(jnp.argmax(logits[0, -1])))
        assert toks[len(p):] == got
