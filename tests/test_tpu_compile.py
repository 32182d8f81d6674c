"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, so a kernel the lowering refuses (block shapes
off the (8, 128) tiling, SMEM blocks it cannot place, a call GSPMD
cannot partition) fails here instead of on the chip.  Interpret-mode
parity tests cannot catch those.  Each test asserts that the compiled
HLO holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, so the
file must not touch it before one of its tests runs.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.int8_matmul import int8_matmul_pallas
from repro.kernels.ops import _pick_block
from repro.kernels.paged_attention import (_heads_per_step,
                                           paged_flash_decode,
                                           paged_flash_mq,
                                           paged_flash_mq_sharded)

HD = 128
BATCH = 4
PAGES_PER_SEQ = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _paged_args(sharding, *, s, n_heads, n_kv, page):
    n_pages = BATCH * PAGES_PER_SEQ + 1
    pool = (n_pages, n_kv, page, HD)
    return (_sds((BATCH, s, n_heads, HD), jnp.float32, sharding),
            _sds(pool, jnp.int8, sharding), _sds(pool, jnp.int8, sharding),
            _sds((BATCH, PAGES_PER_SEQ), jnp.int32, sharding),
            _sds((BATCH,), jnp.int32, sharding),
            _sds((BATCH,), jnp.int32, sharding),
            _sds((BATCH, n_kv), jnp.float32, sharding),
            _sds((BATCH, n_kv), jnp.float32, sharding))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# decode, verify, prefill, and the chat mix's largest prefill bucket
@pytest.mark.parametrize("s", [1, 8, 128, 512])
@pytest.mark.parametrize("page", [16, 64])
@pytest.mark.parametrize("n_heads,n_kv", [(32, 32), (40, 10), (32, 4)])
def test_paged_flash_mq_compiles(one_chip, n_heads, n_kv, page, s):
    args = _paged_args(one_chip, s=s, n_heads=n_heads, n_kv=n_kv, page=page)
    _assert_kernel(paged_flash_mq.lower(*args).compile())


def test_heads_per_step_rule():
    # decode and verify fold every kv head into one grid step
    # (deepseek-7b: 32 heads, group 1; phi3-medium-14b: 10 kv, group 4);
    # prefill buckets take fewer, within the VMEM row budget
    for s in (1, 4, 8):
        assert _heads_per_step(32, s) == 32
        assert _heads_per_step(10, s * 4) == 10
    assert _heads_per_step(32, 128) == 16
    assert _heads_per_step(32, 512) == 4
    assert _heads_per_step(10, 128 * 4) == 2
    assert _heads_per_step(10, 512 * 4) == 1
    assert _heads_per_step(4, 512 * 8) == 1


def test_paged_flash_decode_compiles(one_chip):
    q, kp, vp, bt, lens, _, ks, vs = _paged_args(one_chip, s=1, n_heads=32,
                                                 n_kv=32, page=16)
    q = _sds((BATCH, 32, HD), jnp.float32, one_chip)
    _assert_kernel(paged_flash_decode.lower(q, kp, vp, bt, lens, ks,
                                            vs).compile())


def test_paged_flash_mq_sharded_compiles_without_gathering_pool(topo):
    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(1, 4),
                             ("data", "model"))
    specs = (P(None, None, "model", None), P(None, "model", None, None),
             P(None, "model", None, None), P(), P(), P(),
             P(None, "model"), P(None, "model"))
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                 sharding=NamedSharding(mesh, spec))
            for a, spec in zip(_paged_args(None, s=4, n_heads=32, n_kv=32,
                                           page=16), specs)]
    fn = jax.jit(functools.partial(paged_flash_mq_sharded, mesh=mesh))
    compiled = fn.lower(*args).compile()
    _assert_kernel(compiled)
    # each chip reads only its own kv heads: no collective on the pool
    assert "all-gather" not in compiled.as_text()


def _pool_gathers(text, pool_shape):
    """HLO lines that all-gather a tensor of the per-layer pool's shape."""
    dims = "[" + ",".join(str(d) for d in pool_shape) + "]"
    return [ln for ln in text.splitlines()
            if "all-gather" in ln and dims in ln]


# mesh (data, model) x heads: a data-only mesh, and kv heads that do not
# divide the model axis, both replicate the heads inside shard_map
@pytest.mark.parametrize("mesh_shape,n_heads,n_kv",
                         [((4, 1), 32, 32), ((1, 4), 40, 10)])
def test_paged_flash_mq_sharded_compiles_with_replicated_heads(
        topo, mesh_shape, n_heads, n_kv):
    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(mesh_shape),
                             ("data", "model"))
    rep = NamedSharding(mesh, P())
    args = _paged_args(rep, s=4, n_heads=n_heads, n_kv=n_kv, page=16)
    fn = jax.jit(functools.partial(paged_flash_mq_sharded, mesh=mesh))
    compiled = fn.lower(*args).compile()
    _assert_kernel(compiled)
    assert not _pool_gathers(compiled.as_text(), args[1].shape)


def _sds_like(tree, sharding):
    """ShapeDtypeStructs of ``tree``'s leaves, placed by ``sharding`` (one
    sharding for the whole tree, or a tree of them)."""
    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding), tree)
    return jax.tree.map(lambda a, sh: _sds(a.shape, a.dtype, sh), tree,
                        sharding)


@pytest.fixture(scope="module")
def tp_engine(topo):
    """A small INT8 collaborative engine (hd 128, four kv heads) built on
    a (1, 4) v5e mesh; its state stays on the host, described as placed
    on the mesh."""
    from repro.models.transformer import LMConfig, init_lm
    from repro.serve import engine as E
    from repro.serve.sharding import collab_shardings

    cfg = LMConfig(name="tp-compile", n_layers=3, d_model=512, n_heads=4,
                   n_kv=4, d_ff=1024, vocab=1024, max_seq=128,
                   dtype=jnp.bfloat16, remat=False)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(1, 4),
                             ("data", "model"))
    with pytest.MonkeyPatch.context() as mp:
        # described devices hold no buffers: skip the placement
        mp.setattr(E, "place_collab_engine", lambda eng: None)
        eng = E.CollaborativeServingEngine(params, cfg, cut_layer=1,
                                           max_batch=4, max_len=64,
                                           spec_k=4, mesh=mesh)
    state = {name: _sds_like(getattr(eng, name), sh)
             for name, sh in collab_shardings(eng, mesh).items()}
    return eng, state


def _phase_args(eng, state, phase):
    """(the engine's jitted phase, argument descriptions)."""
    rep = NamedSharding(eng.mesh, P())
    b, k = eng.max_batch, eng.spec_k
    vec = _sds((b,), jnp.int32, rep)
    bt = _sds((b, eng._pool.pages_per_slot), jnp.int32, rep)
    toks = _sds((b, 32), jnp.int32, rep)
    edge = (state["edge_blocks"], state["embed"], toks, state["_edge_cache"],
            vec, bt, vec)
    if phase == "edge_prefill":
        return eng._edge_prefill, edge
    blob, qp, _ = jax.eval_shape(eng._edge_prefill_impl, *edge)
    blob, qp = _sds_like(blob, rep), _sds_like(qp, rep)
    if phase == "cloud_prefill":
        return eng._cloud_prefill, (
            state["cloud_blocks"], state["tail"], blob, qp,
            state["_cloud_cache"], vec, bt, vec, vec, vec)
    draft_fn, verify_fn = eng._spec_fns(k)
    draft = (state["edge_blocks"], state["draft_blocks"], state["embed"],
             state["tail"], vec, state["_edge_cache"], state["_draft_cache"],
             vec, bt)
    if phase == "draft":
        return draft_fn, draft
    blobs, scales, zps, drafts, _, _ = jax.eval_shape(
        functools.partial(eng._spec_draft_impl, k), *draft)
    return verify_fn, (state["cloud_blocks"], state["tail"],
                       *_sds_like((blobs, scales, zps, drafts), rep),
                       state["_cloud_cache"], vec, bt)


_PHASE_IMPL = {"edge_prefill": "edge_prefill_impl",
               "cloud_prefill": "cloud_prefill_impl",
               "draft": "spec_draft_impl", "verify": "verify_impl"}


@pytest.mark.parametrize("phase", list(_PHASE_IMPL))
def test_tp_engine_phase_compiles_without_gathering_pool(tp_engine, phase,
                                                         monkeypatch):
    # every phase of a mesh engine, edge ones included, runs the compiled
    # kernel inside shard_map and never gathers a KV pool onto a chip
    from repro.kernels import paged_attention as PA

    eng, state = tp_engine
    monkeypatch.setattr(PA, "_DEFAULT_IMPL", "pallas")
    fn, args = _phase_args(eng, state, phase)
    compiled = fn.lower(*args).compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    # the phase compiles under its method's name, and the kernel's calls
    # keep the name ``kernel.paged_attn_share`` matches
    assert text.startswith(f"HloModule jit__{_PHASE_IMPL[phase]}"), phase
    assert "paged_flash" in text, phase
    for pool in (eng._edge_cache, eng._cloud_cache, eng._draft_cache):
        assert not _pool_gathers(text, pool["k_pages"].shape[1:]), phase


@pytest.mark.parametrize("m,k,n", [(256, 4096, 11008), (8, 4096, 4096)])
def test_int8_matmul_compiles(one_chip, m, k, n):
    bm, bn, bk = _pick_block(m, n, k, (256, 256, 256), interpret=False)
    pad = lambda d, b: -(-d // b) * b  # noqa: E731
    mp, kp, np_ = pad(m, bm), pad(k, bk), pad(n, bn)
    f32 = functools.partial(_sds, dtype=jnp.float32, sharding=one_chip)
    args = (_sds((mp, kp), jnp.int8, one_chip),
            _sds((kp, np_), jnp.int8, one_chip),
            f32(()), f32(()), f32((np_,)), f32((np_,)), f32((np_,)),
            f32(()), f32(()))
    fn = functools.partial(int8_matmul_pallas, true_k=k, block=(bm, bn, bk))
    _assert_kernel(jax.jit(fn).lower(*args).compile())


def test_int8_matmul_pads_small_block_requests(one_chip):
    # a block below the lowering's (32, 128, 128) int8 floor is refused;
    # compiled, the wrapper pads the operands to the floor instead
    from repro.core.quant import compute_qparams
    from repro.kernels.ops import int8_matmul

    qa = compute_qparams(jnp.linspace(-4.0, 3.0, 64))
    qw = compute_qparams(jnp.linspace(-1.0, 1.0, 64).reshape(1, 64)
                         * jnp.ones((4, 1)), axis=1)
    fn = jax.jit(lambda a, b: int8_matmul(a, b, qa, qw, block=(16, 16, 128),
                                          interpret=False))
    args = (_sds((8, 4096), jnp.int8, one_chip),
            _sds((4096, 64), jnp.int8, one_chip))
    _assert_kernel(fn.lower(*args).compile())
