"""The divisibility-guarded sharding rules and the TP'd cost model.

``launch.shardings`` promises every rule is guarded: a dim that does
not divide its mesh axes stays unsharded rather than letting GSPMD pad.
The property tests sweep (mesh shape x tensor shape x param role) with
a duck-typed FakeMesh — ``jax.make_mesh`` cannot build arbitrary shapes
on one device, and the rules only ever read ``shape``/``axis_names`` —
and check, for every sharded dim of every produced spec, exact
divisibility by the product of the axes it is split over.

The cost-model half pins the tentpole's policy behavior: the per-layer
TP all-reduce term is zero for a 1-chip cloud, scales with mesh size
and activation bytes, and on a crafted grid the jointly tuned cut
moves edge-ward as the cloud mesh grows — more cloud parallelism makes
cloud layers cheap relative to the (now mesh-taxed) channel, so the
tuner hands the cloud more of the network.
"""
import dataclasses

import pytest
from jax.sharding import PartitionSpec as P

from repro.core.autotune import tune_cut_and_k
from repro.core.costmodel import (CLOUD_TITANXP_CLASS, EDGE_TX2_CLASS,
                                  Channel, DeviceModel, _tp_allreduce_s,
                                  speculative_round_time)
from repro.launch.shardings import (cache_spec, paged_pool_spec,
                                    paged_scale_spec, spec_for_param)

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    st = None


class FakeMesh:
    """Duck-typed stand-in for ``jax.sharding.Mesh``: the sharding rules
    only read ``.shape`` (a name->size mapping) and ``.axis_names``."""

    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


def _axis_sizes(mesh, entry):
    axes = entry if isinstance(entry, tuple) else (entry,)
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _assert_divisible(spec, shape, mesh, where=""):
    assert len(spec) <= len(shape), (spec, shape)
    for dim, entry in zip(shape, spec):
        if entry is None:
            continue
        assert dim % _axis_sizes(mesh, entry) == 0, \
            f"{where}: dim {dim} split over {entry} of {mesh.shape}"


if st is not None:
    MESHES = st.builds(
        lambda d, m: FakeMesh(data=d, model=m),
        st.sampled_from([1, 2, 3, 4, 8]), st.sampled_from([1, 2, 3, 4, 8]))
    DIMS = st.sampled_from([1, 2, 3, 4, 6, 8, 16, 24, 64])

    @settings(max_examples=200, deadline=None)
    @given(mesh=MESHES, d_in=DIMS, d_out=DIMS,
           path=st.sampled_from(["blocks/attn/wq", "blocks/attn/wo",
                                 "blocks/mlp/wi", "emb", "lm_head/out",
                                 "final_norm/scale"]),
           stacked=st.booleans(), zero1=st.booleans())
    def test_param_specs_always_divide(mesh, d_in, d_out, path, stacked,
                                       zero1):
        shape = (3, d_in, d_out) if stacked and path.startswith("blocks") \
            else (d_in, d_out)
        spec = spec_for_param(path, shape, mesh, zero1=zero1)
        if stacked and path.startswith("blocks"):
            assert spec[0] is None          # scan layer axis never sharded
        _assert_divisible(spec, shape, mesh, path)

    @settings(max_examples=200, deadline=None)
    @given(mesh=MESHES, batch=DIMS, seq=DIMS, n_kv=DIMS,
           head_dim=st.sampled_from([4, 8, 64, 128]))
    def test_cache_and_pool_specs_always_divide(mesh, batch, seq, n_kv,
                                                head_dim):
        dense = cache_spec(mesh, batch=batch, seq=seq, n_kv=n_kv,
                           head_dim=head_dim)
        _assert_divisible(dense, (3, batch, seq, n_kv, head_dim), mesh,
                          "dense cache")
        n_pages, page = seq, 8
        pool = paged_pool_spec(mesh, n_pages=n_pages, n_kv=n_kv,
                               head_dim=head_dim)
        _assert_divisible(pool, (3, n_pages, n_kv, page, head_dim), mesh,
                          "paged pool")
        # the pool's guarded dims are exactly pages (data) and kv-heads
        # (TP); the page payload [page_size, head_dim] is the DMA unit
        assert pool[0] is None and pool[3] is None and pool[4] is None
        scale = paged_scale_spec(mesh, batch=batch, n_kv=n_kv)
        _assert_divisible(scale, (3, batch, n_kv), mesh, "pool scales")
else:
    @pytest.mark.skip(reason="property tests need hypothesis")
    def test_param_specs_always_divide():
        pass

    @pytest.mark.skip(reason="property tests need hypothesis")
    def test_cache_and_pool_specs_always_divide():
        pass


def test_pool_replicates_heads_when_tp_does_not_divide():
    mesh = FakeMesh(data=2, model=4)
    spec = paged_pool_spec(mesh, n_pages=33, n_kv=2, head_dim=64)
    # 2 kv heads cannot split 4 ways; head_dim must NOT pick up the
    # slack (splitting it tears the per-head gather apart — see the
    # rule's docstring), and 33 pages don't divide data=2 either: the
    # whole pool replicates
    assert spec == P(None, None, None, None, None)
    assert paged_pool_spec(mesh, n_pages=32, n_kv=8, head_dim=64) == \
        P(None, "data", "model", None, None)


# ---------------------------------------------------------------------------
# Cost model: the TP all-reduce term and the mesh-driven cut shift
# ---------------------------------------------------------------------------


def test_tp_allreduce_term_zero_without_a_mesh():
    assert _tp_allreduce_s(CLOUD_TITANXP_CLASS, 4, 1e6) == 0.0   # 1 chip
    meshed = dataclasses.replace(CLOUD_TITANXP_CLASS, n_chips=4)
    assert _tp_allreduce_s(meshed, 4, 1e6) == 0.0                # no link
    linked = dataclasses.replace(meshed, link_bw=1e9)
    assert _tp_allreduce_s(linked, 0, 1e6) == 0.0                # no layers
    # ring all-reduce: 2 ARs/block x 2(n-1)/n x bytes/link
    t = _tp_allreduce_s(linked, 3, 1e6)
    assert t == pytest.approx(2 * 3 * (2 * 3 / 4) * 1e6 / 1e9)
    # grows with the mesh (toward the 2x asymptote) and with the bytes
    assert _tp_allreduce_s(dataclasses.replace(linked, n_chips=8),
                           3, 1e6) > t
    assert _tp_allreduce_s(linked, 3, 2e6) == pytest.approx(2 * t)


def test_verify_round_pays_k_times_the_allreduce_bytes():
    linked = dataclasses.replace(CLOUD_TITANXP_CLASS, n_chips=4,
                                 link_bw=1e9)
    kw = dict(edge_flops=1e7, cloud_flops=4e7, draft_flops=4e7,
              blob_bytes=128.0, edge=EDGE_TX2_CLASS,
              channel=Channel.from_kbps(10_000), acceptance=1.0,
              cloud_layers=3, cloud_act_bytes=4096.0)
    k4 = speculative_round_time(k=4, cloud=linked, **kw)
    k4_flat = speculative_round_time(k=4, cloud=linked,
                                     **dict(kw, cloud_act_bytes=0.0))
    # the verify acts are [B, k, D]: k=4 moves 4x the k=1 AR bytes
    assert k4.decode_s - k4_flat.decode_s == pytest.approx(
        _tp_allreduce_s(linked, 3, 4 * 4096.0))


def test_bigger_cloud_mesh_shifts_best_cut_edgeward():
    """The tentpole's policy consequence, discovered from the joint
    grid: with a deliberately weak single cloud chip behind a fast
    link, small meshes keep the cut deep (tiny edge prefix, the cloud
    carries little); scaling the cloud mesh makes cloud FLOPs cheap
    while the per-layer all-reduce taxes each cloud block only mildly,
    so the tuner hands the cloud the whole network — cut 0."""
    from repro.models.transformer import LMConfig

    cfg = LMConfig(name="cutshift", n_layers=8, d_model=32, n_heads=4,
                   n_kv=2, d_ff=64, vocab=64, max_seq=64, remat=False)
    edge = dataclasses.replace(EDGE_TX2_CLASS, peak_ops_int8=1e9,
                               launch_overhead_s=0.0)
    cloud1 = DeviceModel(name="tpu-sim", peak_flops_fp32=0.5e9,
                         peak_ops_int8=0.5e9, dram_bw=1e12,
                         launch_overhead_s=0.0, n_chips=1, link_bw=1e8)
    ch = Channel.from_kbps(100_000)
    best = {}
    for n in (1, 2, 4, 8):
        cut, _ = tune_cut_and_k(cfg, batch=1, channel=ch,
                                cuts=range(cfg.n_layers - 1),
                                acceptance=0.9, edge=edge,
                                cloud=cloud1.scaled(n), ks=(1, 2, 4, 8))
        best[n] = cut.cut
    assert best[1] == best[2] == 6, best
    assert best[4] == best[8] == 0, best
    assert best[4] < best[1], best
