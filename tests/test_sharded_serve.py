"""Tensor-parallel cloud verify: sharded-engine stream parity.

The engine half runs in a SUBPROCESS with 8 forced host devices (the
main test process must keep its real 1-device view — same discipline
as ``tests/test_multidevice.py``).  In the subprocess, a lossless
(``a_bits=None``) demand-paged engine is built at TP meshes 1/2/4/8
and driven through a seeded chaos run — ``FaultyChannel`` drops/stalls
plus a ``PressureSchedule`` page-pool squeeze that forces preemption —
for several seeds; every mesh's committed greedy stream must equal the
unsharded oracle's token for token (the TP placement may move the
suffix math across devices but must never change it).  The shard_map'd
paged-attention kernel is exercised through the Pallas interpreter
against the unsharded kernel and must match to the bit.
"""
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.costmodel import Channel
    from repro.launch.mesh import make_serve_mesh
    from repro.models.transformer import LMConfig, init_lm
    from repro.serve import (CollaborativeServingEngine, FaultyChannel,
                             PressureSchedule)
    from repro.kernels import paged_attention as PA

    CFG = LMConfig(name="shard-tiny", n_layers=4, d_model=32, n_heads=4,
                   n_kv=2, d_ff=64, vocab=64, max_seq=64, remat=False)
    LOSSLESS_FP = dict(a_bits=None, edge_int8=False, cloud_int8=False,
                       page_size=8, max_batch=2, max_len=64)
    BASE_CH = Channel.from_kbps(500, rtt_ms=10)
    WINDOWS = [(0.0, 1.5, 1)]      # squeeze the pool early -> preemption
    params = init_lm(jax.random.PRNGKey(0), CFG)

    def prompts(seed):
        rng = np.random.RandomState(seed)
        return [rng.randint(0, CFG.vocab, l).astype(np.int32)
                for l in (7, 13)]

    def build(mesh):
        return CollaborativeServingEngine(params, CFG, cut_layer=1,
                                          spec_k=4, demand_paged=True,
                                          mesh=mesh, **LOSSLESS_FP)

    def chaos_run(eng, seed):
        eng.channel = FaultyChannel(BASE_CH, seed=seed, drop_p=0.15,
                                    stall_p=0.15)
        eng.pressure = PressureSchedule(WINDOWS)
        try:
            return eng.generate(prompts(seed), max_new_tokens=6)
        finally:
            eng.pressure.apply(eng._pool.allocator, float("inf"))
            eng.pressure = None

    SEEDS = (0, 1)
    oracle_eng = build(None)
    oracle = {s: chaos_run(oracle_eng, s) for s in SEEDS}
    # the chaos actually fired: link faults and a pool squeeze both hit
    assert sum(oracle_eng.channel.faults.values()) >= 1, \
        oracle_eng.channel.faults

    for n in (1, 2, 4, 8):
        eng = build(make_serve_mesh(model=n))
        # the placement really sharded something: some cloud-suffix leaf
        # is partitioned over the model axis (d_ff=64 divides all n)
        specs = [l.sharding.spec for l in jax.tree.leaves(eng.cloud_blocks)]
        assert any("model" in jax.tree.leaves(tuple(s)) for s in specs), \
            (n, specs)
        for s in SEEDS:
            got = chaos_run(eng, s)
            assert got == oracle[s], (n, s, got, oracle[s])

    # shard_map kernel through the Pallas interpreter: bit-exact vs the
    # unsharded kernel (attention is per-kv-head independent under TP)
    rng = np.random.RandomState(7)
    B, S, H, NKV, HD, PAGE, NP, PPS = 2, 3, 8, 4, 16, 8, 12, 4
    q = jnp.asarray(rng.randn(B, S, H, HD), jnp.float32)
    kp = jnp.asarray(rng.randint(-127, 127, (NP, NKV, PAGE, HD)), jnp.int8)
    vp = jnp.asarray(rng.randint(-127, 127, (NP, NKV, PAGE, HD)), jnp.int8)
    bt = jnp.asarray(rng.permutation(NP)[:B * PPS].reshape(B, PPS),
                     jnp.int32)
    lens = jnp.asarray([17, 25], jnp.int32)
    ks = jnp.asarray(np.abs(rng.randn(B, NKV)) * 0.02, jnp.float32)
    plain = PA.paged_flash_mq(q, kp, vp, bt, lens, lens - S, ks, ks,
                              interpret=True)
    sharded = PA.paged_flash_mq_sharded(
        q, kp, vp, bt, lens, lens - S, ks, ks,
        mesh=make_serve_mesh(model=4, data=2), interpret=True)
    assert bool(jnp.all(plain == sharded)), \
        float(jnp.abs(plain - sharded).max())
    dec_plain = PA.paged_flash_decode(q[:, -1], kp, vp, bt, lens, ks, ks,
                                      interpret=True)
    dec_sharded = PA.paged_flash_decode_sharded(
        q[:, -1], kp, vp, bt, lens, ks, ks,
        mesh=make_serve_mesh(model=4, data=2), interpret=True)
    assert bool(jnp.all(dec_plain == dec_sharded)), \
        float(jnp.abs(dec_plain - dec_sharded).max())

    print("SHARDED_OK")
""")


@pytest.mark.slow
def test_sharded_engine_chaos_parity_subprocess():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=600, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                          "JAX_PLATFORMS": "cpu", "HOME": "/root"})
    assert "SHARDED_OK" in proc.stdout, (
        proc.stdout[-2000:], proc.stderr[-3000:])
