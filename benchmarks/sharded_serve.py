"""Tensor-parallel cloud verify: serving throughput vs mesh size.

Runs the collaborative engine's full serve loop at TP meshes of 1, 2,
4 and 8 devices and reports verify-loop tokens/s per mesh.  The whole
measurement lives in a SUBPROCESS that forces 8 XLA host-platform
devices before importing jax (the parent process must keep its real
1-device view — same discipline as ``tests/test_multidevice.py``).

Host-platform "devices" are slices of the same CPU, so wall time can
not actually drop with mesh size here; what the benchmark checks is
that the sharded *verify phase* — the TP'd computation — stays near
the 1-device wall (per-shard work drops by the TP degree while the
host serializes the shards: n shards × work/n ≈ constant) and converts
that into the headline

    speedup_vs_1dev[n] = (verify_s_1 / verify_s_n) * n / min(n, cpus)

i.e. ideal-parallel extrapolation of the measured per-shard math, with
the serialization the 1-core container forces divided back out.  The
verify jit is timed directly (a blocking wrapper installed after
warm-up) so the replicated edge/draft phases — which the host must run
once per device here, but a real pod runs once per chip for free in
parallel — don't pollute the cloud-scaling number.  The JSON carries
``"emulated": true`` to keep the caveat attached.  End-to-end walls
per mesh are reported untracked alongside.

Also exercised and reported:

* ``lossless_bit_identical`` — a_bits=None greedy streams at mesh
  1/2/4/8 equal the unsharded engine's, token for token;
* ``kernel_interpret_parity_ok`` — ``paged_flash_mq_sharded`` (the
  shard_map'd pallas kernel) run through the Pallas interpreter against
  the unsharded kernel, exact to the bit (attention is per-kv-head
  independent, so TP introduces no reduction reordering).

    PYTHONPATH=src python -m benchmarks.sharded_serve
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

OUT = Path("BENCH_sharded_serve.json")

MESHES = (1, 2, 4, 8)

_SCRIPT = textwrap.dedent("""
    import os, sys, time, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.models.transformer import LMConfig, init_lm
    from repro.serve.engine import CollaborativeServingEngine
    from repro.core.costmodel import Channel
    from repro.launch.mesh import make_serve_mesh
    from repro.kernels import paged_attention as PA

    quick = bool(int(sys.argv[1]))
    new_tokens = 8 if quick else 24
    reps = 1 if quick else 3
    K = 4
    # n_kv=8 so every mesh size up to 8 actually shards the KV pool;
    # d_model=512 keeps per-shard GEMMs large enough that compute (which
    # TP divides) dominates per-op dispatch overhead (which it doesn't)
    CFG = LMConfig(name="sharded-bench-lm", n_layers=4, d_model=512,
                   n_heads=8, n_kv=8, d_ff=1024, vocab=1024, max_seq=128,
                   remat=False)
    params = init_lm(jax.random.PRNGKey(0), CFG)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, CFG.vocab, 12).astype(np.int32)
               for _ in range(4)]

    def build(mesh):
        return CollaborativeServingEngine(
            params, CFG, cut_layer=1, spec_k=K, max_batch=4, max_len=128,
            channel=Channel.from_kbps(10_000_000), page_size=16,
            a_bits=None, edge_int8=False, cloud_int8=False, mesh=mesh)

    def serve(eng):
        t0 = time.perf_counter()
        out = eng.generate([p.copy() for p in prompts],
                           max_new_tokens=new_tokens)
        return out, time.perf_counter() - t0

    def tap_verify(eng):
        # wrap the warm verify jit with a blocking timer: measures the
        # TP'd cloud phase alone, not the replicated edge/draft phases
        draft, verify = eng._spec_fns(K)
        acc = [0.0]
        def timed(*a, **kw):
            # dispatch is async: the draft outputs we receive are still
            # in flight, and blocking on verify's output would charge the
            # tail of the (replicated, once-per-device-on-this-host) edge
            # phase to the verify clock.  Drain the inputs first.
            jax.block_until_ready((a, kw))
            t0 = time.perf_counter()
            out = jax.block_until_ready(verify(*a, **kw))
            acc[0] += time.perf_counter() - t0
            return out
        eng._spec_jits[K] = (draft, timed)
        return acc

    ref_stream, _ = serve(build(None))

    walls, verify_s, streams = {}, {}, {}
    for n in (1, 2, 4, 8):
        eng = build(make_serve_mesh(model=n))
        streams[n], _ = serve(eng)             # warm every phase jit
        acc = tap_verify(eng)
        best_w, best_v = None, None
        for _ in range(reps):
            acc[0] = 0.0
            _, w = serve(eng)
            if best_v is None or acc[0] < best_v:
                best_w, best_v = w, acc[0]
        walls[n], verify_s[n] = best_w, best_v

    # shard_map kernel through the Pallas interpreter vs the plain kernel
    B, S, H, NKV, HD, PAGE, NP, PPS = 2, 3, 8, 4, 16, 8, 12, 4
    q = jnp.asarray(rng.randn(B, S, H, HD), jnp.float32)
    kp = jnp.asarray(rng.randint(-127, 127, (NP, NKV, PAGE, HD)), jnp.int8)
    vp = jnp.asarray(rng.randint(-127, 127, (NP, NKV, PAGE, HD)), jnp.int8)
    bt = jnp.asarray(rng.permutation(NP)[:B * PPS].reshape(B, PPS), jnp.int32)
    lens = jnp.asarray([17, 25], jnp.int32)
    ks = jnp.asarray(np.abs(rng.randn(B, NKV)) * 0.02, jnp.float32)
    plain = PA.paged_flash_mq(q, kp, vp, bt, lens, lens - S, ks, ks,
                              interpret=True)
    sharded = PA.paged_flash_mq_sharded(
        q, kp, vp, bt, lens, lens - S, ks, ks,
        mesh=make_serve_mesh(model=4, data=2), interpret=True)
    kerr = float(jnp.abs(sharded - plain).max())

    cpus = os.cpu_count() or 1
    result = {
        "emulated": True,
        "cpu_count": cpus,
        "config": CFG.name,
        "new_tokens": new_tokens,
        "wall_s": {str(n): walls[n] for n in walls},
        "verify_s": {str(n): verify_s[n] for n in verify_s},
        "verify_tokens_per_s": {
            str(n): 4 * new_tokens / verify_s[n] for n in verify_s},
        "speedup_vs_1dev": {
            str(n): (verify_s[1] / verify_s[n]) * n / min(n, cpus)
            for n in verify_s},
        "lossless_bit_identical": all(streams[n] == ref_stream
                                      for n in streams),
        "kernel_interpret_parity_maxerr": kerr,
        "kernel_interpret_parity_ok": kerr == 0.0,
    }
    print("SHARDED_JSON " + json.dumps(result))
""")


def run(print_fn=print, quick: bool = False) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(int(quick))],
        capture_output=True, text=True, env=env, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("SHARDED_JSON ")]
    assert line, proc.stdout[-4000:]
    result = json.loads(line[-1][len("SHARDED_JSON "):])

    for n in MESHES:
        print_fn(f"mesh {n}: wall {result['wall_s'][str(n)]*1e3:8.1f} ms  "
                 f"verify {result['verify_s'][str(n)]*1e3:7.1f} ms  "
                 f"{result['verify_tokens_per_s'][str(n)]:7.1f} vtok/s  "
                 f"speedup_vs_1dev(emulated) "
                 f"{result['speedup_vs_1dev'][str(n)]:.2f}x")
    print_fn(f"lossless streams bit-identical across meshes: "
             f"{result['lossless_bit_identical']}")
    print_fn(f"shard_map kernel interpret parity: "
             f"{result['kernel_interpret_parity_ok']} "
             f"(maxerr {result['kernel_interpret_parity_maxerr']:.1e})")

    OUT.write_text(json.dumps(result, indent=2, sort_keys=True) + "\n")
    print_fn(f"wrote {OUT}")
    return result


if __name__ == "__main__":
    run()
