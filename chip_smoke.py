#!/usr/bin/env python3
"""Smoke run of the collaborative serving path on a TPU.

    python chip_smoke.py              # one chip: kernels + four serving runs
    python chip_smoke.py --chips 4    # tensor-parallel verify vs unsharded

One process that starts no children.  It needs a TPU: without one it
exits non-zero before printing any result, and it never falls back to
the CPU or to the Pallas interpreter.  Any failed check raises, so any
failed phase exits non-zero.

One chip:
  * ``paged_flash_mq`` (S=1, S=4) and ``int8_matmul`` against their XLA
    oracles, at deepseek-7b widths;
  * deepseek-7b at its published widths with the depth cut to fit one
    chip, random weights from ``--seed``, 8 requests (over 4 slots) of
    about 128 prompt tokens and 32 new tokens each, served by
    (a) the cloud-only ``ServingEngine``,
    (b) ``CollaborativeServingEngine`` at the launcher's auto cut,
        ``spec_k=4``, INT8 edge and INT8 paged KV,
    (c) the same engine lossless (``a_bits=None``, fp caches), whose
        first-step logits are held to a float32 forward and whose greedy
        stream is compared with (a),
    (d) engine (b) at temperature 0.9;
  * the compiled verify phase must hold the paged kernel
    (``tpu_custom_call``).

``--chips 4`` runs only the lossless engine on ``make_serve_mesh(model=4)``
against the same engine unsharded on device 0, and reports stream
identity, the largest first-step logit difference, and whether the
compiled verify phase all-gathers the KV pool.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PROMPT_LEN = 128        # longest prompt; lengths are drawn from [112, 128]
MAX_NEW = 32
N_REQUESTS = 8          # over 4 slots, so continuous batching admits twice
SLOTS = 4
SPEC_K = 4
MAX_LEN = PROMPT_LEN + MAX_NEW + 24     # the launcher's sizing rule
LAYERS = 7              # of deepseek-7b's 30: what one 16 GB chip holds
BANDWIDTH_KBPS, RTT_MS = 250.0, 20.0    # the launcher's default link
LOSSLESS = dict(a_bits=None, edge_int8=False, cloud_int8=False)

# stated tolerances, as a fraction of the reference's largest magnitude
PAGED_TOL = 2e-2        # f32 kernel vs f32 XLA oracle at "highest"
INT8_TOL = 1e-4         # exact int32 path, f32 epilogue
LOGIT_TOL = 5e-2        # bf16 model vs float32 forward


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, msg: str) -> None:
    """A failed check: raises, so the phase and the run fail."""
    if not ok:
        raise RuntimeError(msg)


class CompileClock:
    """Seconds spent in backend compiles (persistent-cache hits included)
    and the number of persistent-cache hits, from JAX's own events."""

    def __init__(self, jax):
        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def peak_bytes(jax) -> int:
    return max(d.memory_stats()["peak_bytes_in_use"] for d in jax.devices())


def resident(name: str, jax) -> None:
    """Device bytes held once an engine is built, before it serves:
    what the engine keeps, apart from serving transients."""
    held = max(d.memory_stats()["bytes_in_use"] for d in jax.devices())
    log(f"{name}: built, bytes_in_use {held}")


def rel_err(got, want) -> tuple:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    require(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    require(bool(np.isfinite(got).all()), "non-finite output")
    err = float(np.max(np.abs(got - want)))
    return err, float(np.max(np.abs(want)))


def check(name: str, got, want, tol: float) -> None:
    err, scale = rel_err(got, want)
    log(f"{name}: max abs err {err:.6g} (ref max |x| {scale:.6g}, "
        f"tolerance {tol:g} x that = {tol * scale:.6g})")
    require(err <= tol * scale, f"{name}: error {err} over {tol * scale}")


# -- kernels ------------------------------------------------------------------

def kernel_checks(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.quant import compute_qparams, quantize
    from repro.kernels.ops import int8_matmul
    from repro.kernels.paged_attention import (paged_attention_mq_ref,
                                               paged_flash_mq)
    from repro.kernels.ref import int8_matmul_ref

    rng = np.random.RandomState(seed)
    b, n_heads, n_kv, hd, page = SLOTS, 32, 32, 128, 16
    per_seq = 12
    n_pages = b * per_seq + 1
    kp = jnp.asarray(rng.randint(-127, 128, (n_pages, n_kv, page, hd)),
                     jnp.int8)
    vp = jnp.asarray(rng.randint(-127, 128, (n_pages, n_kv, page, hd)),
                     jnp.int8)
    bt = jnp.asarray(1 + rng.permutation(n_pages - 1).reshape(b, per_seq),
                     jnp.int32)
    lens = jnp.asarray([184, 100, 37, 160], jnp.int32)
    ks = jnp.asarray(rng.uniform(0.01, 0.05, (b, n_kv)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.05, (b, n_kv)), jnp.float32)
    for s in (1, SPEC_K):
        q = jnp.asarray(rng.randn(b, s, n_heads, hd), jnp.float32)
        q0 = lens - s
        got = paged_flash_mq(q, kp, vp, bt, lens, q0, ks, vs,
                             interpret=False)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(paged_attention_mq_ref)(q, kp, vp, bt, lens, q0,
                                                   ks, vs)
        check(f"paged_flash_mq S={s}", got, want, PAGED_TOL)

    for m, k, n in ((256, 4096, 11008), (8, 4096, 4096)):
        a = jnp.asarray(rng.uniform(-4, 3, (m, k)), jnp.float32)
        w = jnp.asarray(rng.randn(k, n) * 0.02, jnp.float32)
        qa, qw = compute_qparams(a), compute_qparams(w, axis=1)
        a_q, w_q = quantize(a, qa), quantize(w, qw)
        got = int8_matmul(a_q, w_q, qa, qw)
        want = jax.jit(int8_matmul_ref)(a_q, w_q, qa, qw)
        check(f"int8_matmul {m}x{k}x{n}", got, want, INT8_TOL)


# -- serving ------------------------------------------------------------------

def make_prompts(cfg, seed: int):
    rng = np.random.RandomState(seed)
    lens = rng.randint(PROMPT_LEN - 16, PROMPT_LEN + 1, N_REQUESTS)
    return [rng.randint(0, cfg.vocab, n).astype(np.int32) for n in lens]


def serve(name: str, eng, prompts, clock, jax, **kw):
    """One ``generate`` call: checks the streams' shape and prints wall
    time, tokens, compile seconds and peak device bytes."""
    c0, h0 = clock.secs, clock.hits
    t0 = time.perf_counter()
    outs = eng.generate(prompts, max_new_tokens=MAX_NEW, **kw)
    wall = time.perf_counter() - t0
    vocab = eng.cfg.vocab
    require(len(outs) == len(prompts) and all(
        len(o) == MAX_NEW and all(0 <= t < vocab for t in o) for o in outs),
        f"{name}: malformed streams")
    log(f"{name}: {len(outs)} requests, {sum(map(len, outs))} tokens, "
        f"wall {wall:.3f} s, compile {clock.secs - c0:.3f} s "
        f"({clock.hits - h0} cache hits), "
        f"peak_bytes_in_use {peak_bytes(jax)}")
    return outs


def first_step_logits(eng, prompts):
    """First-step logits of a collaborative engine's own prefill phases
    (edge prefill, boundary, cloud prefill through the paged kernel)
    for its first ``max_batch`` prompts, with the padded tokens."""
    import jax.numpy as jnp

    from repro.serve.scheduler import _bucket_len, _jit_phase

    n = eng.max_batch
    plens = np.array([len(p) for p in prompts[:n]], np.int32)
    bucket = _bucket_len(int(plens.max()), eng.max_len)
    toks = np.zeros((n, bucket), np.int32)
    for i, p in enumerate(prompts[:n]):
        toks[i, :len(p)] = p
    slots = np.arange(n)
    bt = eng._pool.admit(slots, plens, np.zeros(n, np.int64), bucket)
    blob, qp, eng._edge_cache = eng._edge_prefill(
        eng.edge_blocks, eng.embed, jnp.asarray(toks), eng._edge_cache,
        jnp.asarray(slots), bt, jnp.asarray(plens))
    body = _jit_phase(eng._cloud_prefill_body, donate=(4,), mesh=eng.mesh)
    eng._cloud_cache, logits = body(
        eng.cloud_blocks, eng.tail, blob, qp, eng._cloud_cache,
        jnp.asarray(slots), bt, jnp.asarray(plens))
    for s in slots:
        eng._pool.retire(int(s))
    return np.asarray(logits.astype(jnp.float32)), toks, plens


def f32_logits(params, cfg, toks, plens):
    """Plain float32 forward (no cache, XLA attention, matmuls at
    "highest") → logits at each row's last prompt position.  Weights
    are upcast one layer at a time inside the scan."""
    import jax
    import jax.numpy as jnp

    from repro.models import layers as ML
    from repro.models import transformer as TF

    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32, remat=False)

    def up(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    def run(params, toks, plens):
        x = ML.embed(up(params["embed"]), toks)
        rope = ML.rope_table(toks.shape[1], cfg.hd, base=cfg.rope_base,
                             dtype=jnp.float32)

        def body(x, bp):
            y, _, _ = TF.block_apply(up(bp), x, cfg32, rope=rope)
            return y, None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        x = x[jnp.arange(toks.shape[0]), plens - 1][:, None]
        tail = {"final_norm": params["final_norm"],
                "lm_head": params["lm_head"]}
        return TF.lm_head(up(tail), x)[:, 0]

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(run)(params, jnp.asarray(toks),
                                       jnp.asarray(plens)))


def compiled_verify_text(eng) -> str:
    """Compiled HLO of ``eng``'s greedy verify phase at k=SPEC_K, fed by
    one real draft call on the drained engine (its writes land in the
    reserved dump page)."""
    import jax.numpy as jnp

    draft_fn, verify_fn = eng._spec_fns(SPEC_K)
    b = eng.max_batch
    cur = jnp.zeros((b,), jnp.int32)
    pos = jnp.full((b,), PROMPT_LEN, jnp.int32)
    bt = jnp.zeros((b, eng._pool.pages_per_slot), jnp.int32)
    (blobs, scales, zps, drafts, eng._edge_cache,
     eng._draft_cache) = draft_fn(eng.edge_blocks, eng.draft_blocks,
                                  eng.embed, eng.tail, cur, eng._edge_cache,
                                  eng._draft_cache, pos, bt)
    return verify_fn.lower(eng.cloud_blocks, eng.tail, blobs, scales, zps,
                           drafts, eng._cloud_cache, pos,
                           bt).compile().as_text()


def stream_agreement(xs, ys) -> str:
    same = sum(x == y for x, y in zip(xs, ys))
    toks = sum(a == b for x, y in zip(xs, ys) for a, b in zip(x, y))
    total = sum(len(x) for x in xs)
    return (f"{same}/{len(xs)} streams identical, {toks}/{total} tokens "
            f"equal position by position")


def build(cfg, seed: int):
    import jax

    from repro.models.transformer import init_lm
    return jax.jit(init_lm, static_argnums=1)(jax.random.PRNGKey(seed), cfg)


def reduced_config():
    from repro.configs import get_arch
    full = get_arch("deepseek-7b").full
    cfg = dataclasses.replace(full, n_layers=LAYERS)
    log(f"model: {full.name} d_model={cfg.d_model} heads={cfg.n_heads} "
        f"n_kv={cfg.n_kv} d_ff={cfg.d_ff} vocab={cfg.vocab} "
        f"dtype={np.dtype(cfg.dtype).name}; reduced: n_layers "
        f"{full.n_layers} -> {cfg.n_layers}")
    return cfg


def collab_kwargs(cfg) -> dict:
    """The collaborative engine's settings: the launcher's auto cut
    over its default link, ``spec_k=SPEC_K``."""
    from repro.core.costmodel import Channel
    from repro.launch.serve import auto_cut

    channel = Channel.from_kbps(BANDWIDTH_KBPS, rtt_ms=RTT_MS)
    return dict(cut_layer=auto_cut(cfg, channel, PROMPT_LEN),
                channel=channel, max_len=MAX_LEN, max_batch=SLOTS,
                spec_k=SPEC_K)


def serving_one_chip(cfg, seed: int, clock) -> None:
    import gc

    import jax

    from repro.serve.engine import (CollaborativeServingEngine,
                                    SamplingParams, ServingEngine)

    params = build(cfg, seed)
    prompts = make_prompts(cfg, seed)
    collab = collab_kwargs(cfg)

    eng = ServingEngine(params, cfg, max_batch=SLOTS, max_len=MAX_LEN,
                        paged=True)
    resident("(a) cloud-only", jax)
    cloud = serve("(a) cloud-only", eng, prompts, clock, jax)
    del eng
    gc.collect()

    eng = CollaborativeServingEngine(params, cfg, **collab)
    resident(f"(b) collaborative INT8, cut {eng.cut}", jax)
    serve("(b) collaborative INT8", eng, prompts, clock, jax)
    log(f"(b) draft acceptance {eng.stats.acceptance_rate():.4f}, "
        f"spec rounds {eng.stats.spec_rounds}, wire bytes "
        f"{eng.stats.transmitted_bytes}")
    hlo = compiled_verify_text(eng)
    n_calls = hlo.count("tpu_custom_call")
    log(f"(b) compiled verify phase: {n_calls} tpu_custom_call mentions")
    require(n_calls > 0, "verify phase does not run the paged kernel")
    sampling = [SamplingParams(temperature=0.9, seed=seed + i)
                for i in range(len(prompts))]
    serve("(d) collaborative INT8, temperature 0.9", eng, prompts, clock,
          jax, sampling=sampling)
    del eng
    gc.collect()

    eng = CollaborativeServingEngine(params, cfg, **collab, **LOSSLESS)
    resident("(c) collaborative lossless", jax)
    lossless = serve("(c) collaborative lossless", eng, prompts, clock, jax)
    log(f"(c) vs (a) greedy streams: {stream_agreement(lossless, cloud)}")
    got, toks, plens = first_step_logits(eng, prompts)
    del eng
    gc.collect()
    want = f32_logits(params, cfg, toks, plens)
    check("(c) first-step logits vs float32 forward", got, want, LOGIT_TOL)
    agree = int((got.argmax(-1) == want.argmax(-1)).sum())
    log(f"(c) first-token argmax equal to the float32 forward: "
        f"{agree}/{len(plens)}")


def tp_four_chips(cfg, seed: int, clock) -> None:
    import gc

    import jax

    from repro.launch.mesh import make_serve_mesh
    from repro.serve.engine import CollaborativeServingEngine

    params = build(cfg, seed)
    prompts = make_prompts(cfg, seed)
    kw = dict(collab_kwargs(cfg), **LOSSLESS)

    eng = CollaborativeServingEngine(params, cfg, **kw)
    resident("lossless, unsharded on device 0", jax)
    one = serve("lossless, unsharded on device 0", eng, prompts, clock, jax)
    logits_one, _, _ = first_step_logits(eng, prompts)
    del eng
    gc.collect()

    mesh = make_serve_mesh(model=4)
    log(f"mesh: {dict(mesh.shape)}")
    eng = CollaborativeServingEngine(params, cfg, mesh=mesh, **kw)
    resident("lossless, TP over 4 chips", jax)
    four = serve("lossless, TP over 4 chips", eng, prompts, clock, jax)
    logits_four, _, _ = first_step_logits(eng, prompts)
    log(f"TP vs unsharded greedy streams: {stream_agreement(four, one)}; "
        f"bit-identical: {four == one}")
    err, scale = rel_err(logits_four, logits_one)
    log(f"TP vs unsharded first-step logits: max abs diff {err:.6g} "
        f"(max |logit| {scale:.6g})")
    hlo = compiled_verify_text(eng)
    require("tpu_custom_call" in hlo, "TP verify does not run the kernel")
    pool = eng._cloud_cache["k_pages"].shape[1:]
    dims = ",".join(str(d) for d in pool)
    gathers = [ln.strip() for ln in hlo.splitlines()
               if re.search(r"\ball-gather(-start)?\(", ln)]
    pool_gathers = [ln for ln in gathers if dims in ln]
    log(f"TP verify HLO: {len(gathers)} all-gathers, "
        f"{len(pool_gathers)} of the KV pool [{dims}]")
    for ln in pool_gathers[:4]:
        log(f"  {ln[:200]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {platform}")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {len(devs)}")
    log(f"device: {devs[0].device_kind} x{len(devs)}")

    from repro.kernels import ops, paged_attention
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    # the dispatchers must pick the compiled kernels, never a stand-in
    require(paged_attention._resolve_impl(None) == "pallas"
            and not ops.default_interpret(), "a kernel stand-in is active")
    clock = CompileClock(jax)
    cfg = reduced_config()

    t0 = time.perf_counter()
    if args.chips == 4:
        tp_four_chips(cfg, args.seed, clock)
    else:
        kernel_checks(args.seed)
        serving_one_chip(cfg, args.seed, clock)
    log(f"total {time.perf_counter() - t0:.3f} s, compile "
        f"{clock.secs:.3f} s, {clock.hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
