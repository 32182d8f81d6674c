"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen3-moe-30b-a3b --shape train_4k --steps 20 --smoke

Builds the (arch × shape) cell with production shardings on the local
mesh (or the 16×16/2×16×16 production mesh under the dry-run env),
feeds the deterministic synthetic pipeline, and runs real optimizer
steps with periodic checkpointing and automatic restart-from-latest.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.data.pipeline import ImagePipeline, LatentPipeline, TokenPipeline
from repro.distributed.checkpoint import (CheckpointManager, latest_step,
                                          restore_checkpoint)
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import build_cell


def _pipeline(spec, cfg, sh, smoke):
    if spec.family == "lm":
        return TokenPipeline(vocab=cfg.vocab, seq_len=sh["seq"],
                             batch=sh["batch"])
    if spec.family == "vision":
        return ImagePipeline(img_res=sh["img"], batch=sh["batch"],
                             n_classes=getattr(cfg, "n_classes", 10))
    return LatentPipeline(latent_res=sh["img"] // 8,
                          channels=getattr(cfg, "in_ch", 4),
                          batch=sh["batch"],
                          ctx_len=getattr(cfg, "ctx_len", 4),
                          ctx_dim=getattr(cfg, "ctx_dim", 16))


def _batch_for(cell, pipe, step, rng):
    """Fill the cell's abstract batch spec from the pipeline."""
    raw = pipe.batch_at(step)
    spec_tree = cell.args[2]
    out = {}
    for k, spec in spec_tree.items():
        if k in raw:
            arr = np.asarray(raw[k])
        elif k == "noise":
            arr = rng.randn(*spec.shape)
        elif k == "t":
            if np.issubdtype(np.dtype(spec.dtype), np.integer):
                arr = rng.randint(0, 1000, spec.shape)
            else:
                arr = rng.rand(*spec.shape)
        elif k in ("txt", "vec", "ctx", "latent"):
            arr = rng.randn(*spec.shape) * 0.5
        else:
            raise KeyError(f"no synthetic source for batch key {k}")
        out[k] = jnp.asarray(np.asarray(arr).astype(spec.dtype)
                             .reshape(spec.shape))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 mesh (needs the 512-device dry-run env)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=5)
    args = ap.parse_args(argv)

    spec = get_arch(args.arch)
    shape = args.shape or next(iter(spec.shapes))
    assert spec.shapes[shape].kind == "train", f"{shape} is not a train shape"
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    cell = build_cell(args.arch, shape, mesh, smoke=args.smoke)
    print(f"arch={args.arch} shape={shape} mesh={dict(mesh.shape)} "
          f"smoke={args.smoke}")
    compiled = cell.lower().compile()

    cfg = spec.smoke if args.smoke else spec.full
    sspec = spec.shapes[shape]
    b_spec = cell.args[2]
    lead = next(iter(b_spec.values())).shape[0]
    fam_sh = {"seq": (b_spec["tokens"].shape[1]
                      if spec.family == "lm" else 0),
              "batch": lead,
              "img": (b_spec["image"].shape[1] if "image" in b_spec
                      else getattr(cfg, "img_res", 0))}
    pipe = _pipeline(spec, cfg, fam_sh, args.smoke)
    rng = np.random.RandomState(0)

    params = _concrete_init(args.arch, shape, cfg, spec, mesh, args.smoke)
    from repro.train.optim import adamw_init
    opt = adamw_init(params)

    start = 0
    mgr = None
    if args.ckpt:
        mgr = CheckpointManager(args.ckpt, every=args.ckpt_every,
                                async_save=False)
        if latest_step(args.ckpt) is not None:
            state, start, _ = restore_checkpoint(args.ckpt,
                                                 {"p": params, "o": opt})
            params, opt = state["p"], state["o"]
            print(f"restored checkpoint @ step {start}")

    for step in range(start, args.steps):
        batch = _batch_for(cell, pipe, step, rng)
        t0 = time.perf_counter()
        with mesh, jax.set_mesh(mesh):
            params, opt, metrics = compiled(params, opt, batch)
        dt = time.perf_counter() - t0
        print(f"step {step + 1:4d} loss={float(metrics['loss']):.4f} "
              f"gnorm={float(metrics['grad_norm']):.3f} {dt * 1e3:.0f}ms",
              flush=True)
        if mgr:
            mgr.maybe_save(step + 1, {"p": params, "o": opt})
    if mgr:
        mgr.wait()


def _concrete_init(arch, shape, cfg, spec, mesh, smoke):
    import dataclasses as _dc
    import jax.random as jr
    from repro.models import mmdit as MM
    from repro.models import resnet as RN
    from repro.models import transformer as TF
    from repro.models import unet as UN
    from repro.models import vit as VT
    key = jr.PRNGKey(0)
    if spec.family == "lm":
        return TF.init_lm(key, cfg)
    if spec.family == "vision":
        if isinstance(cfg, VT.ViTConfig):
            run = _dc.replace(cfg, img_res=spec.shapes[shape].img_res
                              if not smoke else cfg.img_res)
            return VT.init_vit(key, run)
        return RN.init_resnet(key, cfg)
    if isinstance(cfg, MM.MMDiTConfig):
        return MM.init_mmdit(key, cfg)
    return UN.init_unet(key, cfg)


if __name__ == "__main__":
    main()
