"""Cell builder: one jittable (step_fn, abstract args, shardings) per
(architecture × input shape) — the unit the dry-run lowers and the
trainer/server execute.

Kinds per family:
  lm:        train (causal LM + AdamW) | prefill | decode (KV cache)
  diffusion: train (eps/RF matching + AdamW) | denoise (one sampler step)
  vision:    train (CE + AdamW) | infer (forward logits)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ShapeSpec, get_arch, input_specs
from repro.launch import shardings as SH
from repro.launch.mesh import batch_axes
from repro.models import mmdit as MM
from repro.models import resnet as RN
from repro.models import transformer as TF
from repro.models import unet as UN
from repro.models import vit as VT
from repro.train.optim import (AdamW8bitState, AdamWConfig, AdamWState,
                               adamw8bit_init, adamw8bit_update, adamw_init,
                               adamw_update)

__all__ = ["Cell", "build_cell"]


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_name: str
    kind: str
    step_fn: Callable
    args: Tuple[Any, ...]               # abstract (ShapeDtypeStruct trees)
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    mesh: Optional[Mesh] = None
    donate_argnums: Tuple[int, ...] = ()
    model_flops: float = 0.0            # 6·N·D (dense) / 6·N_active·D (MoE)
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def jit(self):
        return jax.jit(self.step_fn, in_shardings=self.in_shardings,
                       out_shardings=self.out_shardings,
                       donate_argnums=self.donate_argnums)

    def lower(self):
        # trace under the ambient mesh so bare-PartitionSpec sharding
        # constraints and shard_map calls inside model code resolve
        with self.mesh, jax.set_mesh(self.mesh):
            return self.jit().lower(*self.args)


def _abstract(fn) -> Any:
    return jax.eval_shape(fn)


def _metrics_sh(mesh: Mesh):
    return {"loss": SH.replicated(mesh), "grad_norm": SH.replicated(mesh)}


def _opt_shardings(mesh: Mesh, abstract_opt) -> Any:
    if isinstance(abstract_opt, AdamW8bitState):
        sh = lambda t: SH.param_shardings(t, mesh)
        return AdamW8bitState(step=SH.replicated(mesh),
                              m_q=sh(abstract_opt.m_q),
                              m_scale=sh(abstract_opt.m_scale),
                              v_q=sh(abstract_opt.v_q),
                              v_scale=sh(abstract_opt.v_scale))
    return AdamWState(step=SH.replicated(mesh),
                      m=SH.param_shardings(abstract_opt.m, mesh),
                      v=SH.param_shardings(abstract_opt.v, mesh))


def _train_cell(arch_id: str, sh: ShapeSpec, mesh: Mesh, *, init_fn,
                loss_fn, batch_specs: Dict[str, jax.ShapeDtypeStruct],
                batch_shardings: Dict[str, NamedSharding],
                model_flops: float, opt_cfg: AdamWConfig = AdamWConfig(),
                grad_accum: int = 1, unroll: bool = False,
                zero1: bool = False) -> Cell:
    a_params = _abstract(init_fn)
    n_params = sum(int(jnp.prod(jnp.array(l.shape)))
                   for l in jax.tree_util.tree_leaves(a_params))
    # fp32 AdamW moments cost 8 B/param; when params+grads+moments would
    # blow the 16 GB/chip budget, switch to 8-bit blockwise moments
    # (grok-314B on 256 chips is the motivating case).
    use_8bit = n_params * 12.0 / mesh.size > 14e9
    opt_init = adamw8bit_init if use_8bit else adamw_init
    opt_update = adamw8bit_update if use_8bit else adamw_update
    a_opt = _abstract(lambda: opt_init(a_params))
    p_sh = SH.param_shardings(a_params, mesh, zero1=zero1)
    o_sh = _opt_shardings(mesh, a_opt)   # moments stay fully sharded
    ba = batch_axes(mesh)
    ba_spec = ba if len(ba) > 1 else (ba[0] if ba else None)

    def step(params, opt, batch):
        if grad_accum > 1:
            # microbatch gradient accumulation: bounds per-step activation
            # memory to (global_batch/grad_accum); grads accumulate f32.
            def micro(carry, mb):
                # keep each microbatch batch-sharded over the DP axes
                mb = jax.tree_util.tree_map(
                    lambda x: jax.lax.with_sharding_constraint(
                        x, P(ba_spec, *([None] * (x.ndim - 1)))), mb)
                loss, g = jax.value_and_grad(loss_fn)(params, mb)
                acc_l, acc_g = carry
                return (acc_l + loss,
                        jax.tree_util.tree_map(jnp.add, acc_g, g)), None

            stacked = jax.tree_util.tree_map(
                lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                    *x.shape[1:]), batch)
            # accumulate in param dtype (bf16 for the big configs: the
            # f32 buffer alone would cost 4 B/param of HBM)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, p.dtype), params)
            # probe mode unrolls so every microbatch is cost-counted
            (loss, grads), _ = jax.lax.scan(
                micro, (jnp.float32(0.0), zeros), stacked,
                unroll=grad_accum if unroll else 1)
            inv = 1.0 / grad_accum
            loss = loss * inv
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_p, new_o, gnorm = opt_update(grads, opt, params, opt_cfg)
        return new_p, new_o, {"loss": loss, "grad_norm": gnorm}

    return Cell(
        arch_id=arch_id, shape_name=sh.name, kind="train", step_fn=step,
        args=(a_params, a_opt, batch_specs),
        in_shardings=(p_sh, o_sh, batch_shardings),
        out_shardings=(p_sh, o_sh, _metrics_sh(mesh)), mesh=mesh,
        donate_argnums=(0, 1), model_flops=model_flops)


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------


def _lm_model_flops(cfg: TF.LMConfig, tokens: int, *, train: bool) -> float:
    n = cfg.active_param_count()
    return (6.0 if train else 2.0) * n * tokens


def _act_pspec(mesh: Mesh, cfg: TF.LMConfig) -> Optional[tuple]:
    """Residual-stream constraint (batch over DP axes, d_model over TP) —
    bounds the remat-carry stash to (B·S·D)/(dp·tp) per device."""
    ba = batch_axes(mesh)
    if cfg.d_model % mesh.shape["model"] != 0:
        return None
    return (ba if len(ba) > 1 else ba[0], None, "model")


def _moe_shard_spec(mesh: Mesh, cfg: TF.LMConfig, batch: int,
                    ) -> Optional[tuple]:
    """shard_map spec for the MoE block: (batch_spec, model_axis)."""
    if cfg.moe is None:
        return None
    ba = batch_axes(mesh)
    dp = 1
    for a in ba:
        dp *= mesh.shape[a]
    bspec = (ba if len(ba) > 1 else ba[0]) if (ba and batch % dp == 0) \
        else None
    return (bspec, "model")


def _lm_cell(arch_id: str, sh: ShapeSpec, mesh: Mesh, cfg: TF.LMConfig,
             specs, *, unroll: bool = False,
             variant: Optional[str] = None) -> Cell:
    b, s = sh.global_batch, sh.seq_len
    ba = batch_axes(mesh)
    uf = cfg.n_layers if unroll else 1
    moe_shard = _moe_shard_spec(mesh, cfg, b)
    zero1 = variant == "zero1"
    int8kv = variant is not None and "int8kv" in variant
    s_shard = variant is not None and "sseq" in variant

    if sh.kind == "train":
        tr_cfg = dataclasses.replace(cfg, scan_unroll=uf,
                                     act_pspec=_act_pspec(mesh, cfg),
                                     moe_shard=moe_shard)
        # microbatching: bound per-device live activations; each
        # microbatch must stay divisible by the DP axes
        dp = 1
        for a in ba:
            dp *= mesh.shape[a]
        # bigger models get more accumulation (smaller live microbatch)
        want = 8 if cfg.param_count() > 1e11 else 4
        accum = 1
        for cand in (want, want // 2, 2):
            if cand >= 2 and b % (dp * cand) == 0:
                accum = cand
                break

        def loss(params, batch):
            return TF.lm_loss(params, batch, tr_cfg)
        bs = {k: SH.data_sharding(mesh, 2, batch=b) for k in specs}
        return dataclasses.replace(_train_cell(
            arch_id, sh, mesh,
            init_fn=lambda: TF.init_lm(jax.random.PRNGKey(0), tr_cfg),
            loss_fn=loss, batch_specs=specs, batch_shardings=bs,
            model_flops=_lm_model_flops(cfg, b * s, train=True),
            grad_accum=accum, unroll=unroll, zero1=zero1), mesh=mesh)

    a_params = _abstract(lambda: TF.init_lm(jax.random.PRNGKey(0), cfg))
    p_sh = SH.param_shardings(a_params, mesh)
    c_sh = SH.cache_sharding(mesh, batch=b, seq=s, n_kv=cfg.n_kv,
                             head_dim=cfg.hd)
    if s_shard:            # flash-decoding layout: sequence over model
        ba_ax = ba if len(ba) > 1 else (ba[0] if ba else None)
        b_ax = ba_ax if b % mesh.shape[ba[0]] == 0 else None
        c_sh = NamedSharding(mesh, P(None, b_ax, "model", None, None))
    cache_sh = {"k": c_sh, "v": c_sh}
    if int8kv:
        cache_sh.update(k_scale=SH.replicated(mesh),
                        v_scale=SH.replicated(mesh))
    logit_sh = SH.logits_sharding(mesh, 2, batch=b, vocab=cfg.vocab)

    if sh.kind == "prefill":
        pf_cfg = dataclasses.replace(cfg, q_chunk=2048, remat=False,
                                     scan_unroll=uf,
                                     act_pspec=_act_pspec(mesh, cfg),
                                     moe_shard=moe_shard)

        def step(params, tokens):
            cache = TF.init_cache(pf_cfg, b, max_len=s)
            return TF.prefill(params, tokens, pf_cfg, cache=cache)

        return Cell(
            arch_id=arch_id, shape_name=sh.name, kind="prefill",
            step_fn=step, args=(a_params, specs["tokens"]),
            in_shardings=(p_sh, SH.data_sharding(mesh, 2, batch=b)),
            out_shardings=(logit_sh, cache_sh), mesh=mesh,
            model_flops=_lm_model_flops(cfg, b * s, train=False))

    # decode: one new token against a seq_len cache
    score_pspec = None
    if s_shard:
        bax = (ba if len(ba) > 1 else ba[0]) \
            if (ba and b % mesh.shape[ba[0]] == 0) else None
        score_pspec = (bax, None, None, "model")
    dec_cfg = dataclasses.replace(cfg, remat=False, scan_unroll=uf,
                                  moe_shard=moe_shard,
                                  score_pspec=score_pspec)
    a_cache = _abstract(lambda: TF.init_cache(dec_cfg, b, max_len=s,
                                              quantized=int8kv))

    def step(params, cache, token, cache_index):
        return TF.decode_step(params, token, cache, cache_index, dec_cfg)

    return Cell(
        arch_id=arch_id, shape_name=sh.name, kind="decode", step_fn=step,
        args=(a_params, a_cache, specs["token"], specs["cache_index"]),
        in_shardings=(p_sh, cache_sh, SH.data_sharding(mesh, 1, batch=b),
                      SH.replicated(mesh)),
        out_shardings=(logit_sh, cache_sh), mesh=mesh,
        donate_argnums=(1,),
        model_flops=_lm_model_flops(dec_cfg, b, train=False))


# ---------------------------------------------------------------------------
# Diffusion cells
# ---------------------------------------------------------------------------


def _diff_input_sharding(mesh: Mesh, spec: jax.ShapeDtypeStruct,
                         batch: int) -> NamedSharding:
    """Batch-shard when divisible; else spatial/token-shard dim 1 over data
    (XLA spatial partitioning handles conv halos)."""
    ba = batch_axes(mesh)
    size = 1
    for a in ba:
        size *= mesh.shape[a]
    nd = len(spec.shape)
    if ba and batch % size == 0:
        return SH.data_sharding(mesh, nd, batch=batch)
    spec_axes: list = [None] * nd
    if nd >= 2 and spec.shape[1] % mesh.shape["data"] == 0:
        spec_axes[1] = "data"
    return NamedSharding(mesh, P(*spec_axes))


def _unet_cell(arch_id: str, sh: ShapeSpec, mesh: Mesh, cfg: UN.UNetConfig,
               specs) -> Cell:
    b = sh.global_batch
    lat = sh.img_res // 8
    # q-tile the full-res self-attention once the token count explodes
    qc = 2048 if lat * lat > 4096 else None
    run_cfg = dataclasses.replace(cfg, img_res=sh.img_res, q_chunk=qc)
    graph_flops = UN.make_graph(run_cfg, batch=b, latent_res=lat
                                ).total_flops()

    if sh.kind == "train":
        def loss(params, batch):
            _, alphas = UN.ddpm_schedule()
            a = alphas[batch["t"]][:, None, None, None]
            x_t = (jnp.sqrt(a) * batch["latent"]
                   + jnp.sqrt(1 - a) * batch["noise"])
            pred = UN.unet_forward(params, x_t, batch["t"], batch["ctx"],
                                   run_cfg)
            return jnp.mean(jnp.square(pred.astype(jnp.float32)
                                       - batch["noise"].astype(jnp.float32)))

        bs = {k: _diff_input_sharding(mesh, v, b) for k, v in specs.items()}
        return _train_cell(
            arch_id, sh, mesh,
            init_fn=lambda: UN.init_unet(jax.random.PRNGKey(0), run_cfg),
            loss_fn=loss, batch_specs=specs, batch_shardings=bs,
            model_flops=3.0 * graph_flops)

    a_params = _abstract(lambda: UN.init_unet(jax.random.PRNGKey(0), run_cfg))
    p_sh = SH.param_shardings(a_params, mesh)
    stride = max(1000 // max(sh.steps, 1), 1)

    def step(params, latent, t, ctx):
        return UN.ddim_step(params, latent, t, t - stride, ctx, run_cfg)

    lat_sh = _diff_input_sharding(mesh, specs["latent"], b)
    return Cell(
        arch_id=arch_id, shape_name=sh.name, kind="denoise", step_fn=step,
        args=(a_params, specs["latent"], specs["t"], specs["ctx"]),
        in_shardings=(p_sh, lat_sh, SH.replicated(mesh),
                      _diff_input_sharding(mesh, specs["ctx"], b)),
        out_shardings=lat_sh, mesh=mesh,
        model_flops=graph_flops)


def _mmdit_cell(arch_id: str, sh: ShapeSpec, mesh: Mesh, cfg: MM.MMDiTConfig,
                specs, *, unroll: bool = False) -> Cell:
    b = sh.global_batch
    uf = max(cfg.n_double, cfg.n_single) if unroll else 1
    ba = batch_axes(mesh)
    act = None
    if cfg.d_model % mesh.shape["model"] == 0:
        dp = 1
        for a in ba:
            dp *= mesh.shape[a]
        bax = (ba if len(ba) > 1 else ba[0]) if b % dp == 0 else None
        act = (bax, None, "model")
    run_cfg = dataclasses.replace(cfg, img_res=sh.img_res, scan_unroll=uf,
                                  act_pspec=act)
    n_tok = (sh.img_res // 16) ** 2 + cfg.txt_len
    graph_flops = MM.make_graph(run_cfg, batch=b).total_flops()

    if sh.kind == "train":
        def loss(params, batch):
            t = batch["t"][:, None, None]
            x_t = (1 - t) * batch["latent"] + t * batch["noise"]
            v = MM.mmdit_forward(params, x_t, batch["t"] * 1000,
                                 batch["txt"], batch["vec"], run_cfg)
            v_true = batch["noise"] - batch["latent"]
            return jnp.mean(jnp.square(v.astype(jnp.float32)
                                       - v_true.astype(jnp.float32)))

        bs = {k: _diff_input_sharding(mesh, v, b) for k, v in specs.items()}
        return _train_cell(
            arch_id, sh, mesh,
            init_fn=lambda: MM.init_mmdit(jax.random.PRNGKey(0), run_cfg),
            loss_fn=loss, batch_specs=specs, batch_shardings=bs,
            model_flops=3.0 * graph_flops)

    a_params = _abstract(lambda: MM.init_mmdit(jax.random.PRNGKey(0),
                                               run_cfg))
    p_sh = SH.param_shardings(a_params, mesh)
    dt = 1.0 / max(sh.steps, 1)

    def step(params, latent, t, txt, vec):
        return MM.rf_step(params, latent, t,
                          jnp.full_like(t, dt), txt, vec, run_cfg)

    lat_sh = _diff_input_sharding(mesh, specs["latent"], b)
    return Cell(
        arch_id=arch_id, shape_name=sh.name, kind="denoise", step_fn=step,
        args=(a_params, specs["latent"], specs["t"], specs["txt"],
              specs["vec"]),
        in_shardings=(p_sh, lat_sh, SH.replicated(mesh),
                      _diff_input_sharding(mesh, specs["txt"], b),
                      _diff_input_sharding(mesh, specs["vec"], b)),
        out_shardings=lat_sh, mesh=mesh,
        model_flops=graph_flops)


# ---------------------------------------------------------------------------
# Vision cells
# ---------------------------------------------------------------------------


def _vision_cell(arch_id: str, sh: ShapeSpec, mesh: Mesh, cfg,
                 specs, *, unroll: bool = False) -> Cell:
    b = sh.global_batch
    if isinstance(cfg, VT.ViTConfig):
        uf = cfg.n_layers if unroll else 1
        run_cfg = dataclasses.replace(cfg, img_res=sh.img_res,
                                      scan_unroll=uf)
        init_fn = lambda: VT.init_vit(jax.random.PRNGKey(0), run_cfg)
        fwd = lambda p, img: VT.forward(p, img, run_cfg)
        graph_flops = VT.make_graph(run_cfg, batch=b).total_flops()
    else:
        run_cfg = dataclasses.replace(cfg, img_res=sh.img_res)
        init_fn = lambda: RN.init_resnet(jax.random.PRNGKey(0), run_cfg)
        fwd = lambda p, img: RN.forward(p, img, run_cfg)
        graph_flops = RN.make_graph(run_cfg, batch=b).total_flops()

    if sh.kind == "train":
        def loss(params, batch):
            logits = fwd(params, batch["image"]).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, batch["label"][:, None], axis=-1)[:, 0]
            return jnp.mean(logz - gold)

        bs = {k: SH.data_sharding(mesh, len(v.shape), batch=b)
              for k, v in specs.items()}
        return _train_cell(arch_id, sh, mesh, init_fn=init_fn, loss_fn=loss,
                           batch_specs=specs, batch_shardings=bs,
                           model_flops=3.0 * graph_flops)

    a_params = _abstract(init_fn)
    p_sh = SH.param_shardings(a_params, mesh)

    def step(params, image):
        return fwd(params, image)

    return Cell(
        arch_id=arch_id, shape_name=sh.name, kind="infer", step_fn=step,
        args=(a_params, specs["image"]),
        in_shardings=(p_sh, SH.data_sharding(mesh, 4, batch=b)),
        out_shardings=SH.data_sharding(mesh, 2, batch=b), mesh=mesh,
        model_flops=graph_flops)


# ---------------------------------------------------------------------------


def build_cell(arch_id: str, shape_name: str, mesh: Mesh, *,
               smoke: bool = False, unroll: bool = False,
               cfg_override: Optional[Dict[str, Any]] = None,
               variant: Optional[str] = None) -> Cell:
    """``unroll=True`` fully unrolls layer scans so compiled
    cost_analysis counts every layer (dry-run probe mode); ``False``
    keeps the compile-fast while-loop form (runtime mode).
    ``cfg_override`` replaces config fields (the dry-run's 1/2-layer
    cost-extrapolation probes use it)."""
    spec = get_arch(arch_id)
    cfg = spec.smoke if smoke else spec.full
    if cfg_override:
        cfg = dataclasses.replace(cfg, **cfg_override)
    sh = spec.shapes[shape_name]
    if smoke:           # shrink the shape to the smoke config's scale
        sh = _smoke_shape(spec.family, sh, cfg)
    specs = _input_specs_for(spec.family, cfg, sh)
    if spec.family == "lm":
        return _lm_cell(arch_id, sh, mesh, cfg, specs, unroll=unroll,
                        variant=variant)
    if spec.family == "diffusion":
        if isinstance(cfg, MM.MMDiTConfig):
            return _mmdit_cell(arch_id, sh, mesh, cfg, specs, unroll=unroll)
        return _unet_cell(arch_id, sh, mesh, cfg, specs)
    return _vision_cell(arch_id, sh, mesh, cfg, specs, unroll=unroll)


def _smoke_shape(family: str, sh: ShapeSpec, cfg) -> ShapeSpec:
    if family == "lm":
        return dataclasses.replace(sh, seq_len=min(sh.seq_len, 64),
                                   global_batch=min(sh.global_batch, 2))
    if family == "diffusion":
        return dataclasses.replace(sh, img_res=min(sh.img_res, 64),
                                   global_batch=min(sh.global_batch, 2))
    return dataclasses.replace(sh, img_res=min(sh.img_res, cfg.img_res),
                               global_batch=min(sh.global_batch, 2))


def _input_specs_for(family: str, cfg, sh: ShapeSpec):
    """input_specs() equivalent but honoring a (possibly smoke-shrunk)
    ShapeSpec and config object directly."""
    f32, i32 = jnp.float32, jnp.int32
    if family == "lm":
        b, s = sh.global_batch, sh.seq_len
        if sh.kind == "train":
            return {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                    "labels": jax.ShapeDtypeStruct((b, s), i32)}
        if sh.kind == "prefill":
            return {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        return {"token": jax.ShapeDtypeStruct((b,), i32),
                "cache_index": jax.ShapeDtypeStruct((), i32)}
    if family == "diffusion":
        b, r = sh.global_batch, sh.img_res
        if isinstance(cfg, MM.MMDiTConfig):
            n_img = (r // 16) ** 2
            lat = jax.ShapeDtypeStruct((b, n_img, cfg.in_ch), f32)
            base = {"latent": lat,
                    "txt": jax.ShapeDtypeStruct((b, cfg.txt_len,
                                                 cfg.txt_dim), f32),
                    "vec": jax.ShapeDtypeStruct((b, cfg.vec_dim), f32),
                    "t": jax.ShapeDtypeStruct((b,), f32)}
            if sh.kind == "train":
                base["noise"] = lat
            return base
        latr = r // 8
        lat = jax.ShapeDtypeStruct((b, latr, latr, cfg.in_ch), f32)
        base = {"latent": lat,
                "ctx": jax.ShapeDtypeStruct((b, cfg.ctx_len, cfg.ctx_dim),
                                            f32),
                "t": jax.ShapeDtypeStruct((b,), i32)}
        if sh.kind == "train":
            base["noise"] = lat
        return base
    b, r = sh.global_batch, sh.img_res
    base = {"image": jax.ShapeDtypeStruct((b, r, r, 3), f32)}
    if sh.kind == "train":
        base["label"] = jax.ShapeDtypeStruct((b,), i32)
    return base
