"""Dry-run step builders: one jit-able step per (arch × shape × mesh) cell.

Every builder returns a ``StepBundle``: the step function, abstract
example inputs (ShapeDtypeStructs — *no allocation*), and input shardings
resolved from the logical-axis rules.  ``launch.dryrun`` lowers and
compiles these and records their cost analyses.

The steps are the *real* production steps (optimizer update included for
training; DART routing included for serving) — not stripped-down facsimiles.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.shapes import ShapeSpec
from repro.core import difficulty as DIFF
from repro.core import routing as R
from repro.core.routing import DartParams
from repro.models import get_family, family_of
from repro.models import transformer_lm as TLM
from repro.models import dit as DIT
from repro.optim import adamw
from repro.parallel.sharding import (abstract_init, unzip, tree_shardings,
                                     resolve_spec, LM_RULES, with_fsdp,
                                     Downgrade)

# big-LM training wants FSDP param/optimizer sharding by default
FSDP_TRAIN = {"internlm2-20b", "deepseek-v3-671b"}
# archs whose train/prefill paths use segment-scan (compile-size control;
# the dry-run extrapolates exact per-layer costs from a probe compile)
SCAN_ARCHS = {"deepseek-v3-671b", "internlm2-20b"}


@dataclasses.dataclass
class StepBundle:
    name: str
    step: Callable
    inputs: tuple            # ShapeDtypeStructs
    in_shardings: tuple
    model_flops: int         # analytic (MODEL_FLOPS for §Roofline)
    downgrades: list
    meta: dict


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _replicated(mesh):
    return NamedSharding(mesh, P())


def _batch_sharding(mesh, shape, rules=LM_RULES):
    spec = resolve_spec(shape, ("batch",) + (None,) * (len(shape) - 1),
                        rules, mesh)
    return NamedSharding(mesh, spec)


def _abstract_params(cfg, rules, mesh, downgrades):
    tree = abstract_init(get_family(cfg).init, jax.random.key(0), cfg)
    values, axes = unzip(tree)
    shardings = tree_shardings(axes, values, rules, mesh, downgrades)
    return values, axes, shardings


def _opt_shardings(opt_state_abs, param_shardings, mesh):
    """Optimizer state mirrors params; step counter replicated."""
    from repro.optim.optimizers import OptimizerState
    return OptimizerState(
        step=_replicated(mesh),
        inner={k: param_shardings for k in opt_state_abs.inner})


def _cache_axes(cfg: TLM.LMConfig):
    if cfg.attn_kind == "mla":
        one = {"c_kv": ("batch", "seq_shard", "latent"),
               "k_rope": ("batch", "seq_shard", "latent")}
    else:
        one = {"k": ("batch", "seq_shard", "kv_heads", "head_dim"),
               "v": ("batch", "seq_shard", "kv_heads", "head_dim")}
    return [dict(one) for _ in range(cfg.n_layers)]


def _cache_shardings(cache_abs, cfg, mesh, downgrades):
    axes = _cache_axes(cfg)
    return tree_shardings(axes, cache_abs, LM_RULES, mesh, downgrades)


# ---------------------------------------------------------------------------
# LM steps
# ---------------------------------------------------------------------------

def _lm_probe_bundle(arch, cfg: TLM.LMConfig, sp: ShapeSpec, mesh,
                     kind: str):
    """Single-MoE-layer probe (fwd+bwd for train, fwd for prefill) used to
    extrapolate exact per-layer FLOPs/collectives for scanned segments."""
    dg: list = []
    layer_tree = abstract_init(TLM._layer_init, jax.random.key(0), cfg,
                               cfg.n_dense_layers)
    lvals, laxes = unzip(layer_tree)
    rules = with_fsdp(LM_RULES) if arch in FSDP_TRAIN and kind == "train" \
        else LM_RULES
    lshard = tree_shardings(laxes, lvals, rules, mesh, dg)
    x = _sds((sp.batch, sp.seq_len, cfg.d_model), cfg.compute_dtype)
    xshard = _batch_sharding(mesh, x.shape)
    cos, sin = TLM.L.rope_freqs(
        cfg.qk_rope_dim if cfg.attn_kind == "mla" else cfg.hd,
        sp.seq_len, cfg.rope_theta)

    if kind == "train":
        def probe(lp, x):
            def loss(lp):
                y, aux = TLM._layer_apply(lp, x, cfg, cfg.n_dense_layers,
                                          cos, sin, mesh)
                return jnp.sum(y.astype(jnp.float32)) + aux
            return jax.grad(loss)(lp)
    else:
        def probe(lp, x):
            y, aux = TLM._layer_apply(lp, x, cfg, cfg.n_dense_layers, cos,
                                      sin, mesh)
            return jnp.sum(y.astype(jnp.float32)) + aux

    return StepBundle(f"{arch}:{sp.name}:probe", probe, (lvals, x),
                      (lshard, xshard), 0, dg, {"kind": f"probe-{kind}"})


def _lm_train(arch, cfg: TLM.LMConfig, sp: ShapeSpec, mesh, downgrades,
              fsdp_dp: bool = False):
    scan = arch in SCAN_ARCHS
    cfg = dataclasses.replace(cfg, max_seq=sp.seq_len,
                              attn_chunked=sp.seq_len > 4096,
                              layer_scan=scan)
    if fsdp_dp:
        from repro.parallel.sharding import FSDP_DP_RULES
        rules = FSDP_DP_RULES
        cfg = dataclasses.replace(cfg, act_shard="none")
    else:
        rules = with_fsdp(LM_RULES) if arch in FSDP_TRAIN else LM_RULES
    params, axes, pshard = _abstract_params(cfg, rules, mesh, downgrades)
    opt = adamw(1e-4, moment_dtype=jnp.bfloat16
                if arch in FSDP_TRAIN else jnp.float32)
    opt_state = jax.eval_shape(opt.init, params)
    oshard = _opt_shardings(opt_state, pshard, mesh)
    toks = _sds((sp.batch, sp.seq_len), jnp.int32)
    labs = _sds((sp.batch, sp.seq_len), jnp.int32)
    bshard = _batch_sharding(mesh, toks.shape, rules)

    def step(params, opt_state, tokens, labels):
        def loss_fn(p):
            return TLM.lm_multi_exit_loss(p, tokens, labels, cfg, mesh=mesh)
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    meta = {"kind": "train", "fsdp": arch in FSDP_TRAIN}
    if scan:
        meta.update(
            scan_layers_total=cfg.n_layers - cfg.n_dense_layers,
            scan_body_instances=len(TLM.scan_segments(cfg)),
            probe=lambda: _lm_probe_bundle(arch, cfg, sp, mesh, "train"))
    flops = TLM.lm_train_flops(cfg, sp.batch, sp.seq_len)
    return StepBundle(f"{arch}:{sp.name}", step,
                      (params, opt_state, toks, labs),
                      (pshard, oshard, bshard, bshard), flops, downgrades,
                      meta)


def _lm_prefill(arch, cfg: TLM.LMConfig, sp: ShapeSpec, mesh, downgrades):
    scan = arch in SCAN_ARCHS
    cfg = dataclasses.replace(cfg, max_seq=sp.seq_len, attn_chunked=True,
                              remat=False, layer_scan=scan)
    params, axes, pshard = _abstract_params(cfg, LM_RULES, mesh, downgrades)
    toks = _sds((sp.batch, sp.seq_len), jnp.int32)
    bshard = _batch_sharding(mesh, toks.shape)
    dart = DartParams.default(cfg.n_exits)

    def gate(params, tokens, exit_h):
        emb = jnp.take(params["embed"]["table"], tokens[:, -64:], axis=0)
        alpha = DIFF.token_difficulty(emb)
        names = [str(i) for i in cfg.exit_layers] + ["final"]
        logits = jnp.stack([TLM.exit_logits(params, cfg, h, n)
                            for n, h in zip(names, exit_h)])   # (E, B, V)
        conf = R.confidence_from_logits(logits)
        routed = R.route(conf, alpha, dart)
        preds = jnp.argmax(logits, axis=-1)                    # (E, B)
        tok = jnp.take_along_axis(preds, routed["exit_idx"][None], 0)[0]
        return tok, routed["exit_idx"], alpha

    if scan:
        def step(params, tokens):
            dense_c, seg_c, exit_h = TLM.lm_prefill_scan(params, tokens,
                                                         cfg, mesh=mesh)
            tok, idx, alpha = gate(params, tokens, exit_h)
            return tok, idx, alpha, dense_c, seg_c
    else:
        def step(params, tokens):
            cache = TLM.lm_init_cache(cfg, sp.batch, sp.seq_len)
            new_cache, exit_h = TLM.lm_prefill(params, tokens, cfg, cache,
                                               mesh=mesh)
            tok, idx, alpha = gate(params, tokens, exit_h)
            return tok, idx, alpha, new_cache

    meta = {"kind": "prefill"}
    if scan:
        meta.update(
            scan_layers_total=cfg.n_layers - cfg.n_dense_layers,
            scan_body_instances=len(TLM.scan_segments(cfg)),
            probe=lambda: _lm_probe_bundle(arch, cfg, sp, mesh, "prefill"))
    flops = TLM.lm_forward_flops(cfg, sp.batch, sp.seq_len)
    return StepBundle(f"{arch}:{sp.name}", step, (params, toks),
                      (pshard, bshard), flops, downgrades, meta)


def _lm_decode(arch, cfg: TLM.LMConfig, sp: ShapeSpec, mesh, downgrades):
    cfg = dataclasses.replace(cfg, max_seq=sp.seq_len, remat=False)
    params, axes, pshard = _abstract_params(cfg, LM_RULES, mesh, downgrades)
    cache_abs = TLM.abstract_cache(cfg, sp.batch, sp.seq_len)
    cshard = _cache_shardings(cache_abs, cfg, mesh, downgrades)
    toks = _sds((sp.batch, 1), jnp.int32)
    alpha = _sds((sp.batch,), jnp.float32)
    idx = _sds((), jnp.int32)
    bshard = _batch_sharding(mesh, toks.shape)
    ashard = _batch_sharding(mesh, (sp.batch,))
    dart = DartParams.default(cfg.n_exits)

    def step(params, tokens, cache, cache_index, alpha_state):
        exit_h, new_cache = TLM.lm_decode_step(params, tokens, cache,
                                               cache_index, cfg, mesh=mesh)
        names = [str(i) for i in cfg.exit_layers] + ["final"]
        logits = jnp.stack([TLM.exit_logits(params, cfg, h, n)
                            for n, h in zip(names, exit_h)])   # (E, B, V)
        conf = R.confidence_from_logits(logits)
        emb = jnp.take(params["embed"]["table"], tokens, axis=0)
        alpha_state = DIFF.token_difficulty_ema(alpha_state, emb)
        routed = R.route(conf, alpha_state, dart)
        preds = jnp.argmax(logits, axis=-1)
        tok = jnp.take_along_axis(preds, routed["exit_idx"][None], 0)[0]
        return tok, routed["exit_idx"], alpha_state, new_cache

    flops = TLM.lm_forward_flops(cfg, sp.batch, 1, kv_len=sp.seq_len)
    return StepBundle(f"{arch}:{sp.name}", step,
                      (params, toks, cache_abs, idx, alpha),
                      (pshard, bshard, cshard, _replicated(mesh), ashard),
                      flops, downgrades, {"kind": "decode"})


# ---------------------------------------------------------------------------
# Vision steps
# ---------------------------------------------------------------------------

def _vision_cfg_at_res(cfg, res):
    return dataclasses.replace(cfg, img_res=res)


def _vision_train(arch, cfg, sp: ShapeSpec, mesh, downgrades):
    cfg = _vision_cfg_at_res(cfg, sp.img_res)
    fam = get_family(cfg)
    params, axes, pshard = _abstract_params(cfg, LM_RULES, mesh, downgrades)
    opt = adamw(1e-4)
    opt_state = jax.eval_shape(opt.init, params)
    oshard = _opt_shardings(opt_state, pshard, mesh)
    imgs = _sds((sp.batch, sp.img_res, sp.img_res, 3), cfg.compute_dtype)
    labs = _sds((sp.batch,), jnp.int32)
    ishard = _batch_sharding(mesh, imgs.shape)
    lshard = _batch_sharding(mesh, labs.shape)

    def step(params, opt_state, images, labels):
        def loss_fn(p):
            out = fam.forward(p, images, cfg, mesh=mesh, train=True)
            loss, aux = R.multi_exit_xent(out["exit_logits"], labels)
            return loss, out.get("bn_updates", {})
        (loss, bn), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    flops = (fam.forward_flops(cfg, sp.batch) * 3
             if fam.forward_flops else 0)
    return StepBundle(f"{arch}:{sp.name}", step,
                      (params, opt_state, imgs, labs),
                      (pshard, oshard, ishard, lshard), flops, downgrades,
                      {"kind": "train"})


def _vision_serve(arch, cfg, sp: ShapeSpec, mesh, downgrades):
    cfg = _vision_cfg_at_res(cfg, sp.img_res)
    fam = get_family(cfg)
    params, axes, pshard = _abstract_params(cfg, LM_RULES, mesh, downgrades)
    imgs = _sds((sp.batch, sp.img_res, sp.img_res, 3), cfg.compute_dtype)
    ishard = _batch_sharding(mesh, imgs.shape)
    dart = DartParams.default(cfg.n_exits)

    def step(params, images):
        out = fam.forward(params, images, cfg, mesh=mesh)
        routed = R.classify_routed(out["exit_logits"], images, dart)
        return routed["pred"], routed["exit_idx"], routed["conf"]

    flops = fam.forward_flops(cfg, sp.batch) if fam.forward_flops else 0
    return StepBundle(f"{arch}:{sp.name}", step, (params, imgs),
                      (pshard, ishard), flops, downgrades, {"kind": "serve"})


# ---------------------------------------------------------------------------
# Diffusion steps
# ---------------------------------------------------------------------------

def _dit_train(arch, cfg: DIT.DiTConfig, sp: ShapeSpec, mesh, downgrades):
    cfg = dataclasses.replace(cfg, img_res=sp.img_res)
    params, axes, pshard = _abstract_params(cfg, LM_RULES, mesh, downgrades)
    opt = adamw(1e-4)
    opt_state = jax.eval_shape(opt.init, params)
    oshard = _opt_shardings(opt_state, pshard, mesh)
    lat = _sds((sp.batch, cfg.latent_res, cfg.latent_res, cfg.in_channels),
               cfg.compute_dtype)
    y = _sds((sp.batch,), jnp.int32)
    seed = _sds((), jnp.int32)

    def step(params, opt_state, x0, labels, seed):
        key = jax.random.key(seed)
        def loss_fn(p):
            return DIT.diffusion_loss(p, cfg, x0, labels, key, mesh=mesh)
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    flops = DIT.dit_forward_flops(cfg, sp.batch) * 3
    return StepBundle(f"{arch}:{sp.name}", step,
                      (params, opt_state, lat, y, seed),
                      (pshard, oshard, _batch_sharding(mesh, lat.shape),
                       _batch_sharding(mesh, y.shape), _replicated(mesh)),
                      flops, downgrades, {"kind": "train"})


def _dit_denoise(arch, cfg: DIT.DiTConfig, sp: ShapeSpec, mesh, downgrades):
    cfg = dataclasses.replace(cfg, img_res=sp.img_res, remat=False)
    params, axes, pshard = _abstract_params(cfg, LM_RULES, mesh, downgrades)
    lat = _sds((sp.batch, cfg.latent_res, cfg.latent_res, cfg.in_channels),
               cfg.compute_dtype)
    t = _sds((sp.batch,), jnp.int32)
    tp = _sds((sp.batch,), jnp.int32)
    y = _sds((sp.batch,), jnp.int32)
    dart = DartParams.default(cfg.n_exits, tau=0.9)
    lshard = _batch_sharding(mesh, lat.shape)
    vshard = _batch_sharding(mesh, t.shape)

    def step(params, xt, t, t_prev, labels):
        abar = DIT.cosine_alpha_bar()
        out = DIT.dit_forward(params, xt, t, labels, cfg, mesh=mesh)
        eps_stack = jnp.stack([e[..., :cfg.in_channels]
                               for e in out["exit_eps"]])
        routed = R.diffusion_routed(eps_stack, xt, jnp.sqrt(abar[t]), dart)
        eps_hat = routed["eps"]
        at = abar[t][:, None, None, None]
        ap = abar[t_prev][:, None, None, None]
        x0_hat = (xt - jnp.sqrt(1 - at) * eps_hat) / jnp.sqrt(at)
        x_next = jnp.sqrt(ap) * x0_hat + jnp.sqrt(1 - ap) * eps_hat
        return x_next, routed["exit_idx"]

    flops = DIT.dit_forward_flops(cfg, sp.batch)
    return StepBundle(f"{arch}:{sp.name}", step, (params, lat, t, tp, y),
                      (pshard, lshard, vshard, vshard, vshard), flops,
                      downgrades, {"kind": "denoise",
                                   "sampler_steps": sp.steps})


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------

def build(arch: str, sp: ShapeSpec, mesh, *, reduced=False,
          variant: str = "baseline") -> StepBundle:
    """variant — §Perf hillclimbing knobs, '+'-combinable:
      baseline   : the paper-faithful default sharding
      sp         : Megatron sequence-parallel residual stream
      a2a        : token-sharded all-to-all EP MoE dispatch (implies sp)
      fsdp-dp    : pure FSDP — model axis becomes extra data parallelism
      trunc<K>   : serve only the first K layers + that exit head (the
                   DART expected-depth component for blended rooflines)
    """
    cfg = registry.get_reduced(arch) if reduced else registry.get(arch)
    fam = family_of(cfg)
    downgrades: list[Downgrade] = []
    parts = set(variant.split("+"))
    if fam == "lm":
        if "sp" in parts or "a2a" in parts:
            cfg = dataclasses.replace(cfg, act_shard="sp")
        if "a2a" in parts:
            cfg = dataclasses.replace(cfg, moe_dispatch="a2a")
        trunc = next((p for p in parts if p.startswith("trunc")), None)
        if trunc is not None:
            k = int(trunc[5:])
            exits = tuple(e for e in cfg.exit_layers if e < k - 1)
            cfg = dataclasses.replace(cfg, n_layers=k, exit_layers=exits)
        fn = {"train": _lm_train, "prefill": _lm_prefill,
              "decode": _lm_decode}[sp.kind]
    elif fam == "dit":
        trunc = next((p for p in parts if p.startswith("trunc")), None)
        if trunc is not None:
            k = int(trunc[5:])
            exits = tuple(e for e in cfg.exit_layers if e < k - 1)
            cfg = dataclasses.replace(cfg, n_layers=k, exit_layers=exits)
        fn = {"train": _dit_train, "denoise": _dit_denoise}[sp.kind]
    else:
        fn = {"train": _vision_train, "serve": _vision_serve}[sp.kind]
    bundle = fn(arch, cfg, sp, mesh, downgrades,
                fsdp_dp="fsdp-dp" in parts) \
        if fam == "lm" and sp.kind == "train" \
        else fn(arch, cfg, sp, mesh, downgrades)
    bundle.meta["variant"] = variant
    return bundle
