"""Step factories: build sharded, jitted train / prefill / decode steps.

The factory resolves the sharding recipe for (arch, shape-kind, mesh),
computes PartitionSpecs for params / optimizer / batch / cache, installs
the activation-rule context at trace time, and returns the jitted function
plus its shardings (the dry-run lowers the same object the trainer runs).

Features:
* microbatch gradient accumulation (lax.scan over microbatches)
* remat policy from ModelConfig
* ZeRO-1 optimizer sharding over the DP axes
* optional int8+error-feedback compressed gradient all-reduce (shard_map
  over DP) for the "dp" recipe
* cache donation on decode (in-place KV update)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import (decode_step as model_decode, init_cache, init_params,
                      loss_fn, prefill as model_prefill)
from ..models.model import STEP_COUNTERS
from ..models.config import ModelConfig
from ..optim import AdamWConfig, apply_update, init_opt_state
from ..sharding.ctx import activation_ctx
from ..sharding.rules import (Recipe, activation_rules, batch_specs,
                              cache_specs, opt_specs, param_specs_tree,
                              recipe_for, zero_axes_for)


@dataclass(frozen=True)
class TrainConfig:
    adamw: AdamWConfig = field(default_factory=AdamWConfig)
    microbatches: int = 0       # 0 = auto: target ~2 samples/device/microbatch
    zero1: bool = True
    grad_compression: Optional[str] = None     # None | "int8_ef"
    grad_reduce_dtype: Optional[str] = None    # e.g. "bfloat16": cast the
                                               # accumulated grads before the
                                               # cross-replica reduction
    recipe: Optional[str] = None               # override recipe name


def _named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


@dataclass
class StepBundle:
    """A compiled-step package: fn + shardings (dry-run lowers fn too)."""
    fn: Any
    in_shardings: Any
    out_shardings: Any
    recipe: Recipe
    abstract_inputs: Any = None


# ------------------------------------------------------------------ train
def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                    global_batch: int, seq_len: int) -> StepBundle:
    recipe = recipe_for(cfg, "train", mesh)
    if tcfg.recipe:
        recipe = Recipe(tcfg.recipe, "train")
    pshape = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    pspec = param_specs_tree(cfg, recipe, mesh, pshape)
    oshape = jax.eval_shape(lambda: init_opt_state(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), pshape)))
    zero_axes = zero_axes_for(recipe, mesh) if tcfg.zero1 else ()
    ospec = {
        "step": P(),
        "master": opt_specs(pspec, pshape, mesh, zero_axes),
        "m": opt_specs(pspec, pshape, mesh, zero_axes),
        "v": opt_specs(pspec, pshape, mesh, zero_axes),
    }
    bspec = batch_specs(cfg, recipe, mesh, global_batch)
    arules = activation_rules(cfg, recipe, mesh, global_batch)
    nmicro = tcfg.microbatches
    if nmicro == 0:
        # auto: per-device microbatch of ~2 samples bounds saved activations
        baxes = bspec["tokens"][0] or ()
        dp_size = 1
        for a in (baxes if isinstance(baxes, tuple) else (baxes,)):
            dp_size *= mesh.shape[a]
        per_dev = max(1, global_batch // dp_size)
        nmicro = max(1, per_dev // 2)
        while global_batch % (nmicro * dp_size) and nmicro > 1:
            nmicro -= 1

    def step(params, opt_state, batch):
        with activation_ctx(arules):
            with jax.named_scope("fwd_bwd"):
                if nmicro == 1:
                    (loss, metrics), grads = jax.value_and_grad(
                        lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
                else:
                    def micro(acc, mb):
                        (l, m), g = jax.value_and_grad(
                            lambda p: loss_fn(cfg, p, mb),
                            has_aux=True)(params)
                        return jax.tree.map(jnp.add, acc, g), (l, m)

                    mbs = jax.tree.map(
                        lambda a: a.reshape((nmicro, a.shape[0] // nmicro)
                                            + a.shape[1:]), batch)
                    zero_g = jax.tree.map(
                        lambda a: jnp.zeros(a.shape, jnp.float32), params)
                    grads, (losses, metrics) = jax.lax.scan(micro, zero_g,
                                                            mbs)
                    grads = jax.tree.map(lambda g: g / nmicro, grads)
                    # the step's loss is the mean over its microbatches,
                    # as its gradient is; counters add up
                    loss = losses.mean()
                    metrics = {k: a.sum(0) if k in STEP_COUNTERS
                               else a.mean(0)
                               for k, a in sorted(metrics.items())}
            if tcfg.grad_reduce_dtype is not None:
                rd = jnp.dtype(tcfg.grad_reduce_dtype)
                grads = jax.tree.map(lambda g: g.astype(rd), grads)
            with jax.named_scope("optimizer"):
                new_params, new_opt, stats = apply_update(
                    tcfg.adamw, params, opt_state, grads)
            out_metrics = {"loss": loss, **metrics, **stats}
        return new_params, new_opt, out_metrics

    in_sh = (_named(mesh, pspec), _named(mesh, ospec),
             {k: NamedSharding(mesh, s) for k, s in bspec.items()})
    out_sh = (_named(mesh, pspec), _named(mesh, ospec), None)
    fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                 donate_argnums=(0, 1))
    return StepBundle(fn=fn, in_shardings=in_sh, out_shardings=out_sh,
                      recipe=recipe,
                      abstract_inputs=(pshape, oshape, None))


# ---------------------------------------------------------------- prefill
def make_prefill_step(cfg: ModelConfig, mesh: Mesh, global_batch: int,
                      seq_len: int, recipe_name: Optional[str] = None
                      ) -> StepBundle:
    recipe = recipe_for(cfg, "prefill", mesh)
    if recipe_name:
        recipe = Recipe(recipe_name, "prefill")
    pshape = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    pspec = param_specs_tree(cfg, recipe, mesh, pshape)
    bspec = batch_specs(cfg, recipe, mesh, global_batch)
    arules = activation_rules(cfg, recipe, mesh, global_batch)
    cshape = jax.eval_shape(
        lambda: init_cache(cfg, global_batch, seq_len))
    cspec = cache_specs(cfg, Recipe("decode", "decode"), mesh,
                        global_batch, cshape)

    def step(params, tokens, prefix_embeds=None):
        with activation_ctx(arules):
            cache, logits = model_prefill(cfg, params, tokens,
                                          prefix_embeds)
        return cache, logits

    in_sh = [_named(mesh, pspec), NamedSharding(mesh, bspec["tokens"])]
    if cfg.n_prefix_embeds:
        in_sh.append(NamedSharding(mesh, bspec["prefix_embeds"]))
    out_sh = (_named(mesh, cspec), None)
    fn = jax.jit(step, in_shardings=tuple(in_sh), out_shardings=out_sh)
    return StepBundle(fn=fn, in_shardings=tuple(in_sh), out_shardings=out_sh,
                      recipe=recipe, abstract_inputs=(pshape,))


# ----------------------------------------------------------------- decode
def make_decode_step(cfg: ModelConfig, mesh: Mesh, global_batch: int,
                     cache_len: int, recipe_name: Optional[str] = None
                     ) -> StepBundle:
    recipe = Recipe(recipe_name or "decode", "decode")
    pshape = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    # decode params follow the prefill/train recipe for weight placement
    wrecipe = recipe_for(cfg, "train", mesh)
    pspec = param_specs_tree(cfg, wrecipe, mesh, pshape)
    cshape = jax.eval_shape(lambda: init_cache(cfg, global_batch, cache_len))
    cspec = cache_specs(cfg, recipe, mesh, global_batch, cshape)
    arules = activation_rules(cfg, recipe, mesh, global_batch)
    baxes = batch_specs(cfg, recipe, mesh, global_batch)

    def step(params, cache, tokens, pos):
        with activation_ctx(arules):
            cache, logits = model_decode(cfg, params, cache, tokens, pos)
        return cache, logits

    in_sh = (_named(mesh, pspec), _named(mesh, cspec),
             NamedSharding(mesh, P(baxes["tokens"][0])),
             NamedSharding(mesh, P()))
    out_sh = (_named(mesh, cspec), None)
    fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                 donate_argnums=(1,))
    return StepBundle(fn=fn, in_shardings=in_sh, out_shardings=out_sh,
                      recipe=recipe, abstract_inputs=(pshape, cshape))
