"""Top-level model: embedding, scan-over-layers stack, loss, prefill, decode.

Parameters are layer-stacked (every block leaf gets a leading ``n_layers``
dim) and applied with ``lax.scan`` so the HLO stays O(1) in depth — critical
for 62-layer models compiled for 512 SPMD devices. Rematerialization policy
is applied to the scanned block body. A model whose first layers differ
(DeepSeek-V2's leading dense layers) keeps them as a stack of their own,
``dense_blocks``, scanned before ``blocks``; ``LAYER_STACKS`` names the
per-layer subtrees.

The LM head / CE loss is computed *chunked over the sequence* so the full
(B, S, V) logits tensor is never materialized (vocab up to 256k).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..sharding.ctx import constrain
from .blocks import (MOE_COUNTERS, apply_block, aux_zero, decode_block,
                     init_block, init_layer_cache)
from .config import ModelConfig
from .layers import dense, rms_norm, trunc_normal

LOSS_CHUNK = 512
LAYER_STACKS = ("dense_blocks", "blocks")
# metrics of the step that add up over its microbatches (not averaged)
STEP_COUNTERS = tuple(f"moe_{k}" for k in MOE_COUNTERS)


def layer_stacks(cfg: ModelConfig):
    """(subtree name, block configuration, layers) of each stack, in the
    order they are applied."""
    lead = cfg.first_k_dense
    out = [("dense_blocks", cfg.dense_lead(), lead)] if lead else []
    return out + [("blocks", cfg, cfg.n_layers - lead)]


# ------------------------------------------------------------------- params
def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    Vp = padded_vocab(cfg)
    params = {
        "embed": trunc_normal(k_embed, (Vp, cfg.d_model), 1.0, dt),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    for name, lcfg, n in layer_stacks(cfg):
        k = k_blocks if name == "blocks" else jax.random.fold_in(k_blocks, 1)
        params[name] = jax.vmap(lambda k: init_block(lcfg, k))(
            jax.random.split(k, n))
    if not cfg.tie_embeddings:
        params["lm_head"] = trunc_normal(k_head, (cfg.d_model, Vp),
                                         cfg.d_model ** -0.5, dt)
    return params


def padded_vocab(cfg: ModelConfig, multiple: int = 256) -> int:
    """Vocab padded for clean TP sharding (standard MaxText-style trick);
    logits for padding ids are masked to -inf in the loss."""
    return -(-cfg.vocab // multiple) * multiple


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    """Abstract (ShapeDtypeStruct) params — used by the dry-run."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ------------------------------------------------------------------ helpers
def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if cfg.remat == "outputs":
        # save only the post-collective block outputs: backward never
        # re-executes the forward TP psums / SP gathers
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "block_out"))
    return jax.checkpoint(fn)      # "nothing": save only block boundaries


def embed_tokens(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                 prefix_embeds: Optional[jax.Array] = None) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0) \
        .astype(jnp.dtype(cfg.compute_dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if prefix_embeds is not None:
        # modality stub: precomputed patch/frame embeddings occupy the first
        # n_prefix positions (assignment: frontend is a stub).
        P = prefix_embeds.shape[1]
        x = jnp.concatenate(
            [prefix_embeds.astype(x.dtype), x[:, P:]], axis=1)
    return x


def _scan_layers(cfg: ModelConfig, params: Dict, x: jax.Array,
                 positions: jax.Array, collect_cache: bool = False):
    """Every stack of blocks in turn. Returns (hidden, the last stack's
    summed aux, the caches as a dict by stack name)."""
    caches = {}
    for name, lcfg, _ in layer_stacks(cfg):
        def layer(carry, p, lcfg=lcfg):
            h, aux = carry
            h, a, cache = apply_block(lcfg, p, h, positions, collect_cache)
            return (h, jax.tree.map(jnp.add, aux, a)), cache

        (x, aux), caches[name] = jax.lax.scan(
            _remat(lcfg, layer), (x, aux_zero(lcfg)), params[name])
    return x, aux, caches


def backbone(cfg: ModelConfig, params: Dict, x: jax.Array,
             positions: jax.Array) -> Tuple[jax.Array, Any]:
    """Apply all blocks. Returns (hidden, aux summed over the layers: the
    router's loss, or a dict of it and the MoE counters). The leading dense
    layers add no router term."""
    x, aux, _ = _scan_layers(cfg, params, x, positions)
    return rms_norm(x, params["final_norm"], cfg.rms_eps), aux


def lm_head_weight(cfg: ModelConfig, params: Dict) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


# ------------------------------------------------------------------- train
def token_loss(cfg: ModelConfig, params: Dict, hidden: jax.Array,
               labels: jax.Array, mask: jax.Array) -> jax.Array:
    """Chunked cross-entropy: never materializes (B, S, V) logits."""
    B, S, d = hidden.shape
    W = lm_head_weight(cfg, params)
    Vp = W.shape[-1]
    chunk = min(LOSS_CHUNK, S)
    while S % chunk:
        chunk //= 2
    nc = S // chunk
    hc = hidden.reshape(B, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nc, chunk).transpose(1, 0, 2)
    mc = mask.reshape(B, nc, chunk).transpose(1, 0, 2)
    vocab_ok = jnp.arange(Vp) < cfg.vocab        # mask padded vocab ids

    @jax.checkpoint       # logits are recomputed in backward, never stored
    def chunk_loss(carry, inp):
        h, l, m = inp
        logits = dense(h, W).astype(jnp.dtype(cfg.logit_dtype))
        logits = constrain(logits, "logits_chunk")
        logits = jnp.where(vocab_ok[None, None], logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        ce = (lse - picked) * m
        return (carry[0] + ce.sum(), carry[1] + m.sum()), None

    (tot, cnt), _ = jax.lax.scan(chunk_loss, (jnp.float32(0), jnp.float32(0)),
                                 (hc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(cfg: ModelConfig, params: Dict, batch: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """batch: tokens (B,S) int32, labels (B,S) int32, mask (B,S) f32,
    optional prefix_embeds (B,P,d)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = embed_tokens(cfg, params, tokens, batch.get("prefix_embeds"))
    hidden, aux = backbone(cfg, params, x, positions)
    ce = token_loss(cfg, params, hidden, batch["labels"], batch["mask"])
    if isinstance(aux, dict):
        # DeepSeek-V2: the balance loss of every MoE layer, summed
        loss = ce + cfg.router_aux_coef * aux["balance"]
        return loss, {"ce": ce, "aux": aux["balance"],
                      **{f"moe_{k}": aux[k] for k in MOE_COUNTERS}}
    loss = ce + cfg.router_aux_coef * aux / max(cfg.n_layers, 1)
    return loss, {"ce": ce, "aux": aux}


# ------------------------------------------------------------------- serve
def prefill(cfg: ModelConfig, params: Dict, tokens: jax.Array,
            prefix_embeds: Optional[jax.Array] = None
            ) -> Tuple[Dict, jax.Array]:
    """Full-sequence forward that also builds the decode cache.

    Returns (cache: a dict by stack name of pytrees stacked over layers,
    last-position logits (B, V)).
    """
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = embed_tokens(cfg, params, tokens, prefix_embeds)

    x, _, caches = _scan_layers(cfg, params, x, positions,
                                collect_cache=True)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = dense(x[:, -1], lm_head_weight(cfg, params)) \
        .astype(jnp.dtype(cfg.logit_dtype))
    return caches, logits[:, :cfg.vocab] if padded_vocab(cfg) != cfg.vocab \
        else logits


def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    """Zeroed decode cache: a dict by stack name of caches stacked over
    that stack's layers."""
    one = init_layer_cache(cfg, batch, cfg.cache_len(cache_len))
    return {name: jax.tree.map(
        lambda a, n=n: jnp.broadcast_to(a, (n,) + a.shape).copy(), one)
        for name, _, n in layer_stacks(cfg)}


def decode_step(cfg: ModelConfig, params: Dict, cache, tokens: jax.Array,
                pos: jax.Array) -> Tuple[Any, jax.Array]:
    """One decode step. tokens: (B,) int32; pos: scalar int32 (the position
    being generated, whose K/V enter the cache). Returns (cache', logits)."""
    x = jnp.take(params["embed"], tokens, axis=0) \
        .astype(jnp.dtype(cfg.compute_dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    new_cache = {}
    for name, lcfg, _ in layer_stacks(cfg):
        def layer(x_t, inp, lcfg=lcfg):
            p, c = inp
            c, x_t = decode_block(lcfg, p, c, x_t, pos)
            return x_t, c

        x, new_cache[name] = jax.lax.scan(layer, x,
                                          (params[name], cache[name]))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = dense(x, lm_head_weight(cfg, params)) \
        .astype(jnp.dtype(cfg.logit_dtype))
    return new_cache, logits
