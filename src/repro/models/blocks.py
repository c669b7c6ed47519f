"""Per-family transformer blocks: init, train/prefill apply, decode step.

Every family exposes:
    init_block(cfg, key)                      -> params pytree (one layer)
    apply_block(cfg, p, x, positions)         -> (x', aux, cache_entry|None)
    decode_block(cfg, p, cache, x_t, pos)     -> (cache', x_t')
    init_layer_cache(cfg, batch, cache_len)   -> per-layer cache pytree

Weights are head-structured (d, H, Dh) / (H, Dh, d) — TP sharding lives on
an explicit head (or head-dim) axis, never on a flattened dim the SPMD
partitioner would have to re-factor. ``constrain(x, name)`` pins named
activations to the recipe's PartitionSpec (no-op outside a launcher).

``apply_block`` serves both train (cache ignored) and prefill (cache
collected). Caches hold ungrouped K/V (KVH heads); SWA archs use a ring
buffer of ``window`` slots.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..sharding.ctx import constrain
from .attention import attention, attention_decode
from .config import ModelConfig
from .layers import (apply_rope, dense, dense_init, proj_heads, rms_norm,
                     trunc_normal, unproj_heads)
from .moe import moe_ffn
from .ssm import (causal_conv, causal_conv_step, ssd_chunked,
                  ssd_decode_step)


# =========================================================== shared helpers
def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    return k if n_rep == 1 else jnp.repeat(k, n_rep, axis=2)


def _head_init(key, d, H, Dh, dtype):
    return trunc_normal(key, (d, H, Dh), d ** -0.5, dtype)


def _qkv(cfg: ModelConfig, p: Dict, x: jax.Array, positions: jax.Array):
    q = proj_heads(x, p["wq"])
    k = proj_heads(x, p["wk"])
    v = proj_heads(x, p["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return constrain(q, "act_q"), constrain(k, "act_kv"), \
        constrain(v, "act_kv")


def _self_attention(cfg: ModelConfig, p: Dict, h: jax.Array,
                    positions: jax.Array):
    """-> (attn output (B,S,d), k, v)."""
    q, k, v = _qkv(cfg, p, h, positions)
    rep = cfg.n_heads // cfg.n_kv_heads
    kr = constrain(_repeat_kv(k, rep), "act_kv_rep")
    vr = constrain(_repeat_kv(v, rep), "act_kv_rep")
    o = attention(q, kr, vr, causal=True, window=cfg.window,
                  impl=cfg.attn_impl, kv_block=cfg.kv_block,
                  q_block=cfg.q_block, score_dtype=cfg.score_dtype)
    o = constrain(o, "act_q")
    return unproj_heads(o, p["wo"]), k, v


def _attn_init(cfg: ModelConfig, key) -> Dict:
    ks = jax.random.split(key, 4)
    d, dt = cfg.d_model, cfg.param_dtype
    H, KVH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": _head_init(ks[0], d, H, Dh, dt),
        "wk": _head_init(ks[1], d, KVH, Dh, dt),
        "wv": _head_init(ks[2], d, KVH, Dh, dt),
        "wo": trunc_normal(ks[3], (H, Dh, d), (H * Dh) ** -0.5, dt),
    }


def _mlp_init(cfg: ModelConfig, key) -> Dict:
    ks = jax.random.split(key, 3)
    d, dt = cfg.d_model, cfg.param_dtype
    return {
        "w_gate": dense_init(ks[0], d, cfg.d_ff, dt),
        "w_up": dense_init(ks[1], d, cfg.d_ff, dt),
        "w_down": dense_init(ks[2], cfg.d_ff, d, dt),
    }


def _mlp(cfg: ModelConfig, p: Dict, h: jax.Array) -> jax.Array:
    g = constrain(dense(h, p["w_gate"]), "act_ffh")
    u = constrain(dense(h, p["w_up"]), "act_ffh")
    if cfg.act == "swiglu":
        hh = jax.nn.silu(g) * u
    else:
        hh = jax.nn.gelu(g, approximate=True) * u
    return dense(hh, p["w_down"])


def _ring_tail(k: jax.Array, C: int) -> jax.Array:
    """Last C positions of k (B,S,...) laid out ring-style (slot = pos % C)
    so decode's ``pos % C`` insertion continues consistently."""
    S = k.shape[1]
    if S < C:
        pad = [(0, 0), (C - S, 0)] + [(0, 0)] * (k.ndim - 2)
        return jnp.pad(k, pad)
    tail = k[:, -C:]
    shift = S % C
    return jnp.roll(tail, shift, axis=1) if shift else tail


def _kv_cache_init(cfg: ModelConfig, batch: int, cache_len: int) -> Dict:
    shape = (batch, cache_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, jnp.dtype(cfg.compute_dtype)),
            "v": jnp.zeros(shape, jnp.dtype(cfg.compute_dtype))}


def _cache_positions(cache_len: int, pos: jax.Array) -> jax.Array:
    """Absolute position held in each ring slot; invalid slots get INT_MAX."""
    s = jnp.arange(cache_len)
    cand = pos - jnp.mod(pos - s, cache_len)
    return jnp.where(cand >= 0, cand, jnp.iinfo(jnp.int32).max)


def _kv_cache_insert(cache: Dict, k_t: jax.Array, v_t: jax.Array,
                     pos: jax.Array) -> Dict:
    C = cache["k"].shape[1]
    slot = jnp.mod(pos, C)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_t, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_t, slot, axis=1)
    return {"k": constrain(k, "cache_kv"), "v": constrain(v, "cache_kv")}


def _attn_decode(cfg: ModelConfig, p: Dict, cache: Dict, x_t: jax.Array,
                 pos: jax.Array) -> Tuple[Dict, jax.Array]:
    B = x_t.shape[0]
    x1 = x_t[:, None]                                       # (B, 1, d)
    q = proj_heads(x1, p["wq"])
    k = proj_heads(x1, p["wk"])
    v = proj_heads(x1, p["wv"])
    pos_b = jnp.broadcast_to(pos, (B, 1))
    q = apply_rope(q, pos_b, cfg.rope_theta)
    k = apply_rope(k, pos_b, cfg.rope_theta)
    cache = _kv_cache_insert(cache, k, v, pos)
    cpos = _cache_positions(cache["k"].shape[1], pos)
    o = attention_decode(q, cache["k"], cache["v"], cpos, pos,
                         window=cfg.window)
    y = unproj_heads(o, p["wo"])[:, 0]
    return cache, y


# ================================================================== dense
def init_dense_block(cfg: ModelConfig, key) -> Dict:
    ks = jax.random.split(key, 2)
    d = cfg.d_model
    return {
        "attn_norm": jnp.ones((d,), jnp.float32),
        "mlp_norm": jnp.ones((d,), jnp.float32),
        **_attn_init(cfg, ks[0]),
        **_mlp_init(cfg, ks[1]),
    }


def apply_dense_block(cfg: ModelConfig, p: Dict, x: jax.Array,
                      positions: jax.Array, collect_cache: bool = False):
    from jax.ad_checkpoint import checkpoint_name
    # "act_block_in": under tp_sp this is THE Megatron-SP gather point —
    # one all-gather per block half, shared by every projection after it.
    h = constrain(rms_norm(x, p["attn_norm"], cfg.rms_eps), "act_block_in")
    a, k, v = _self_attention(cfg, p, h, positions)
    a = checkpoint_name(a, "block_out")     # post-psum: remat="outputs"
    x = constrain(x + a, "act_hidden")      # saves these, skips recompute
    h = constrain(rms_norm(x, p["mlp_norm"], cfg.rms_eps), "act_block_in")
    m = checkpoint_name(_mlp(cfg, p, h), "block_out")
    x = constrain(x + m, "act_hidden")
    cache = None
    if collect_cache:
        C = cfg.cache_len(x.shape[1])
        cache = {"k": _ring_tail(k, C), "v": _ring_tail(v, C)}
    return x, jnp.float32(0.0), cache


def decode_dense_block(cfg: ModelConfig, p: Dict, cache: Dict,
                       x_t: jax.Array, pos: jax.Array):
    h = rms_norm(x_t, p["attn_norm"], cfg.rms_eps)
    cache, a = _attn_decode(cfg, p, cache, h, pos)
    x_t = x_t + a
    h = rms_norm(x_t, p["mlp_norm"], cfg.rms_eps)
    x_t = x_t + _mlp(cfg, p, h)
    return cache, x_t


# ==================================================================== moe
def init_moe_block(cfg: ModelConfig, key) -> Dict:
    ks = jax.random.split(key, 5)
    d, dt = cfg.d_model, cfg.param_dtype
    E, fe = cfg.n_experts, cfg.d_ff_expert
    return {
        "attn_norm": jnp.ones((d,), jnp.float32),
        "mlp_norm": jnp.ones((d,), jnp.float32),
        **_attn_init(cfg, ks[0]),
        "router": dense_init(ks[1], d, E, jnp.float32),
        "w_gate": (jax.random.truncated_normal(ks[2], -2, 2, (E, d, fe))
                   * d ** -0.5).astype(dt),
        "w_up": (jax.random.truncated_normal(ks[3], -2, 2, (E, d, fe))
                 * d ** -0.5).astype(dt),
        "w_down": (jax.random.truncated_normal(ks[4], -2, 2, (E, fe, d))
                   * fe ** -0.5).astype(dt),
    }


def _moe(cfg: ModelConfig, p: Dict, h2d: jax.Array):
    return moe_ffn(h2d, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                   top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                   act=cfg.act)


def _moe_local(cfg: ModelConfig, p: Dict, h: jax.Array, spec):
    """Fully-local MoE: shard_map over the token axes with REPLICATED
    expert weights — each shard routes its own tokens into its own
    capacity buffer; zero collectives inside the MoE (the scatter/sort/
    psum pathologies of the SPMD-auto path disappear). Used when the
    rule table provides "moe_local" (small-expert archs under sp)."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    axes = tuple(a for e in tuple(spec) if e is not None
                 for a in (e if isinstance(e, tuple) else (e,)))

    def body(hb, router, wg, wu, wd):
        B, S, d = hb.shape
        y, aux = moe_ffn(hb.reshape(B * S, d), router, wg, wu, wd,
                         top_k=cfg.top_k,
                         capacity_factor=cfg.capacity_factor, act=cfg.act)
        aux = jax.lax.pmean(aux, axes)
        return y.reshape(B, S, d), aux

    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, P(), P(), P(), P()),
                       out_specs=(spec, P()), check_vma=False)
    return fn(h, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def apply_moe_block(cfg: ModelConfig, p: Dict, x: jax.Array,
                    positions: jax.Array, collect_cache: bool = False):
    B, S, d = x.shape
    h = rms_norm(x, p["attn_norm"], cfg.rms_eps)
    a, k, v = _self_attention(cfg, p, h, positions)
    x = constrain(x + a, "act_hidden")
    h = rms_norm(x, p["mlp_norm"], cfg.rms_eps)
    from ..sharding.ctx import _RULES
    rules = _RULES.get() or {}
    if rules.get("moe_local") is not None:
        # fully-local dispatch (see _moe_local)
        y3, aux = _moe_local(cfg, p, h, rules["moe_local"])
        x = constrain(x + y3, "act_hidden")
        return x, aux, ({"k": _ring_tail(k, cfg.cache_len(S)),
                         "v": _ring_tail(v, cfg.cache_len(S))}
                        if collect_cache else None)
    # Otherwise: pin the MoE input layout (all-gather in, reduce-scatter
    # out — the Megatron-SP MoE pattern) so flattening (B,S) never mixes
    # sharded dims inside the sort-based dispatch.
    h = constrain(h, "act_moe_in")
    y, aux = _moe(cfg, p, h.reshape(B * S, d))
    x = constrain(x + constrain(y.reshape(B, S, d), "act_moe_out"),
                  "act_hidden")
    cache = None
    if collect_cache:
        C = cfg.cache_len(S)
        cache = {"k": _ring_tail(k, C), "v": _ring_tail(v, C)}
    return x, aux, cache


def decode_moe_block(cfg: ModelConfig, p: Dict, cache: Dict,
                     x_t: jax.Array, pos: jax.Array):
    h = rms_norm(x_t, p["attn_norm"], cfg.rms_eps)
    cache, a = _attn_decode(cfg, p, cache, h, pos)
    x_t = x_t + a
    h = rms_norm(x_t, p["mlp_norm"], cfg.rms_eps)
    y, _ = _moe(cfg, p, h)
    return cache, x_t + y


# ==================================================================== mla
def init_mla_block(cfg: ModelConfig, key) -> Dict:
    ks = jax.random.split(key, 6)
    d, dt, H = cfg.d_model, cfg.param_dtype, cfg.n_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "attn_norm": jnp.ones((d,), jnp.float32),
        "mlp_norm": jnp.ones((d,), jnp.float32),
        "wq_a": dense_init(ks[0], d, qr, dt),
        "q_norm": jnp.ones((qr,), jnp.float32),
        "wq_b": _head_init(ks[1], qr, H, nope + rope, dt),
        "wkv_a": dense_init(ks[2], d, kr + rope, dt),
        "kv_norm": jnp.ones((kr,), jnp.float32),
        "wkv_b": _head_init(ks[3], kr, H, nope + vh, dt),
        "wo": trunc_normal(ks[4], (H, vh, d), (H * vh) ** -0.5, dt),
        **_mlp_init(cfg, ks[5]),
    }


def _mla_qkv(cfg: ModelConfig, p: Dict, h: jax.Array, positions: jax.Array):
    """-> q (B,S,H,nope+rope), c_kv (B,S,kr) normed, k_rope (B,S,rope)."""
    nope = cfg.qk_nope_dim
    qa = rms_norm(dense(h, p["wq_a"]), p["q_norm"], cfg.rms_eps)
    q = proj_heads(qa, p["wq_b"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    q = constrain(jnp.concatenate([q_nope, q_rope], axis=-1), "act_q")
    kv_a = dense(h, p["wkv_a"])
    c_kv = rms_norm(kv_a[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.rms_eps)
    k_rope = apply_rope(kv_a[..., cfg.kv_lora_rank:], positions,
                        cfg.rope_theta)
    return q, c_kv, k_rope


def apply_mla_block(cfg: ModelConfig, p: Dict, x: jax.Array,
                    positions: jax.Array, collect_cache: bool = False):
    B, S, d = x.shape
    H = cfg.n_heads
    nope, rope, vh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    h = rms_norm(x, p["attn_norm"], cfg.rms_eps)
    q, c_kv, k_rope = _mla_qkv(cfg, p, h, positions)
    # expand keys/values from the latent (training path)
    kv = proj_heads(c_kv, p["wkv_b"])                       # (B,S,H,nope+vh)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], (B, S, H, rope))],
        axis=-1)
    k = constrain(k, "act_q")
    o = attention(q, k, constrain(v, "act_q"), causal=True,
                  window=cfg.window, impl=cfg.attn_impl,
                  kv_block=cfg.kv_block, q_block=cfg.q_block,
                  scale=(nope + rope) ** -0.5,
                  score_dtype=cfg.score_dtype)
    x = constrain(x + unproj_heads(constrain(o, "act_q"), p["wo"]),
                  "act_hidden")
    h = rms_norm(x, p["mlp_norm"], cfg.rms_eps)
    x = constrain(x + _mlp(cfg, p, h), "act_hidden")
    cache = None
    if collect_cache:
        cache = {"c_kv": c_kv, "k_rope": k_rope}
    return x, jnp.float32(0.0), cache


def mla_cache_init(cfg: ModelConfig, batch: int, cache_len: int) -> Dict:
    dt = jnp.dtype(cfg.compute_dtype)
    return {"c_kv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dt),
            "k_rope": jnp.zeros((batch, cache_len, cfg.qk_rope_dim), dt)}


def decode_mla_block(cfg: ModelConfig, p: Dict, cache: Dict,
                     x_t: jax.Array, pos: jax.Array):
    """Absorbed MLA decode: attention runs in latent space; the cache is the
    (kv_lora_rank + rope) latent — MLA's memory advantage."""
    B, d = x_t.shape
    nope, rope, vh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    h = rms_norm(x_t, p["attn_norm"], cfg.rms_eps)[:, None]     # (B,1,d)
    pos_b = jnp.broadcast_to(pos, (B, 1))
    q, c_kv, k_rope = _mla_qkv(cfg, p, h, pos_b)
    q_nope, q_rope = q[..., :nope], q[..., nope:]               # (B,1,H,·)
    C = cache["c_kv"].shape[1]
    slot = jnp.mod(pos, C)
    c_cache = constrain(jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv, slot, axis=1), "cache_latent")
    r_cache = constrain(jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope, slot, axis=1), "cache_latent")
    # absorb W_UK into q:   q_abs = q_nope @ W_UK^T  -> latent space
    w_uk = p["wkv_b"][..., :nope]                               # (kr,H,nope)
    w_uv = p["wkv_b"][..., nope:]                               # (kr,H,vh)
    q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))                # (B,1,H,kr)
    s = jnp.einsum("bqhr,bcr->bhqc", q_abs,
                   c_cache.astype(jnp.float32)) + \
        jnp.einsum("bqhr,bcr->bhqc", q_rope.astype(jnp.float32),
                   r_cache.astype(jnp.float32))
    s = s * (nope + rope) ** -0.5
    cpos = _cache_positions(C, pos)
    s = jnp.where(cpos[None, None, None] <= pos, s, -1e30)
    pw = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqc,bcr->bqhr", pw, c_cache.astype(jnp.float32))
    o = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv.astype(jnp.float32))
    y = unproj_heads(o.astype(x_t.dtype), p["wo"])[:, 0]
    x_t = x_t + y
    h2 = rms_norm(x_t, p["mlp_norm"], cfg.rms_eps)
    x_t = x_t + _mlp(cfg, p, h2)
    return {"c_kv": c_cache, "k_rope": r_cache}, x_t


# ==================================================================== ssm
def _ssm_dims(cfg: ModelConfig):
    di, N, G, Hs = cfg.d_inner, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_heads
    return di, N, G, Hs, di // Hs


def init_ssm_core(cfg: ModelConfig, key) -> Dict:
    di, N, G, Hs, P = _ssm_dims(cfg)
    d, dt, K = cfg.d_model, cfg.param_dtype, cfg.conv_kernel
    ks = jax.random.split(key, 11)
    u = jax.random.uniform(ks[0], (Hs,), jnp.float32, 1e-3, 1e-1)
    dt_bias = u + jnp.log(-jnp.expm1(-u))       # inverse softplus
    return {
        "w_z": trunc_normal(ks[1], (d, Hs, P), d ** -0.5, dt),
        "w_x": trunc_normal(ks[2], (d, Hs, P), d ** -0.5, dt),
        "w_B": trunc_normal(ks[3], (d, G, N), d ** -0.5, dt),
        "w_C": trunc_normal(ks[4], (d, G, N), d ** -0.5, dt),
        "w_dt": trunc_normal(ks[5], (d, Hs), d ** -0.5, dt),
        "conv_x_w": (jax.random.normal(ks[6], (Hs, P, K)) / K).astype(dt),
        "conv_x_b": jnp.zeros((Hs, P), jnp.float32),
        "conv_B_w": (jax.random.normal(ks[7], (G, N, K)) / K).astype(dt),
        "conv_B_b": jnp.zeros((G, N), jnp.float32),
        "conv_C_w": (jax.random.normal(ks[8], (G, N, K)) / K).astype(dt),
        "conv_C_b": jnp.zeros((G, N), jnp.float32),
        "A_log": jnp.log(jax.random.uniform(ks[9], (Hs,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((Hs,), jnp.float32),
        "dt_bias": dt_bias,
        "gate_norm": jnp.ones((Hs, P), jnp.float32),
        "out_proj": trunc_normal(ks[10], (Hs, P, d), di ** -0.5, dt),
    }


def _gated_rms(y: jax.Array, z: jax.Array, scale: jax.Array,
               eps: float) -> jax.Array:
    """RMSNorm(y * silu(z)) jointly over the (H, P) channel block."""
    g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    var = jnp.mean(g * g, axis=(-2, -1), keepdims=True)
    return (g * jax.lax.rsqrt(var + eps) *
            scale.astype(jnp.float32)).astype(y.dtype)


def apply_ssm_core(cfg: ModelConfig, p: Dict, h: jax.Array,
                   collect_cache: bool = False):
    """h: (B, S, d) normed input -> (y (B,S,d), cache|None)."""
    B, S, _ = h.shape
    di, N, G, Hs, P = _ssm_dims(cfg)
    z = constrain(proj_heads(h, p["w_z"]), "act_ssm")       # (B,S,H,P)
    x_pre = constrain(proj_heads(h, p["w_x"]), "act_ssm")
    B_pre = proj_heads(h, p["w_B"])                          # (B,S,G,N)
    C_pre = proj_heads(h, p["w_C"])
    dt = dense(h, p["w_dt"])                                 # (B,S,H)
    xs = jax.nn.silu(causal_conv(x_pre, p["conv_x_w"], p["conv_x_b"]))
    Bc = jax.nn.silu(causal_conv(B_pre, p["conv_B_w"], p["conv_B_b"]))
    Cc = jax.nn.silu(causal_conv(C_pre, p["conv_C_w"], p["conv_C_b"]))
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, h_final = ssd_chunked(xs, dtf, A, Bc, Cc, p["D"], chunk=cfg.ssm_chunk)
    y = _gated_rms(y, z, p["gate_norm"], cfg.rms_eps)
    out = unproj_heads(y, p["out_proj"])
    cache = None
    if collect_cache:
        K = cfg.conv_kernel
        cdt = jnp.dtype(cfg.compute_dtype)

        def tail(t):     # chronological last K-1 inputs (left-pad if short)
            if t.shape[1] >= K - 1:
                return t[:, -(K - 1):].astype(cdt)
            pad = [(0, 0), (K - 1 - t.shape[1], 0)] + \
                [(0, 0)] * (t.ndim - 2)
            return jnp.pad(t, pad).astype(cdt)

        cache = {"conv_x": tail(x_pre), "conv_B": tail(B_pre),
                 "conv_C": tail(C_pre), "h": h_final}
    return out, cache


def init_ssm_block(cfg: ModelConfig, key) -> Dict:
    return {"norm": jnp.ones((cfg.d_model,), jnp.float32),
            **init_ssm_core(cfg, key)}


def apply_ssm_block(cfg: ModelConfig, p: Dict, x: jax.Array,
                    positions: jax.Array, collect_cache: bool = False):
    h = rms_norm(x, p["norm"], cfg.rms_eps)
    y, cache = apply_ssm_core(cfg, p, h, collect_cache)
    return constrain(x + y, "act_hidden"), jnp.float32(0.0), cache


def ssm_cache_init(cfg: ModelConfig, batch: int, cache_len: int = 0) -> Dict:
    di, N, G, Hs, P = _ssm_dims(cfg)
    K = cfg.conv_kernel
    cdt = jnp.dtype(cfg.compute_dtype)
    return {"conv_x": jnp.zeros((batch, K - 1, Hs, P), cdt),
            "conv_B": jnp.zeros((batch, K - 1, G, N), cdt),
            "conv_C": jnp.zeros((batch, K - 1, G, N), cdt),
            "h": jnp.zeros((batch, Hs, P, N), jnp.float32)}


def decode_ssm_core(cfg: ModelConfig, p: Dict, cache: Dict, h: jax.Array):
    """h: (B, d) normed -> (cache', y (B, d))."""
    B, _ = h.shape
    di, N, G, Hs, P = _ssm_dims(cfg)
    z = proj_heads(h, p["w_z"])                              # (B,H,P)
    x_pre = proj_heads(h, p["w_x"])
    B_pre = proj_heads(h, p["w_B"])
    C_pre = proj_heads(h, p["w_C"])
    dt = dense(h, p["w_dt"])
    conv_x, xs = causal_conv_step(cache["conv_x"], x_pre, p["conv_x_w"],
                                  p["conv_x_b"])
    conv_B, Bc = causal_conv_step(cache["conv_B"], B_pre, p["conv_B_w"],
                                  p["conv_B_b"])
    conv_C, Cc = causal_conv_step(cache["conv_C"], C_pre, p["conv_C_w"],
                                  p["conv_C_b"])
    xs, Bc, Cc = jax.nn.silu(xs), jax.nn.silu(Bc), jax.nn.silu(Cc)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    h_new, y = ssd_decode_step(cache["h"], xs, dtf, A, Bc, Cc, p["D"])
    y = _gated_rms(y, z, p["gate_norm"], cfg.rms_eps)
    out = unproj_heads(y, p["out_proj"])
    return {"conv_x": conv_x, "conv_B": conv_B, "conv_C": conv_C,
            "h": h_new}, out


def decode_ssm_block(cfg: ModelConfig, p: Dict, cache: Dict,
                     x_t: jax.Array, pos: jax.Array):
    h = rms_norm(x_t, p["norm"], cfg.rms_eps)
    cache, y = decode_ssm_core(cfg, p, cache, h)
    return cache, x_t + y


# ================================================================= hybrid
def init_hybrid_block(cfg: ModelConfig, key) -> Dict:
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    return {
        "norm": jnp.ones((d,), jnp.float32),
        "mlp_norm": jnp.ones((d,), jnp.float32),
        "attn_fuse_norm": jnp.ones((d,), jnp.float32),
        "ssm_fuse_norm": jnp.ones((d,), jnp.float32),
        "attn": _attn_init(cfg, ks[0]),
        "ssm": init_ssm_core(cfg, ks[1]),
        **_mlp_init(cfg, ks[2]),
    }


def apply_hybrid_block(cfg: ModelConfig, p: Dict, x: jax.Array,
                       positions: jax.Array, collect_cache: bool = False):
    """Hymba-style: attention heads and SSM heads read the same input in
    parallel; outputs are RMS-normed and averaged (the paper's mean fusion)."""
    h = rms_norm(x, p["norm"], cfg.rms_eps)
    attn_out, k, v = _self_attention(cfg, p["attn"], h, positions)
    ssm_out, ssm_cache = apply_ssm_core(cfg, p["ssm"], h, collect_cache)
    fused = 0.5 * (rms_norm(attn_out, p["attn_fuse_norm"], cfg.rms_eps) +
                   rms_norm(ssm_out, p["ssm_fuse_norm"], cfg.rms_eps))
    x = constrain(x + fused, "act_hidden")
    h2 = rms_norm(x, p["mlp_norm"], cfg.rms_eps)
    x = constrain(x + _mlp(cfg, p, h2), "act_hidden")
    cache = None
    if collect_cache:
        C = cfg.cache_len(x.shape[1])
        cache = {"k": _ring_tail(k, C), "v": _ring_tail(v, C), **ssm_cache}
    return x, jnp.float32(0.0), cache


def hybrid_cache_init(cfg: ModelConfig, batch: int, cache_len: int) -> Dict:
    return {**_kv_cache_init(cfg, batch, cache_len),
            **ssm_cache_init(cfg, batch)}


def decode_hybrid_block(cfg: ModelConfig, p: Dict, cache: Dict,
                        x_t: jax.Array, pos: jax.Array):
    h = rms_norm(x_t, p["norm"], cfg.rms_eps)
    kv_cache = {"k": cache["k"], "v": cache["v"]}
    kv_cache, attn_out = _attn_decode(cfg, p["attn"], kv_cache, h, pos)
    ssm_cache = {k2: cache[k2] for k2 in ("conv_x", "conv_B", "conv_C", "h")}
    ssm_cache, ssm_out = decode_ssm_core(cfg, p["ssm"], ssm_cache, h)
    fused = 0.5 * (rms_norm(attn_out, p["attn_fuse_norm"], cfg.rms_eps) +
                   rms_norm(ssm_out, p["ssm_fuse_norm"], cfg.rms_eps))
    x_t = x_t + fused
    h2 = rms_norm(x_t, p["mlp_norm"], cfg.rms_eps)
    x_t = x_t + _mlp(cfg, p, h2)
    return {**kv_cache, **ssm_cache}, x_t


# ============================================================== dispatch
FAMILY_INIT = {"dense": init_dense_block, "moe": init_moe_block,
               "mla": init_mla_block, "ssm": init_ssm_block,
               "hybrid": init_hybrid_block}
FAMILY_APPLY = {"dense": apply_dense_block, "moe": apply_moe_block,
                "mla": apply_mla_block, "ssm": apply_ssm_block,
                "hybrid": apply_hybrid_block}
FAMILY_DECODE = {"dense": decode_dense_block, "moe": decode_moe_block,
                 "mla": decode_mla_block, "ssm": decode_ssm_block,
                 "hybrid": decode_hybrid_block}


def init_block(cfg: ModelConfig, key):
    return FAMILY_INIT[cfg.family](cfg, key)


def apply_block(cfg, p, x, positions, collect_cache=False):
    return FAMILY_APPLY[cfg.family](cfg, p, x, positions, collect_cache)


def decode_block(cfg, p, cache, x_t, pos):
    return FAMILY_DECODE[cfg.family](cfg, p, cache, x_t, pos)


def init_layer_cache(cfg: ModelConfig, batch: int, cache_len: int):
    if cfg.family in ("dense", "moe"):
        return _kv_cache_init(cfg, batch, cache_len)
    if cfg.family == "mla":
        return mla_cache_init(cfg, batch, cache_len)
    if cfg.family == "ssm":
        return ssm_cache_init(cfg, batch)
    if cfg.family == "hybrid":
        return hybrid_cache_init(cfg, batch, cache_len)
    raise ValueError(cfg.family)
