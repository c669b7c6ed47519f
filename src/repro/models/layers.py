"""Shared neural-net primitives (pure jnp, SPMD-friendly)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


def dense(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: (..., d_in) @ w: (d_in, d_out) in compute dtype."""
    return jnp.einsum("...d,df->...f", x, w.astype(x.dtype))


def proj_heads(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: (..., d) @ w: (d, H, Dh) -> (..., H, Dh).

    Head-structured weights keep TP sharding on the head axis explicit —
    no flat-dim reshape for the SPMD partitioner to second-guess.
    """
    return jnp.einsum("...d,dhk->...hk", x, w.astype(x.dtype))


def unproj_heads(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: (..., H, Dh) @ w: (H, Dh, d) -> (..., d)."""
    return jnp.einsum("...hk,hkd->...d", x, w.astype(x.dtype))


def gated_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
              w_down: jax.Array, act: str = "swiglu") -> jax.Array:
    g = dense(x, w_gate)
    u = dense(x, w_up)
    if act == "swiglu":
        h = jax.nn.silu(g) * u
    elif act == "geglu":
        h = jax.nn.gelu(g, approximate=True) * u
    else:
        raise ValueError(act)
    return dense(h, w_down)


# --------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (DeepSeek-V2's yarn_get_mscale)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(dim: int, base: float, factor: float, original_len: int,
               beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies, as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``: the extrapolated frequencies above
    the correction range, the interpolated ones (divided by ``factor``)
    below it, and a linear ramp between."""
    extra = rope_freqs(dim, base)
    inter = extra / factor

    def corr(rot):
        return dim * math.log(original_len / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs=None, interleave: bool = False,
               scale: float = 1.0) -> jax.Array:
    """x: (..., S, H, D) or (..., S, D); positions: broadcastable to (..., S).

    Rotates the full last dim (D must be even) in rotate-half pairs
    (i, i + D/2). ``freqs`` replaces the plain inverse frequencies (YaRN);
    ``interleave`` first de-interleaves the pairs (2i, 2i+1), as
    DeepSeek-V2 does; ``scale`` multiplies cos and sin.
    """
    D = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(D, theta)
    freqs = jnp.asarray(freqs)                          # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    if x.ndim == ang.ndim + 1:                          # has head axis
        cos, sin = cos[..., None, :], sin[..., None, :]
    xf = x.astype(jnp.float32)
    if interleave:
        xf = jnp.concatenate([xf[..., 0::2], xf[..., 1::2]], axis=-1)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- init
def trunc_normal(key, shape, std, dtype) -> jax.Array:
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    return trunc_normal(key, (d_in, d_out), d_in ** -0.5, dtype)
