"""The ``ssm`` family: Mamba-2 (arXiv:2405.21060).

Reference: per layer, RMSNorm, input projections to z, x, B, C and dt, a
depthwise causal convolution of x, B and C followed by SiLU, dt =
softplus(dt + dt_bias), A = -exp(A_log), the SSD in its quadratic (dual)
form over the whole sequence
y_i = sum_{j<=i} (C_i . B_j) exp(sum_{j<k<=i} dt_k A) dt_j x_j + D x_i,
then RMSNorm(y * silu(z)) over the inner width, the output projection and
the residual. The output head is the transposed embedding. The
configuration file uses mamba_ssm's keys (``d_model``, ``n_layer``,
``ssm_cfg``, ...).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference import (decoder_layout, decoder_logits, mm,
                                 padded_vocab, rms)

F32_LEAVES = ("norm", "gate_norm", "conv_x_b", "conv_B_b", "conv_C_b",
              "A_log", "D", "dt_bias")


def dims(c: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference uses, from the configuration file."""
    s = c["ssm_cfg"]
    d = c["d_model"]
    di = s["expand"] * d
    return dict(L=c["n_layer"], d=d, V=c["vocab_size"], di=di,
                N=s["d_state"], G=s["ngroups"], P=s["headdim"],
                H=di // s["headdim"], K=s["d_conv"],
                eps=c["norm_epsilon"], tied=c["tie_embeddings"],
                Vp=padded_vocab(c, c["vocab_size"]))


def layout(c: Dict[str, Any]):
    z = dims(c)
    d, H, P, G, N, K = z["d"], z["H"], z["P"], z["G"], z["N"], z["K"]
    return decoder_layout(z, {
        "norm": (d,), "w_z": (d, H, P), "w_x": (d, H, P),
        "w_B": (d, G, N), "w_C": (d, G, N), "w_dt": (d, H),
        "conv_x_w": (H, P, K), "conv_x_b": (H, P),
        "conv_B_w": (G, N, K), "conv_B_b": (G, N),
        "conv_C_w": (G, N, K), "conv_C_b": (G, N),
        "A_log": (H,), "D": (H,), "dt_bias": (H,),
        "gate_norm": (H, P), "out_proj": (H, P, d)}, F32_LEAVES)


def _causal_conv(x, w, b):
    """x: (B, S, *C); w: (*C, K): y_t = b + sum_k w_k x_{t-K+1+k}."""
    K, S = w.shape[-1], x.shape[1]
    xp = jnp.pad(x, [(0, 0), (K - 1, 0)] + [(0, 0)] * (x.ndim - 2))
    return b + sum(xp[:, k:k + S] * w[..., k] for k in range(K))


def _ssd(x, dt, A, Bm, Cm, D, prec):
    """Dual (quadratic) form of the SSD over the whole sequence.
    x (b,S,H,P), dt (b,S,H), A (H,), Bm/Cm (b,S,G,N), D (H,)."""
    H, G = x.shape[2], Bm.shape[2]
    Lc = jnp.cumsum(dt * A, axis=1)                          # (b,S,H)
    seg = Lc[:, :, None, :] - Lc[:, None, :, :]              # (b,i,j,H)
    S = x.shape[1]
    causal = (jnp.arange(S)[:, None] >= jnp.arange(S)[None, :])
    decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
    cb = mm("bign,bjgn->bgij", Cm, Bm, prec)
    cb = jnp.repeat(cb, H // G, axis=1)                      # (b,H,i,j)
    w = cb * decay.transpose(0, 3, 1, 2) * dt.transpose(0, 2, 1)[:, :, None]
    y = mm("bhij,bjhp->bihp", w, x, prec)
    return y + x * D[None, None, :, None]


def _ssm_layer(z, p, x, prec):
    h = rms(x, p["norm"], z["eps"])
    zg = mm("bsd,dhp->bshp", h, p["w_z"], prec)
    xs = mm("bsd,dhp->bshp", h, p["w_x"], prec)
    Bm = mm("bsd,dgn->bsgn", h, p["w_B"], prec)
    Cm = mm("bsd,dgn->bsgn", h, p["w_C"], prec)
    dt = mm("bsd,dh->bsh", h, p["w_dt"], prec)
    xs = jax.nn.silu(_causal_conv(xs, p["conv_x_w"], p["conv_x_b"]))
    Bm = jax.nn.silu(_causal_conv(Bm, p["conv_B_w"], p["conv_B_b"]))
    Cm = jax.nn.silu(_causal_conv(Cm, p["conv_C_w"], p["conv_C_b"]))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = _ssd(xs, dt, -jnp.exp(p["A_log"]), Bm, Cm, p["D"], prec)
    g = rms(y * jax.nn.silu(zg), p["gate_norm"], z["eps"], axes=(-2, -1))
    return x + mm("bshp,hpd->bsd", g, p["out_proj"], prec)


def logits(c, params, tokens, precision):
    return decoder_logits(dims(c), _ssm_layer, params, tokens, precision)


def forward_per_token(c: Dict[str, Any], seq: int) -> float:
    """Forward FLOPs per token (``chipbench.flops`` says what counts)."""
    s = c["ssm_cfg"]
    d = c["d_model"]
    di = s["expand"] * d
    n, g, p = s["d_state"], s["ngroups"], s["headdim"]
    h = di // p
    q = min(s["chunk_size"], seq)
    in_proj = 2 * d * (2 * di + 2 * g * n + h)
    conv = 2 * s["d_conv"] * (di + 2 * g * n)
    intra = 2 * g * n * (q + 1) / 2 + 2 * h * p * (q + 1) / 2
    inter = 2 * h * p * n + 2 * h * p * n
    out_proj = 2 * di * d
    head = 2 * d * c["vocab_size"]
    return c["n_layer"] * (in_proj + conv + intra + inter + out_proj) + head


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` (``repro.models.config``)."""
    from repro.models.config import ModelConfig
    from chipbench.program import shared_fields
    s = c["ssm_cfg"]
    di = s["expand"] * c["d_model"]
    return ModelConfig(
        family="ssm", n_layers=c["n_layer"], d_model=c["d_model"],
        vocab=c["vocab_size"], d_inner=di, ssm_state=s["d_state"],
        ssm_heads=di // s["headdim"], ssm_groups=s["ngroups"],
        conv_kernel=s["d_conv"], ssm_chunk=s["chunk_size"],
        tie_embeddings=c["tie_embeddings"], rms_eps=c["norm_epsilon"],
        **shared_fields(c))
