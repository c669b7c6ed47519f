"""The ``dense`` family: a Llama-architecture decoder (Yi,
arXiv:2403.04652).

Reference: per layer, RMSNorm, grouped-query attention with rotary
embeddings (rotate-half convention, theta from the configuration), causal
softmax, RMSNorm, SwiGLU MLP; the output head is its own matrix unless
the configuration ties it. The configuration file uses the Hugging Face
Llama keys (``hidden_size``, ``num_key_value_heads``, ...); the head size
is ``hidden_size / num_attention_heads``.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference import (decoder_layout, decoder_logits, mm,
                                 padded_vocab, rms, rope)

F32_LEAVES = ("attn_norm", "mlp_norm")


def dims(c: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference uses, from the configuration file."""
    d = c["hidden_size"]
    H = c["num_attention_heads"]
    return dict(L=c["num_hidden_layers"], d=d, V=c["vocab_size"],
                H=H, KVH=c["num_key_value_heads"], Dh=d // H,
                F=c["intermediate_size"], eps=c["rms_norm_eps"],
                theta=c["rope_theta"], tied=c["tie_word_embeddings"],
                Vp=padded_vocab(c, c["vocab_size"]))


def layout(c: Dict[str, Any]):
    z = dims(c)
    d, H, KVH, Dh, F = z["d"], z["H"], z["KVH"], z["Dh"], z["F"]
    return decoder_layout(z, {
        "attn_norm": (d,), "mlp_norm": (d,), "wq": (d, H, Dh),
        "wk": (d, KVH, Dh), "wv": (d, KVH, Dh), "wo": (H, Dh, d),
        "w_gate": (d, F), "w_up": (d, F), "w_down": (F, d)}, F32_LEAVES)


def _dense_layer(z, p, x, prec):
    h = rms(x, p["attn_norm"], z["eps"])
    q = rope(mm("bsd,dhk->bshk", h, p["wq"], prec), z["theta"])
    k = rope(mm("bsd,dhk->bshk", h, p["wk"], prec), z["theta"])
    v = mm("bsd,dhk->bshk", h, p["wv"], prec)
    rep = z["H"] // z["KVH"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = mm("bqhk,bshk->bhqs", q, k, prec) / math.sqrt(z["Dh"])
    S = x.shape[1]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = mm("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v, prec)
    x = x + mm("bqhk,hkd->bqd", o, p["wo"], prec)
    h = rms(x, p["mlp_norm"], z["eps"])
    u = jax.nn.silu(mm("bsd,df->bsf", h, p["w_gate"], prec)) * \
        mm("bsd,df->bsf", h, p["w_up"], prec)
    return x + mm("bsf,fd->bsd", u, p["w_down"], prec)


def logits(c, params, tokens, precision):
    return decoder_logits(dims(c), _dense_layer, params, tokens, precision)


def forward_per_token(c: Dict[str, Any], seq: int) -> float:
    """Forward FLOPs per token (``chipbench.flops`` says what counts)."""
    d = c["hidden_size"]
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    dh = d // h
    proj = 2 * d * (2 * h * dh + 2 * kvh * dh)
    mlp = 2 * 3 * d * c["intermediate_size"]
    attn = 2 * 2 * h * dh * (seq + 1) / 2
    head = 2 * d * c["vocab_size"]
    return c["num_hidden_layers"] * (proj + mlp + attn) + head


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` (``repro.models.config``)."""
    from repro.models.config import ModelConfig
    from chipbench.program import shared_fields
    d, h = c["hidden_size"], c["num_attention_heads"]
    return ModelConfig(
        family="dense", n_layers=c["num_hidden_layers"], d_model=d,
        vocab=c["vocab_size"], n_heads=h,
        n_kv_heads=c["num_key_value_heads"], head_dim=d // h,
        d_ff=c["intermediate_size"], act="swiglu",
        rope_theta=c["rope_theta"], rms_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], **shared_fields(c))
