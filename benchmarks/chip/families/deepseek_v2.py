"""The ``deepseek_v2`` family: DeepSeek-V2 (arXiv:2405.04434), as its
published ``modeling_deepseek.py`` computes it, for configurations without
a query LoRA (DeepSeek-V2-Lite).

Reference, per layer: RMSNorm; multi-head latent attention: q = h wq split
into a no-position part (``qk_nope_head_dim``) and a rotary part
(``qk_rope_head_dim``); [c, k_pe] = h wkv_a, c = RMSNorm(c) (the
``kv_lora_rank`` latent), [k_nope, v] = c wkv_b per head; k_pe is one
rotary key per token shared by every head; the rotary parts are
de-interleaved (pairs (2i, 2i+1) become (i, i + D/2)) and rotated
rotate-half with YaRN's frequencies and cos/sin scale; causal softmax of
[q_nope, q_pe].[k_nope, k_pe] times (nope + rope)^-1/2 times YaRN's
mscale(factor, mscale_all_dim) squared; the output projection; the
residual. Then RMSNorm and, in the first ``first_k_dense_replace`` layers,
a SwiGLU MLP of ``intermediate_size``; in the others the routed experts
beside the shared ones: softmax scores over every routed expert, greedy
top-k, the chosen scores times ``routed_scaling_factor`` as gates (not
renormalised), the output the shared experts' SwiGLU (width
``n_shared_experts`` x ``moe_intermediate_size``) plus, over the chosen
experts this chip holds, gate x SwiGLU_e. The loss adds, per MoE layer,
``aux_loss_alpha`` times the sequence-wise balance loss
mean_b sum_e f_e P_e, f_e = E/(k S) #{t : e chosen at t}, P_e = mean_t
s_{t,e}, over all E routed experts.

The chip's share: ``n_routed_experts`` is the count held here, experts
[``expert_offset``, + held) of ``n_routed_experts_total``; the router
scores all of them; what the absent experts would add is left out. The
held experts are computed densely over every token, weighted by a mask of
the routing. No departure from the published equations; the rotary pairs
are as published. The configuration file uses the Hugging Face
DeepSeek-V2 keys.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference
from chipbench.reference import decoder_layout, mm, padded_vocab, rms

F32_LEAVES = ("attn_norm", "mlp_norm", "kv_norm", "router")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
Q_BLOCK = 1024


def dims(c: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference uses, from the configuration file; a
    setting this reference does not implement is an error."""
    rs = c["rope_scaling"]
    implemented = {"q_lora_rank": None, "scoring_func": "softmax",
                   "topk_method": "greedy", "norm_topk_prob": False,
                   "moe_layer_freq": 1, "seq_aux": True,
                   "hidden_act": "silu"}
    for k, v in implemented.items():
        if c[k] != v:
            raise ValueError(f"{k}={c[k]!r}: only {v!r} is implemented")
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r}")
    lead = c["first_k_dense_replace"]
    return dict(L=c["num_hidden_layers"] - lead, lead=lead,
                d=c["hidden_size"], V=c["vocab_size"],
                H=c["num_attention_heads"], nope=c["qk_nope_head_dim"],
                rope=c["qk_rope_head_dim"], vh=c["v_head_dim"],
                kr=c["kv_lora_rank"], F=c["intermediate_size"],
                fe=c["moe_intermediate_size"], E=c["n_routed_experts"],
                E_all=c["n_routed_experts_total"],
                offset=c["expert_offset"], k=c["num_experts_per_tok"],
                shared=c["n_shared_experts"],
                scaling=c["routed_scaling_factor"],
                alpha=c["aux_loss_alpha"], eps=c["rms_norm_eps"],
                theta=c["rope_theta"], yarn=rs,
                tied=c["tie_word_embeddings"],
                Vp=padded_vocab(c, c["vocab_size"]))


def _attention_leaves(z):
    d, H, nope, rope, vh, kr = (z[k] for k in ("d", "H", "nope", "rope",
                                               "vh", "kr"))
    return {"attn_norm": (d,), "mlp_norm": (d,), "kv_norm": (kr,),
            "wq": (d, H, nope + rope), "wkv_a": (d, kr + rope),
            "wkv_b": (kr, H, nope + vh), "wo": (H, vh, d)}


def layout(c: Dict[str, Any]):
    z = dims(c)
    d, F, fe, E = z["d"], z["F"], z["fe"], z["E"]
    fs = z["shared"] * fe
    out = decoder_layout(z, {
        **_attention_leaves(z), "router": (d, z["E_all"]),
        "w_gate": (E, d, fe), "w_up": (E, d, fe), "w_down": (E, fe, d),
        "shared_gate": (d, fs), "shared_up": (d, fs),
        "shared_down": (fs, d)}, F32_LEAVES)
    dense = {**_attention_leaves(z), "w_gate": (d, F), "w_up": (d, F),
             "w_down": (F, d)}
    for k, shape in dense.items():
        out[f"dense_blocks/{k}"] = ((z["lead"],) + shape, "float32"
                                    if k in F32_LEAVES else "bfloat16")
    return out


def leaf_init(name: str, shape, key, c: Dict[str, Any]) -> jax.Array:
    """The default values (``reference.leaf_init``), with the leading
    dense layers stacked like the blocks, the latent norm a norm, and a
    stacked expert leaf (L, E, a, b) scaled by its fan-in a (axis 2)."""
    name = name.removeprefix("dense_")
    base = name.split("/")[-1]
    if base == "kv_norm":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if base in EXPERT_LEAVES and len(shape) == 4:
        return jax.random.normal(key, shape, jnp.float32) / \
            math.sqrt(shape[2])
    return reference.leaf_init(name, shape, key, c)


# --------------------------------------------------------------- rotary
def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_tables(z: Dict[str, Any], S: int):
    """cos and sin (S, rope) of the published YaRN embedding, float64."""
    y, D, base = z["yarn"], z["rope"], z["theta"]
    factor, orig = y["factor"], y["original_max_position_embeddings"]

    def corr_dim(rot):
        return D * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(y["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(y["beta_slow"])), D - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, D, 2) / D)
    inter = 1.0 / (factor * base ** (np.arange(0, D, 2) / D))
    mask = 1.0 - np.clip((np.arange(D // 2) - low) / (high - low), 0, 1)
    inv_freq = inter * (1 - mask) + extra * mask
    emb = np.concatenate([np.outer(np.arange(S), inv_freq)] * 2, axis=-1)
    scale = _mscale(factor, y["mscale"]) / _mscale(factor,
                                                    y["mscale_all_dim"])
    return np.cos(emb) * scale, np.sin(emb) * scale


def _rotary(x, cos, sin):
    """x: (b, S, h, D): de-interleave, then rotate-half."""
    D = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (D // 2, 2)).swapaxes(-1, -2) \
        .reshape(x.shape)
    c = jnp.asarray(cos, jnp.float32)[None, :, None]
    s = jnp.asarray(sin, jnp.float32)[None, :, None]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * c + rot * s


def softmax_scale(z: Dict[str, Any]) -> float:
    y = z["yarn"]
    m = _mscale(y["factor"], y["mscale_all_dim"]) \
        if y["mscale_all_dim"] else 1.0
    return (z["nope"] + z["rope"]) ** -0.5 * m * m


# ---------------------------------------------------------------- layer
def _swiglu(h, wg, wu, wd, prec):
    return mm("bsf,fd->bsd", jax.nn.silu(mm("bsd,df->bsf", h, wg, prec)) *
              mm("bsd,df->bsf", h, wu, prec), wd, prec)


def _attention(z, p, x, prec):
    b, S, _ = x.shape
    nope, kr = z["nope"], z["kr"]
    h = rms(x, p["attn_norm"], z["eps"])
    q = mm("bsd,dhk->bshk", h, p["wq"], prec)
    kv_a = mm("bsd,dr->bsr", h, p["wkv_a"], prec)
    c = rms(kv_a[..., :kr], p["kv_norm"], z["eps"])
    kv = mm("bsr,rhk->bshk", c, p["wkv_b"], prec)
    cos, sin = yarn_tables(z, S)
    q_pe = _rotary(q[..., nope:], cos, sin)
    k_pe = _rotary(kv_a[:, :, None, kr:], cos, sin)[:, :, 0]  # one head

    @jax.checkpoint
    def rows(q_nope, q_pe, first):
        """Causal attention of the queries first, first + 1, ... (blocks
        of queries bound the float32 scores the reference holds)."""
        s = mm("bqhk,bshk->bhqs", q_nope, kv[..., :nope], prec) + \
            mm("bqhk,bsk->bhqs", q_pe, k_pe, prec)
        s = s * softmax_scale(z)
        pos = first + jnp.arange(q_nope.shape[1])
        causal = pos[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        return mm("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1),
                  kv[..., nope:], prec)

    blk = min(Q_BLOCK, S)
    o = jnp.concatenate([rows(q[:, i:i + blk, :, :nope], q_pe[:, i:i + blk],
                              i) for i in range(0, S, blk)], axis=1)
    return x + mm("bqhk,hkd->bqd", o, p["wo"], prec)


def _routed(z, p, h, prec):
    """-> (shared + held experts' output, sum over sequences of the
    sequence-wise balance loss)."""
    b, S, _ = h.shape
    E_all, k, E, off = z["E_all"], z["k"], z["E"], z["offset"]
    scores = jax.nn.softmax(mm("bsd,de->bse", h, p["router"], prec), -1)
    top, idx = jax.lax.top_k(scores, k)                       # greedy
    gate = top * z["scaling"]
    chosen = jax.nn.one_hot(idx, E_all, dtype=jnp.float32)  # (b,S,k,E_all)
    f = chosen.sum((1, 2)) * (E_all / (k * S))
    balance = jnp.sum(f * scores.mean(1))
    weight = jnp.einsum("bske,bsk->bse", chosen, gate)[..., off:off + E]
    u = jax.nn.silu(mm("bsd,edf->bsef", h, p["w_gate"], prec)) * \
        mm("bsd,edf->bsef", h, p["w_up"], prec)
    y = mm("bsef,efd->bsed", u, p["w_down"], prec)
    out = mm("bsed,bse->bsd", y, weight, prec) + _swiglu(
        h, p["shared_gate"], p["shared_up"], p["shared_down"], prec)
    return out, balance


def _layer(z, p, x, prec, routed: bool):
    x = _attention(z, p, x, prec)
    h = rms(x, p["mlp_norm"], z["eps"])
    if routed:
        y, balance = _routed(z, p, h, prec)
        return x + y, balance
    return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], prec), 0.0


def forward(c, params, tokens, precision):
    """(b, S) tokens -> ((b, S, V) float32 logits over the vocabulary, the
    balance loss summed over MoE layers and sequences)."""
    z = dims(c)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = jnp.take(p["embed"], tokens, axis=0)
    total = jnp.float32(0.0)
    for stack, routed in (("dense_blocks", False), ("blocks", True)):
        def body(carry, lp, routed=routed):
            h, t = carry
            h, bal = _layer(z, lp, h, precision, routed)
            return (h, t + bal), None

        (x, total), _ = jax.lax.scan(jax.checkpoint(body), (x, total),
                                     p[stack])
    x = rms(x, p["final_norm"], z["eps"])
    head = p["embed"].T if z["tied"] else p["lm_head"]
    return mm("bsd,dv->bsv", x, head[:, :z["V"]], precision), total


def logits(c, params, tokens, precision):
    return forward(c, params, tokens, precision)[0]


def loss_sum(c, params, tokens, labels, precision="f32"):
    """Summed cross-entropy plus, per token, the balance term: the
    training reference divides by the tokens, which leaves the mean
    cross-entropy + alpha x sum over MoE layers of the mean over
    sequences of the balance loss."""
    lg, balance = forward(c, params, tokens, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked) + \
        c["aux_loss_alpha"] * tokens.shape[1] * balance


def forward_per_token(c: Dict[str, Any], seq: int) -> float:
    """Forward FLOPs per token (``chipbench.flops`` says what counts). A
    held expert's SwiGLU counts for the share of a token's assignments
    that land here on average, k x held / routed."""
    z = dims(c)
    d, H, nope, rope, vh, kr = (z[k] for k in ("d", "H", "nope", "rope",
                                               "vh", "kr"))
    proj = 2 * (d * H * (nope + rope) + d * (kr + rope) +
                kr * H * (nope + vh) + H * vh * d)
    attn = 2 * H * (nope + rope + vh) * (seq + 1) / 2
    swiglu = 2 * 3 * d
    dense = swiglu * z["F"]
    moe = 2 * d * z["E_all"] + swiglu * z["fe"] * (
        z["shared"] + z["k"] * z["E"] / z["E_all"])
    head = 2 * d * z["V"]
    return (z["lead"] * (proj + attn + dense) +
            z["L"] * (proj + attn + moe) + head)


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` (``repro.models.config``)."""
    from repro.models.config import ModelConfig
    from chipbench.program import shared_fields
    z = dims(c)
    y = z["yarn"]
    return ModelConfig(
        family="mla", n_layers=c["num_hidden_layers"], d_model=z["d"],
        vocab=z["V"], n_heads=z["H"], n_kv_heads=z["H"], head_dim=z["vh"],
        q_lora_rank=0, kv_lora_rank=z["kr"], qk_nope_dim=z["nope"],
        qk_rope_dim=z["rope"], v_head_dim=z["vh"], rope_theta=z["theta"],
        yarn_factor=y["factor"],
        yarn_original_len=y["original_max_position_embeddings"],
        yarn_beta_fast=y["beta_fast"], yarn_beta_slow=y["beta_slow"],
        yarn_mscale=y["mscale"], yarn_mscale_all_dim=y["mscale_all_dim"],
        rope_interleave=True, d_ff=z["F"], act="swiglu",
        first_k_dense=z["lead"], n_experts=z["E"],
        n_routed_experts=z["E_all"], expert_offset=z["offset"],
        top_k=z["k"], d_ff_expert=z["fe"], n_shared_experts=z["shared"],
        routed_scaling=z["scaling"], router_aux_coef=z["alpha"],
        rms_eps=z["eps"], tie_embeddings=z["tied"], **shared_fields(c))
