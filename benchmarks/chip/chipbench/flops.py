"""Operations a training step needs, per token, from a configuration's
published sizes (the benchmark's own count, not the compiler's).

Forward FLOPs are 2 per multiply-add of every matrix product the model
requires; the backward pass is counted as twice the forward, so a trained
token costs three forwards. Causal attention and the SSD's intra-chunk
form count only the unmasked half (position i sees i + 1 positions).
Recomputation (rematerialisation) is not counted: it is work the model
does not need. Embedding lookups, norms and elementwise ops are left out.
"""
from __future__ import annotations

from typing import Any, Dict


def dense_forward_per_token(c: Dict[str, Any], seq: int) -> float:
    d = c["hidden_size"]
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    dh = d // h
    proj = 2 * d * (2 * h * dh + 2 * kvh * dh)
    mlp = 2 * 3 * d * c["intermediate_size"]
    attn = 2 * 2 * h * dh * (seq + 1) / 2
    head = 2 * d * c["vocab_size"]
    return c["num_hidden_layers"] * (proj + mlp + attn) + head


def ssm_forward_per_token(c: Dict[str, Any], seq: int) -> float:
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


FORWARD = {"dense": dense_forward_per_token, "ssm": ssm_forward_per_token}


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    return 3.0 * FORWARD[config["family"]](config, seq)
