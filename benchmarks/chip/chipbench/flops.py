"""Operations a training step needs, per token, from a configuration's
published sizes (the benchmark's own count, not the compiler's). Each
family file counts its forward pass; the rules below hold for all.

Forward FLOPs are 2 per multiply-add of every matrix product the model
requires; the backward pass is counted as twice the forward, so a trained
token costs three forwards. Causal attention and the SSD's intra-chunk
form count only the unmasked half (position i sees i + 1 positions).
Recomputation (rematerialisation) is not counted: it is work the model
does not need. Embedding lookups, norms and elementwise ops are left out.
"""
from __future__ import annotations

from typing import Any, Dict

from .harness import load_family


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Three forwards of the configuration's family
    (``families/<family>.py``'s ``forward_per_token``)."""
    return 3.0 * load_family(config["family"]).forward_per_token(config, seq)
