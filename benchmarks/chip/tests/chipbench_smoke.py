"""Smoke-size cells for the CPU tests: the configurations keep the
published files' keys at toy sizes; the traffic is the committed traffic
with short sequences."""
from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench.harness import Cell, find_cell, load_benchmark  # noqa: E402

PREC = {"params": "bfloat16", "compute": "bfloat16", "logits": "float32"}
CONFIGS = {
    "mamba2-130m": {
        "name": "mamba2-smoke", "family": "ssm", "d_model": 64, "n_layer": 2,
        "vocab_size": 199, "ssm_cfg": {
            "layer": "Mamba2", "d_state": 16, "d_conv": 4, "expand": 2,
            "headdim": 16, "ngroups": 1, "chunk_size": 16},
        "norm_epsilon": 1e-5, "tie_embeddings": True,
        "pad_vocab_size_multiple": 256, "precision": PREC},
    "yi-6b-2L": {
        "name": "yi-smoke", "family": "dense", "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 199,
        "rms_norm_eps": 1e-5, "rope_theta": 5e6,
        "tie_word_embeddings": False, "precision": PREC},
}
SIZES = {"train": dict(batch=4, seq=64, ckpt_every=2, setup_steps=4),
         "serve_refresh": dict(batch=4, seq=64, prompt_len=8, new_tokens=4)}


# Cells that no entry of BENCHMARK.json runs: the program departs from
# mamba2-130m's published configuration (PERF.md). Their drivers are
# rehearsed here with the metrics and the limits they last ran with on the
# chip.
UNLISTED = {
    "mamba2-130m.train-full": dict(
        traffic="train-full",
        limits={"grad_gap": 0.1, "update_gap": 0.015, "store_mismatch": 0},
        end_to_end=["train_tokens_per_s", "resume_s", "setup_s"],
        per_layer=["save_stall_s", "save_write_s", "restore_s",
                   "device_idle_share.train"]),
    "mamba2-130m.serve-refresh": dict(
        traffic="serve-refresh",
        limits={"served_gap": 0.25, "params_mismatch": 0},
        end_to_end=["save_to_served_s", "setup_s"],
        per_layer=["follower_poll_s", "refresh_s",
                   "device_idle_share.serve"]),
}
UNITS = {"train_tokens_per_s": "tokens/s", "device_idle_share.train": "%",
         "device_idle_share.serve": "%"}


def committed_or_unlisted(workload: str) -> Cell:
    """The cell as BENCHMARK.json has it, or as ``UNLISTED`` has it, at
    its published configuration."""
    bench = load_benchmark()
    if workload in {w["name"] for w in bench["workloads"]}:
        return find_cell(bench, workload)
    u = UNLISTED[workload]
    with open(os.path.join(BENCH, "configs",
                           f"{workload.split('.')[0]}.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{u['traffic']}.json")) as f:
        traffic = json.load(f)
    return Cell(workload, 1, config, traffic, dict(u["limits"]),
                [{"name": m, "unit": UNITS.get(m, "s")}
                 for m in u["end_to_end"]],
                [{"name": m, "unit": UNITS.get(m, "s")}
                 for m in u["per_layer"]])


def smoke_cell(workload: str) -> Cell:
    """The cell (its traffic, limits and metrics) at toy size."""
    cell = committed_or_unlisted(workload)
    config = json.loads(json.dumps(CONFIGS[workload.split(".")[0]]))
    traffic = dict(cell.traffic, **SIZES[cell.traffic["driver"]])
    return Cell(cell.name, cell.chips, config, traffic, cell.limits,
                cell.end_to_end, cell.per_layer)
