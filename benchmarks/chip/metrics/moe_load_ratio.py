"""The busiest held expert's load over the held experts' mean: the
program's ``train.moe`` counts ``max_expert_load`` (the largest
per-expert assignments of each MoE layer, summed over the layers) over
``assignments_held`` / experts held, over the window's saves. 1 is an
even load; the step's grouped matmuls wait on the largest group."""
from chipbench.program_spans import in_window


def read(rec):
    spans = in_window(rec, "train.moe")
    if not spans:
        return None
    held = rec.cell.config["n_routed_experts"]
    most = sum(s.counts["max_expert_load"] for s in spans)
    mean = sum(s.counts["assignments_held"] for s in spans) / held
    return most / mean if mean else None
