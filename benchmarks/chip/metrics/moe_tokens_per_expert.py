"""Routed assignments one held expert receives in one MoE layer of one
step: the program's ``train.moe`` span (at each save step of
``repro.launch.train.main``; count ``assignments_held``, summed over the
MoE layers) over the MoE layers and the experts held, mean over the
window's saves."""
from chipbench.program_spans import in_window


def read(rec):
    spans = in_window(rec, "train.moe")
    if not spans:
        return None
    c = rec.cell.config
    layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    per_span = [s.counts["assignments_held"] / (layers * c["n_routed_experts"])
                for s in spans]
    return sum(per_span) / len(per_span)
