"""The train step's share of the chips' bf16 peak: the FLOPs of one step
(forward and backward per token from ``chipbench.flops``, times the
step's tokens) over the step's device seconds (``train_step_s``, from
the trace)."""
from chipbench.flops import train_flops_per_token
from chipbench.harness import load_reader


def read(rec):
    if rec.peaks is None:
        return None
    step_s = load_reader("train_step_s")(rec)
    if step_s is None:
        return None
    t = rec.cell.traffic
    flops = train_flops_per_token(rec.cell.config, t["seq"]) * \
        t["batch"] * t["seq"]
    return 100.0 * flops / step_s / (rec.chips * rec.peaks["bf16_flops"])
