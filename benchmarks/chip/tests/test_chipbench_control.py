"""The control of ``correct``: the plain reference put in the program's
place, computed with fp8 operands (the step below the configurations'
bfloat16), fails the committed limits, on three seeds, on the CPU: the
training control at toy size, the serving control at the published
configuration with short requests (the chip readings at the cells' own
sizes are in PERF.md)."""
import numpy as np
import pytest

from chipbench_smoke import committed_or_unlisted, smoke_cell
from chipbench import compare
from chipbench.reference import flat, init_weights, nest
from chipbench.serve_driver import control_gap, prompts_at
from chipbench.train_driver import reference_readings

SEEDS = (11, 12, 2 ** 32 + 13)


@pytest.mark.parametrize("workload", ["mamba2-130m.train-full",
                                      "yi-6b-2L.train-full"])
def test_training_control_is_not_correct(workload):
    cell = smoke_cell(workload)
    for seed in SEEDS:
        ref = reference_readings(cell.config, cell.traffic, seed)
        ctl = reference_readings(cell.config, cell.traffic, seed, "fp8")
        got = compare.with_limits(compare.train_numbers(ctl, ref, 0.0),
                                  cell.limits)
        assert not all(c.ok for c in got), (seed, got)


def test_serving_control_is_not_correct():
    # the published configuration (toy depths hide the fp8 error), with
    # requests short enough for the CPU: 4 prompts of 16 tokens, 8 served
    cell = committed_or_unlisted("mamba2-130m.serve-refresh")
    c = cell.config
    t = dict(cell.traffic, requests=4, prompt_len=16, new_tokens=8)
    for seed in SEEDS:
        saved = nest({k: np.asarray(v) for k, v in
                      flat(init_weights(c, seed)).items()})
        tokens = np.random.default_rng(seed).integers(
            0, c["vocab_size"], (t["requests"], t["new_tokens"]),
            dtype=np.int32)
        gap = control_gap(c, {"saved": saved, "tokens": tokens,
                              "prompts": prompts_at(seed, 0, t,
                                                    c["vocab_size"])})
        assert gap > cell.limits["served_gap"], (seed, gap)
