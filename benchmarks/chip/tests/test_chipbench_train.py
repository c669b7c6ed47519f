"""The training driver rehearsed at toy size on the CPU, through the rest
of a run (``run.execute`` without the look for a chip): a sound run is
correct, and each fault planted under the timed path is caught."""
import importlib.util
import os

import pytest

from chipbench_smoke import BENCH, smoke_cell
from chipbench import train_driver

spec = importlib.util.spec_from_file_location("chipbench_run",
                                              os.path.join(BENCH, "run.py"))
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)


def execute(workload, fault=None, trace=0, seed=2 ** 33 + 7):
    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", "1", "--trace", str(trace)])
    sel = dict(device_plane=lambda n: n == "/host:CPU",
               op_line=lambda n: n.startswith("tf_XLA"),
               module_line=lambda n: False)
    return run.execute(args, require_tpu=False, fault=fault,
                       cell=smoke_cell(workload), tracer_selectors=sel)


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch, tmp_path):
    # the program keeps no persistent cache when the variable is set after
    # JAX was imported: nothing is written into the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


@pytest.mark.parametrize("workload", ["mamba2-130m.train-full",
                                      "yi-6b-2L.train-full"])
def test_sound_run_is_correct(workload):
    out, rec = execute(workload)
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "resume_s",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert rec.counters["window_chunks_written"] > 0
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("fault", train_driver.FAULTS)
def test_fault_under_the_timed_path_is_not_correct(fault):
    out, _ = execute("yi-6b-2L.train-full", fault=fault)
    assert not out["correct"], (fault, out["compared"])


def test_traced_run_reports_per_layer_metrics():
    out, rec = execute("yi-6b-2L.train-full", trace=1)
    assert out["correct"]
    want = {"save_stall_s", "save_write_s", "restore_s",
            "device_idle_share.train"}
    assert want <= set(out["metrics"]), out["metrics"]
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    assert out["breakdown"]["device_ops"]
