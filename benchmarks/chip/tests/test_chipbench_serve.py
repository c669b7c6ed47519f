"""The serving driver rehearsed at toy size on the CPU: a sound run is
correct, a stale refresh and an altered token are caught."""
import pytest

from test_chipbench_train import execute, no_compile_cache  # noqa: F401
from chipbench import serve_driver


def test_sound_run_is_correct():
    out, rec = execute("mamba2-130m.serve-refresh")
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {"save_to_served_s", "setup_s"}
    assert rec.counters["served_tokens"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("fault", serve_driver.FAULTS)
def test_fault_under_the_timed_path_is_not_correct(fault):
    out, _ = execute("mamba2-130m.serve-refresh", fault=fault)
    assert not out["correct"], (fault, out["compared"])
