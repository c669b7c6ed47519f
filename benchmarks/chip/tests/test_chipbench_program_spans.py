"""The program's spans (``repro.obs``) in a traced run, rehearsed at toy
size on the CPU: the six readers that read them report, the spans
account for a save's write, and a program without spans gives the
readers nothing to read."""
import importlib.util
import os
import sys

import pytest

from chipbench_smoke import BENCH, smoke_cell
from chipbench.harness import load_reader

SPAN_METRICS = ("save_d2h_s", "ckpt_wait_s", "save_diff_s", "save_commit_s",
                "restore_read_s", "step_build_s")

spec = importlib.util.spec_from_file_location("chipbench_run",
                                              os.path.join(BENCH, "run.py"))
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        # the program keeps no persistent cache when the variable is set
        # after JAX was imported: nothing is written into the checkout
        mp.setenv("JAX_COMPILATION_CACHE_DIR",
                  str(tmp_path_factory.mktemp("jax_cache")))
        args = run.parse(["--workload", "yi-6b-2L.train-full", "--seed",
                          str(2 ** 33 + 11), "--seconds", "1", "--trace",
                          "1"])
        sel = dict(device_plane=lambda n: n == "/host:CPU",
                   op_line=lambda n: n.startswith("tf_XLA"),
                   module_line=lambda n: False)
        return run.execute(args, require_tpu=False,
                           cell=smoke_cell("yi-6b-2L.train-full"),
                           tracer_selectors=sel)


def window(rec, name):
    from repro import obs
    m = rec.spans.marks
    return obs.records(name, since=m["window_start"], until=m["window_end"])


def test_span_metrics_are_reported(traced):
    out, _ = traced
    assert out["correct"]
    for name in SPAN_METRICS:
        assert out["metrics"][name]["value"] > 0, name
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["restore_read_s"] <= m["restore_s"]


def test_save_write_s_is_the_inject_span(traced):
    out, rec = traced
    inject = window(rec, "store.inject")
    assert len(inject) == rec.counters["cycles"]
    assert out["metrics"]["save_write_s"]["value"] == \
        sum(s.seconds for s in inject) / len(inject)


def test_write_phases_cover_the_write(traced):
    _, rec = traced
    spans = window(rec, None)
    writes = [s for s in spans if s.name == "ckpt.write"]
    assert writes
    for w in writes:
        children = [s for s in spans if s.parent == w.id]
        assert {"ckpt.diff", "store.inject", "ckpt.retention"} <= \
            {s.name for s in children}
        assert all(s.ids["step"] == w.ids["step"] for s in children)
        assert sum(s.seconds for s in children) >= 0.9 * w.seconds


def test_readers_read_nothing_without_program_spans(traced, monkeypatch):
    _, rec = traced
    assert all(load_reader(m)(rec) is not None for m in SPAN_METRICS)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for m in SPAN_METRICS:
        assert load_reader(m)(rec) is None, m
