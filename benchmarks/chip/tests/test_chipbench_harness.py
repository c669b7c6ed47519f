"""The harness finds every file of a cell and metric by name."""
import json
import os
import subprocess
import sys

import pytest

from chipbench_smoke import BENCH
from chipbench.harness import (BenchError, find_cell, load_benchmark,
                               load_reader, peaks_for, seed_words,
                               RunRecord)
from chipbench.flops import train_flops_per_token

ROOT = os.path.dirname(os.path.dirname(BENCH))


def test_every_cell_and_metric_is_found_by_name():
    bench = load_benchmark()
    for w in bench["workloads"]:
        cell = find_cell(bench, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["driver"] in ("train", "serve_refresh")
        assert cell.limits
        assert any(m["name"] != "setup_s" for m in cell.end_to_end)
        assert cell.per_layer
    for m in bench["per_layer"]:
        assert callable(load_reader(m["name"]))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))


def test_unknown_names_are_errors():
    bench = load_benchmark()
    with pytest.raises(BenchError):
        find_cell(bench, "no-such.cell")
    with pytest.raises(BenchError):
        load_reader("no_such_metric")


def test_peaks_table_rejects_unknown_device():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(BenchError):
        peaks_for("cpu")


def test_readers_return_nothing_where_nothing_was_recorded():
    bench = load_benchmark()
    rec = RunRecord(cell=find_cell(bench, "yi-6b-2L.train-full"))
    for m in bench["per_layer"]:
        assert load_reader(m["name"])(rec) is None


def test_seed_words_use_every_bit():
    big = 2 ** 33 + 5
    assert seed_words(big) != seed_words(5)
    assert seed_words(big) == seed_words(big)
    with pytest.raises(BenchError):
        seed_words(-1)


def test_flops_per_token_by_hand():
    dense = {"family": "dense", "hidden_size": 8, "num_attention_heads": 2,
             "num_key_value_heads": 1, "intermediate_size": 16,
             "num_hidden_layers": 3, "vocab_size": 10}
    # q,o: 8x8 each; k,v: 8x4 each; mlp 3 x 8x16; attention 2*2*2*4*(5)/2
    per_layer = 2 * (64 + 64 + 32 + 32) + 2 * 3 * 128 + 2 * 2 * 8 * 5 / 2
    assert train_flops_per_token(dense, 4) == 3 * (3 * per_layer + 2 * 80)
    ssm = {"family": "ssm", "d_model": 8, "n_layer": 2, "vocab_size": 10,
           "ssm_cfg": {"expand": 2, "d_state": 4, "ngroups": 1,
                       "headdim": 4, "d_conv": 4, "chunk_size": 8}}
    # di 16, heads 4; in_proj 8 x (32 + 8 + 4); conv 4 x 24;
    # intra (chunk 4): 4*5/2*2 + 16*5/2*2; inter 2 * 2*16*4; out 16 x 8
    per_layer = 2 * 8 * 44 + 2 * 4 * 24 + (20 + 80) + 256 + 2 * 128
    assert train_flops_per_token(ssm, 4) == 3 * (2 * per_layer + 2 * 80)


def test_run_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "yi-6b-2L.train-full", "--seed", "7", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "TPU" in p.stderr


def test_benchmark_file_keys():
    """Holds for any later addition: the contract's keys, today's names
    among the metrics, a reader for each per-layer metric, known cells in
    every ``workloads`` list, and a family file for each configuration."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    assert {"train_tokens_per_s", "resume_s", "setup_s"} <= end_to_end
    assert {"train_mfu", "train_step_s", "save_stall_s", "save_write_s",
            "restore_s", "device_idle_share.train", "save_d2h_s",
            "ckpt_wait_s", "save_diff_s", "save_commit_s", "restore_read_s",
            "step_build_s"} <= {m["name"] for m in bench["per_layer"]}
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py")), m["name"]
        assert m["moves"] in end_to_end, m["name"]
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
    for c in bench["configs"]:
        family = json.load(open(os.path.join(ROOT, c["file"])))["family"]
        assert os.path.exists(os.path.join(BENCH, "families",
                                           f"{family}.py")), c["name"]
