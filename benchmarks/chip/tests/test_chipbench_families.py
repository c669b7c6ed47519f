"""Model families are files (``families/<family>.py``): the two moved
families give the numbers recorded before the move, bit for bit; a family
is added by a file alone; a family without a file is an error that names
the missing file; the traffic's ``reference_rows`` reaches the reference."""
import hashlib
import json
import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_smoke import BENCH, CONFIGS, smoke_cell
from chipbench import harness, reference
from chipbench.flops import train_flops_per_token
from chipbench.harness import BenchError, find_cell
from chipbench.program import check_layout, import_program, model_config
from chipbench.reference import flat, init_weights, layout, logits, loss_sum
from chipbench.train_driver import reference_readings

SEED = 2 ** 33 + 7
# Recorded from the reference as it was before the families moved into
# their files: the smoke configurations, SEED, tokens from
# default_rng(5), sequence 16 (and 64 for the FLOP count).
PINNED = {
    "mamba2-130m": {
        "weights": "e9ed29ff5563019c0e9cd535b671cd5d"
                   "3763a47db76b5c155ab8bb2ee4eb09f9",
        "logits_f32": "7ade9b8b5dbae082cc4a2095466264bd"
                      "fe666b7a10b9cf41c86275d9727984ce",
        "logits_fp8": "fa3852764d32af92535bdcf665602b83"
                      "52d16f586670ef90e42e4d7ed32f0499",
        "loss_sum": "0x1.52856c0000000p+7",
        "flops": 473568.0,
    },
    "yi-6b-2L": {
        "weights": "503ab426b5f2701f85c51ff533d31bdd"
                   "bfbd506b42a20cfd671cce05c04fecea",
        "logits_f32": "1a5e4038cf3f087c7434b7ab97744303"
                      "19aabc7f7c6c24927f1de92e4c0d5c98",
        "logits_fp8": "5b2a99216af50ef76cc9862bdacbef54"
                      "82772e85b13fd915f281e7ed1be9c4f6",
        "loss_sum": "0x1.6b51600000000p+7",
        "flops": 568704.0,
    },
}


def sha(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


def tokens_and_labels(c):
    toks = np.random.default_rng(5).integers(0, c["vocab_size"], (2, 17),
                                             dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def numbers(c):
    p = init_weights(c, SEED)
    f = flat(p)
    tok, lab = tokens_and_labels(c)
    return {"weights": sha(f[k] for k in sorted(f)),
            "logits_f32": sha([logits(c, p, tok, "f32")]),
            "logits_fp8": sha([logits(c, p, tok, "fp8")]),
            "loss_sum": float(loss_sum(c, p, tok, lab)).hex(),
            "flops": train_flops_per_token(c, 64)}


@pytest.mark.parametrize("config", sorted(PINNED))
def test_moved_family_reproduces_the_recorded_numbers(config):
    assert numbers(CONFIGS[config]) == PINNED[config]


def test_a_family_is_added_by_a_file_alone(tmp_path, monkeypatch):
    import_program()
    dense = CONFIGS["yi-6b-2L"]
    want = dict(numbers(dense), layout=layout(dense))
    for sub in ("families", "configs", "traffic", "limits"):
        (tmp_path / sub).mkdir()
    shutil.copy(os.path.join(BENCH, "families", "dense.py"),
                tmp_path / "families" / "toy.py")
    (tmp_path / "configs" / "toy-smoke.json").write_text(
        json.dumps(dict(dense, name="toy-smoke", family="toy")))
    shutil.copy(os.path.join(BENCH, "traffic", "train-full.json"),
                tmp_path / "traffic")
    shutil.copy(os.path.join(BENCH, "limits", "yi-6b-2L.train-full.json"),
                tmp_path / "limits" / "toy-smoke.train-full.json")
    bench = {"workloads": [{"name": "toy-smoke.train-full",
                            "config": "toy-smoke", "traffic": "train-full",
                            "chips": 1}],
             "end_to_end": [], "per_layer": []}
    monkeypatch.setattr(harness, "BENCH_DIR", str(tmp_path))
    c = find_cell(bench, "toy-smoke.train-full",
                  bench_dir=str(tmp_path)).config
    check_layout(c, model_config(c))
    assert dict(numbers(c), layout=layout(c)) == want
    with pytest.raises(BenchError, match=r"families/dense\.py"):
        layout(dense)           # only the files under the new dir count


@pytest.mark.parametrize("call", ["model_config", "layout",
                                  "train_flops_per_token"])
def test_a_family_without_a_file_is_an_error(call):
    c = dict(CONFIGS["yi-6b-2L"], family="no_such_family")
    fn = {"model_config": model_config, "layout": layout,
          "train_flops_per_token": lambda c: train_flops_per_token(c, 64)}
    with pytest.raises(BenchError, match=r"families/no_such_family\.py"):
        fn[call](c)


@pytest.mark.parametrize("rows", [None, 1])
def test_traffic_sets_the_reference_rows(rows, monkeypatch):
    seen = []

    def train_reference(c, opt, params, batches, precision, row_block):
        seen.append(row_block)
        return {}

    monkeypatch.setattr(reference, "train_reference", train_reference)
    cell = smoke_cell("yi-6b-2L.train-full")
    t = dict(cell.traffic)
    if rows is not None:
        t["reference_rows"] = rows
    reference_readings(cell.config, t, SEED)
    assert seen == [2 if rows is None else rows]
