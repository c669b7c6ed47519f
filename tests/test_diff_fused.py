"""The host diff's one pass per chunk: SHA-256 and the sidecar fingerprint
from the same zero-copy slice of the payload, inline or on the hash pool.

Expected values come from the whole-tensor paths the diff must agree with
bit for bit: ``chunk_tensor`` (content addresses) and
``fingerprint_chunks_ref`` (the fingerprint kernel's numpy oracle)."""
import ml_dtypes
import numpy as np
import pytest

from repro import obs
from repro.ckpt import CheckpointManager, CheckpointPolicy
from repro.core import (Instruction, LayerStore, TensorRecord, chunk_tensor,
                        chunker, diff_layer_host, fingerprint_chunks_ref)
from repro.core.diff import LayerDiff, _host_compare_tensor


def _array(dtype, shape, seed=0):
    rng = np.random.default_rng(seed)
    n = int(np.prod(shape)) if shape else 1
    if dtype == "bool":
        a = rng.integers(0, 2, n).astype(bool)
    elif dtype in ("int32", "int64", "uint8"):
        a = rng.integers(0, 250, n).astype(dtype)
    else:
        a = rng.standard_normal(n).astype(
            ml_dtypes.bfloat16 if dtype == "bfloat16" else dtype)
    return a.reshape(shape)


def _expected(arr, cb):
    """[(sha256, fp row)] per chunk, from the whole-tensor paths."""
    rec, _ = chunk_tensor("x", arr, cb)
    fp = fingerprint_chunks_ref(arr, cb)
    return [(h, (int(fp[i, 0]), int(fp[i, 1])))
            for i, h in enumerate(rec.chunks)]


CASES = [
    # (dtype, shape, chunk_bytes); 70,000 f32 = 280,000 B is above the
    # pool's size threshold, the rest run inline
    ("float32", (70_000,), 4096),
    ("bfloat16", (64, 300), 4096),
    ("float16", (1000,), 512),
    ("int32", (999,), 512),           # partial last chunk
    ("int64", (3, 170), 512),
    ("float64", (257,), 256),
    ("bool", (5000,), 1024),
    ("uint8", (4097,), 1024),
    ("float32", (), 512),             # 0-d leaf
    ("float32", (0, 4), 512),         # empty leaf: one chunk of one lane
    ("int32", (1,), 1 << 20),         # the one-element opt/__step__ leaf
    ("float32", (1000,), 1000),       # misaligned: no sidecar
    ("bfloat16", (100_001,), 65_536),  # pooled, partial last chunk
]


@pytest.mark.parametrize("dtype,shape,cb", CASES)
def test_fused_chunk_pass_matches_whole_tensor_paths(dtype, shape, cb):
    arr = _array(dtype, shape)
    want = _expected(arr, cb)
    # a record no chunk of ``arr`` matches, so every chunk is an edit
    rec = TensorRecord("x", tuple(shape), dtype, cb, ("",) * len(want),
                       fp=((0, 0),) * len(want))
    diff = LayerDiff("l")
    _host_compare_tensor(rec, "x", arr, diff)
    aligned = cb % arr.dtype.itemsize == 0
    assert [e.index for e in diff.edits] == list(range(len(want)))
    assert [e.new_hash for e in diff.edits] == [h for h, _ in want]
    assert [e.fp for e in diff.edits] == \
        [fp if aligned else None for _, fp in want]
    assert diff.fp_chunks == (len(want) if aligned else 0)
    assert diff.chunks_compared == len(want)
    assert diff.bytes_hashed == arr.nbytes


def _layer(store, payload):
    ins = [Instruction("FROM", "b", "config"),
           Instruction("COPY", "data", "content")]
    m, _, _ = store.build_image("m", "v1", ins, {"data": lambda: payload})
    return store.read_layer(m.layer_ids[1])


def _payload(seed):
    return {"w": _array("float32", (70_000,), seed),
            "e": _array("bfloat16", (3000,), seed),
            "s": _array("int32", (1,), seed)}


def test_diff_layer_host_edits_match_plain_reference(tmp_path):
    cb = 4096
    old = _payload(0)
    store = LayerStore(str(tmp_path / "s"), chunk_bytes=cb)
    layer = _layer(store, old)
    assert all(r.fp is not None for r in layer.records)
    new = {k: v.copy() for k, v in old.items()}
    new["w"][[0, 5000, 69_999]] += 1.0
    new["e"][1500] += 1
    new["s"][0] += 1
    want = []
    for rec in layer.records:
        new_rec, pairs = chunk_tensor(rec.name, new[rec.name], cb)
        fp = fingerprint_chunks_ref(new[rec.name], cb)
        for i, (h, piece) in enumerate(pairs):
            if h != rec.chunks[i]:
                want.append((rec.name, i, h, (int(fp[i, 0]), int(fp[i, 1])),
                             bytes(piece)))
    got = diff_layer_host(layer, new)
    assert [(e.tensor, e.index, e.new_hash, e.fp, bytes(e.data))
            for e in got.edits] == want
    assert len(want) == 5 and got.fp_chunks == 5


def test_edit_data_is_a_view_of_the_payload(tmp_path):
    old = _payload(0)
    store = LayerStore(str(tmp_path / "s"), chunk_bytes=4096)
    layer = _layer(store, old)
    new = _payload(1)
    diff = diff_layer_host(layer, new)
    assert diff.edits
    for e in diff.edits:
        assert isinstance(e.data, memoryview) and e.data.format == "B"
        assert np.shares_memory(np.frombuffer(e.data, np.uint8),
                                new[e.tensor])


@pytest.mark.parametrize("n", [1000, 200_000], ids=["inline", "pooled"])
def test_incremental_save_restores_bit_identical(tmp_path, monkeypatch, n):
    # a one-core machine has no pool width to fan out over: pretend two so
    # the pooled path still runs through the executor
    monkeypatch.setattr(chunker, "_HASH_POOL_WORKERS",
                        max(2, chunker._HASH_POOL_WORKERS))
    cb = 4096
    mgr = CheckpointManager(str(tmp_path), "toy", CheckpointPolicy(
        every_steps=1, keep=3, async_write=False, chunk_bytes=cb))
    params = {"w": _array("float32", (n,), 0),
              "e": _array("bfloat16", (n // 2,), 0)}
    opt = {"m": _array("float32", (n,), 1)}
    mgr.save(1, params, opt)
    e = params["e"].copy()
    e[::7] += 1
    params = {"w": params["w"] + 1, "e": e}
    mgr.save(2, params, opt)
    assert mgr.last_report.layers_injected >= 1
    diff = obs.records("ckpt.diff")[-1]
    assert diff.ids["step"] == 2
    assert (diff.counts["hash_workers"] > 1) == (n > 100_000)
    p, o, step = mgr.restore()
    assert step == 2
    for k, v in params.items():
        assert p[k].dtype == v.dtype
        assert np.asarray(p[k]).tobytes() == v.tobytes()
    assert np.asarray(o["m"]).tobytes() == opt["m"].tobytes()
    # the injected records hold the same content addresses and sidecar a
    # full build of this state computes
    manifest, _ = mgr.store.read_image(mgr.image, mgr.tag_of(2))
    seen = 0
    for lid in manifest.layer_ids:
        for rec in mgr.store.read_layer(lid).records:
            if not rec.name.startswith("params/"):
                continue
            arr = params[rec.name[len("params/"):]]
            want = _expected(arr, cb)
            assert rec.chunks == tuple(h for h, _ in want)
            assert rec.fp == tuple(fp for _, fp in want)
            for h, piece in chunk_tensor("x", arr, cb)[1]:
                assert mgr.store.read_blob(h) == bytes(piece)
            seen += 1
    assert seen == 2
