"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes the full results
(means, stds, speedups, Z-test P-values) to benchmarks/results/*.json.

    PYTHONPATH=src python -m benchmarks.run [--trials 30] [--quick]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import tempfile
import time

import numpy as np

RESULTS = os.path.join(os.path.dirname(__file__), "results")

# Adapted H0 thresholds for the paper's Table II hypothesis test
# (null: speedup <= H0). The paper's absolute H0s (100 / 105000 / 20 / 0.7)
# embed docker-daemon and network-install costs that do not exist here;
# these test the same ORDERING claims on our measured regime.
H0 = {"s1_python_tiny": 1.5, "s2_python_conda": 50.0,
      "s3_java_precompiled": 1.0, "s4_java_compile_inside": 0.7}


def z_test_p(speedups: np.ndarray, h0: float) -> float:
    """P(observed | mu <= h0) one-sided Z (paper eq. 2)."""
    n = len(speedups)
    mu = float(speedups.mean())
    s = float(speedups.std(ddof=1)) or 1e-12
    z = (mu - h0) / (s / math.sqrt(n))
    return 0.5 * math.erfc(z / math.sqrt(2))


def bench_scenarios(trials: int, chunk_bytes: int = 1 << 18) -> dict:
    """Fig. 5 (rebuild time mean±std), Fig. 6 (times faster), Table II."""
    from .scenarios import SCENARIOS, run_scenario
    out = {}
    root = tempfile.mkdtemp(prefix="lc_bench_")
    try:
        for mk in SCENARIOS:
            sc = mk(chunk_bytes)
            base, inj = run_scenario(sc, root, trials, chunk_bytes)
            speed = base / inj
            out[sc.name] = {
                "baseline_mean_s": float(base.mean()),
                "baseline_std_s": float(base.std(ddof=1)),
                "inject_mean_s": float(inj.mean()),
                "inject_std_s": float(inj.std(ddof=1)),
                "speedup_mean": float(speed.mean()),
                "speedup_std": float(speed.std(ddof=1)),
                "speedup_min": float(speed.min()),
                "speedup_max": float(speed.max()),
                "H0": H0[sc.name],
                "P": z_test_p(speed, H0[sc.name]),
                "trials": trials,
            }
            print(f"{sc.name}_baseline,{base.mean() * 1e6:.1f},")
            print(f"{sc.name}_inject,{inj.mean() * 1e6:.1f},"
                  f"speedup={speed.mean():.1f}x P={out[sc.name]['P']:.2e}")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_decompose(trials: int) -> dict:
    """Paper §III-A: explicit (docker save tar) vs implicit (in-place)."""
    from repro.core import Instruction, LayerStore
    from .scenarios import _gen
    out = {}
    root = tempfile.mkdtemp(prefix="lc_decomp_")
    try:
        store = LayerStore(os.path.join(root, "s"), chunk_bytes=1 << 18)
        ins = [Instruction("FROM", "base", "config"),
               Instruction("COPY", "payload", "content")]
        payload = {"data": _gen(7, 64 << 20)}
        m, _, _ = store.build_image("app", "v1", ins,
                                    {"payload": lambda: payload})
        explicit, implicit = [], []
        for _ in range(trials):
            t0 = time.perf_counter()
            bundle = store.export_image("app", "v1")      # docker save
            store2 = LayerStore(os.path.join(root, "tmp"),
                                chunk_bytes=1 << 18)
            store2.import_image(bundle)
            lay = store2.read_layer(m.layer_ids[1])
            _ = lay.records[0].chunks[0]
            explicit.append(time.perf_counter() - t0)
            shutil.rmtree(os.path.join(root, "tmp"))
            t0 = time.perf_counter()
            lay = store.open_layer_inplace(m.layer_ids[1])
            _ = lay.records[0].chunks[0]
            implicit.append(time.perf_counter() - t0)
        e, i = np.asarray(explicit), np.asarray(implicit)
        out = {"explicit_mean_s": float(e.mean()),
               "implicit_mean_s": float(i.mean()),
               "speedup": float(e.mean() / i.mean()), "trials": trials}
        print(f"decompose_explicit,{e.mean() * 1e6:.1f},")
        print(f"decompose_implicit,{i.mean() * 1e6:.1f},"
              f"speedup={out['speedup']:.0f}x")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_fallthrough(trials: int) -> dict:
    """Fig. 2 anatomy: rebuild cost vs depth of the edited layer."""
    from repro.core import Instruction, LayerStore, inject_payload_update
    from .scenarios import _edit_chunks, _gen
    out = {}
    root = tempfile.mkdtemp(prefix="lc_ft_")
    n_layers = 6
    try:
        for edit_at in (1, n_layers // 2, n_layers - 1):
            ins = [Instruction("FROM", "base", "config")]
            payloads = {}
            for i in range(n_layers):
                key = f"layer{i}"
                ins.append(Instruction("RUN" if i % 2 else "COPY", key,
                                       "content"))
                payloads[key] = _gen(100 + i, 8 << 20)
            bt, it = [], []
            for tr in range(trials):
                store = LayerStore(os.path.join(root, f"{edit_at}_{tr}"),
                                   chunk_bytes=1 << 18)
                prov = {k: (lambda v=v: {"data": v})
                        for k, v in payloads.items()}
                store.build_image("app", "v1", ins, prov)
                edited = dict(payloads)
                key = f"layer{edit_at}"
                edited[key] = _edit_chunks(payloads[key], 1, 1 << 18)
                prov2 = {k: (lambda v=v: {"data": v})
                         for k, v in edited.items()}
                t0 = time.perf_counter()
                store.build_image("app", "v2", ins, prov2,
                                  parent=("app", "v1"))
                bt.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                inject_payload_update(store, "app", "v1", "v2i",
                                      {key: {"data": edited[key]}})
                it.append(time.perf_counter() - t0)
                shutil.rmtree(os.path.join(root, f"{edit_at}_{tr}"))
            b, i2 = np.asarray(bt), np.asarray(it)
            out[f"edit_at_{edit_at}"] = {
                "baseline_mean_s": float(b.mean()),
                "inject_mean_s": float(i2.mean()),
                "speedup": float((b / i2).mean())}
            print(f"fallthrough_depth{edit_at}_baseline,"
                  f"{b.mean() * 1e6:.1f},")
            print(f"fallthrough_depth{edit_at}_inject,{i2.mean() * 1e6:.1f},"
                  f"speedup={(b / i2).mean():.1f}x")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_ckpt_cadence(trials: int) -> dict:
    """Framework integration: full vs incremental checkpoint save cost for
    an adapter-style update on a real model state (the deployment story)."""
    import jax
    import jax.numpy as jnp
    from repro.ckpt import CheckpointManager, CheckpointPolicy
    from repro.configs import get_smoke_config
    from repro.models import init_params
    out = {}
    cfg = get_smoke_config("yi-6b").replace(
        n_layers=4, d_model=256, d_ff=1024, vocab=8192)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = {"step": jnp.int32(0)}
    root = tempfile.mkdtemp(prefix="lc_ckpt_")
    try:
        for mode in ("full", "incremental"):
            times = []
            mgr = CheckpointManager(
                os.path.join(root, mode), cfg.name,
                CheckpointPolicy(incremental=(mode == "incremental"),
                                 async_write=False, chunk_bytes=1 << 18))
            mgr.save(0, params, opt)
            p2 = jax.tree.map(lambda a: a, params)
            for t in range(trials):
                p2 = dict(p2)
                p2["final_norm"] = p2["final_norm"] * (1.0 + 1e-4)
                t0 = time.perf_counter()
                mgr.save(t + 1, p2, opt)
                times.append(time.perf_counter() - t0)
            out[mode] = {"mean_s": float(np.mean(times)),
                         "std_s": float(np.std(times))}
            print(f"ckpt_{mode},{np.mean(times) * 1e6:.1f},")
        out["speedup"] = out["full"]["mean_s"] / out["incremental"]["mean_s"]
        print(f"ckpt_speedup,,{out['speedup']:.1f}x")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_incremental_save(trials: int) -> dict:
    """The fused save pipeline (this repo's perf tentpole): incremental
    checkpoint save on a 100+-leaf state, seed per-leaf fingerprint
    dispatch vs the packed single-dispatch + batch-durability pipeline.
    Also records a bit-identity sweep of the packed fingerprints against
    the numpy oracle. (main() snapshots this to BENCH_incremental_save.json
    at the repo root under --update-baseline.)
    """
    import jax.numpy as jnp
    from repro.ckpt import CheckpointManager, CheckpointPolicy
    from repro.core import fingerprint_chunks_ref, fingerprint_tree_packed
    from .scenarios import many_leaf_tree

    n_leaves, leaf_elems, chunk_bytes = 512, 4096, 1 << 14
    # device-resident state, as in real training (the whole point: only
    # fingerprints + changed ranges should cross the host link)
    base_tree = {k: jnp.asarray(v) for k, v in
                 many_leaf_tree(n_leaves=n_leaves,
                                leaf_elems=leaf_elems).items()}
    opt = {"step": jnp.int32(0)}
    out = {"n_leaves": n_leaves, "leaf_bytes": leaf_elems * 4,
           "chunk_bytes": chunk_bytes, "trials": trials}
    root = tempfile.mkdtemp(prefix="lc_incsave_")
    try:
        modes = {
            "perleaf_dispatch": dict(packed_fingerprints=False,
                                     durability="full"),
            "packed_pipeline": dict(packed_fingerprints=True,
                                    durability="batch"),
        }
        for mode, pol in modes.items():
            mgr = CheckpointManager(
                os.path.join(root, mode), "bench",
                CheckpointPolicy(incremental=True, use_fingerprints=True,
                                 async_write=False, chunk_bytes=chunk_bytes,
                                 **pol))
            params = {"blocks": dict(base_tree)}
            mgr.save(0, params, opt)
            # warm the jit caches (packed trace covers the full tree shape)
            params["blocks"] = dict(params["blocks"])
            params["blocks"]["l000"] = params["blocks"]["l000"] + 1e-3
            mgr.save(1, params, opt)
            times = []
            rep = None
            for t in range(trials):
                idx = t % n_leaves
                params["blocks"] = dict(params["blocks"])
                params["blocks"][f"l{idx:03d}"] = \
                    params["blocks"][f"l{idx:03d}"] + 1e-3
                t0 = time.perf_counter()
                rep = mgr.save(t + 2, params, opt)
                times.append(time.perf_counter() - t0)
            times = np.asarray(times)
            out[mode] = {
                "mean_s": float(times.mean()),
                "median_s": float(np.median(times)),
                "std_s": float(times.std(ddof=1)) if trials > 1 else 0.0,
                "min_s": float(times.min()),
                "last_report": {
                    "bytes_d2h": rep.bytes_d2h,
                    "chunks_prefiltered": rep.chunks_prefiltered,
                    "fsyncs": rep.fsyncs,
                    "bytes_serialized": rep.bytes_serialized,
                    "chunks_written": rep.chunks_written,
                },
            }
            print(f"incsave_{mode},{np.median(times) * 1e6:.1f},")
        # median-based headline: robust to fsync-latency outlier trials on
        # shared boxes (mean and min are recorded alongside)
        out["speedup"] = (out["perleaf_dispatch"]["median_s"] /
                          out["packed_pipeline"]["median_s"])
        out["speedup_mean"] = (out["perleaf_dispatch"]["mean_s"] /
                               out["packed_pipeline"]["mean_s"])
        print(f"incsave_speedup,,{out['speedup']:.2f}x")

        # packed fingerprints must be bit-identical to the numpy oracle
        import ml_dtypes
        rng = np.random.default_rng(3)
        sweep = {
            "float32": rng.standard_normal(5000).astype(np.float32),
            "bfloat16": rng.standard_normal(1025).astype(ml_dtypes.bfloat16),
            "int8": rng.integers(-100, 100, 3000).astype(np.int8),
            "bool": rng.standard_normal(1000) > 0,
            "int64": rng.integers(-5, 5, 300).astype(np.int64),
        }
        packed = fingerprint_tree_packed(sweep, 1024)
        out["fingerprint_bit_identical"] = {
            k: bool(np.array_equal(packed[k],
                                   fingerprint_chunks_ref(np.asarray(v),
                                                          1024)))
            for k, v in sweep.items()}
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_multilayer_inject(trials: int) -> dict:
    """The multi-layer transactional unit (this repo's CI tentpole): k
    changed content layers saved as ONE batched injection
    (``inject_image_multi``: one re-key walk + one manifest commit) vs a
    CONSTRUCTED per-layer protocol — one single-layer injection
    transaction per changed layer (k walks, k commits). Both arms run
    under identical batch durability, so the ratio isolates the
    transactional-unit cost (walks, re-keys, commits), not fsync mode.
    Note the baseline is the design alternative a per-layer transactional
    unit would cost, not the seed save path (which already batched diffs
    into one call); edits are one chunk per layer, so wall time IS the
    metadata path. BuildReport counters prove the 1-vs-k walk/commit
    claim.
    """
    from repro.core import (Instruction, LayerStore, diff_image,
                            inject_image_multi)
    from .scenarios import _edit_chunks, _gen

    n_layers, chunk_bytes, layer_bytes = 8, 1 << 16, 2 << 20
    ins = [Instruction("FROM", "base", "config")]
    payloads = {}
    for i in range(n_layers):
        key = f"layer{i}"
        ins.append(Instruction("COPY", key, "content"))
        payloads[key] = _gen(300 + i, layer_bytes)
    ins.append(Instruction("RUN", "setup", "content"))   # independent tail
    payloads["setup"] = _gen(299, layer_bytes)
    ins.append(Instruction("CMD", "serve", "config"))

    def diffs_for(store, tag, keys, edited):
        m, _ = store.read_image("app", tag)
        layers = [store.read_layer(lid) for lid in m.layer_ids]
        return diff_image(layers, {k: {"data": edited[k]} for k in keys})

    out = {"n_layers": n_layers, "chunk_bytes": chunk_bytes,
           "layer_bytes": layer_bytes, "trials": trials}
    root = tempfile.mkdtemp(prefix="lc_multi_")
    try:
        for k in (1, 2, 4, 8):
            keys = [f"layer{i}" for i in range(k)]
            bt, st = [], []
            b_rep = None
            s_counters = {"rekey_walks": 0, "manifest_commits": 0,
                          "layers_rekeyed": 0, "fsyncs": 0}
            for tr in range(trials):
                edited = {key: _edit_chunks(payloads[key], 1, chunk_bytes,
                                            seed=tr + 1) for key in keys}
                store = LayerStore(os.path.join(root, f"b{k}_{tr}"),
                                   chunk_bytes=chunk_bytes)
                prov = {key: (lambda v=v: {"data": v})
                        for key, v in payloads.items()}
                store.build_image("app", "v1", ins, prov)
                diffs = diffs_for(store, "v1", keys, edited)
                t0 = time.perf_counter()
                _, _, b_rep = inject_image_multi(store, "app", "v1", "v2",
                                                 diffs)
                bt.append(time.perf_counter() - t0)
                shutil.rmtree(os.path.join(root, f"b{k}_{tr}"))

                store = LayerStore(os.path.join(root, f"s{k}_{tr}"),
                                   chunk_bytes=chunk_bytes)
                store.build_image("app", "v1", ins, prov)
                tag, elapsed = "v1", 0.0
                for i, key in enumerate(keys):
                    diffs = diffs_for(store, tag, [key], edited)
                    next_tag = f"v2_{i}"
                    t0 = time.perf_counter()
                    _, _, r = inject_image_multi(store, "app", tag,
                                                 next_tag, diffs,
                                                 durability="batch")
                    elapsed += time.perf_counter() - t0
                    for c in s_counters:
                        s_counters[c] += getattr(r, c)
                    tag = next_tag
                st.append(elapsed)
                shutil.rmtree(os.path.join(root, f"s{k}_{tr}"))
            b, s = np.asarray(bt), np.asarray(st)
            out[f"k{k}"] = {
                "batched": {
                    "median_s": float(np.median(b)),
                    "mean_s": float(b.mean()),
                    "min_s": float(b.min()),
                    "rekey_walks": b_rep.rekey_walks,
                    "manifest_commits": b_rep.manifest_commits,
                    "layers_injected": b_rep.layers_injected,
                    "layers_rekeyed": b_rep.layers_rekeyed,
                    "fsyncs": b_rep.fsyncs,
                },
                "sequential": {
                    "median_s": float(np.median(s)),
                    "mean_s": float(s.mean()),
                    "min_s": float(s.min()),
                    **{c: v // trials for c, v in s_counters.items()},
                },
                "speedup_wall": float(np.median(s) / np.median(b)),
            }
            out[f"k{k}"]["metadata_op_ratio"] = (
                (out[f"k{k}"]["sequential"]["layers_rekeyed"]
                 + out[f"k{k}"]["sequential"]["manifest_commits"]) /
                max(out[f"k{k}"]["batched"]["layers_rekeyed"]
                    + out[f"k{k}"]["batched"]["manifest_commits"], 1))
            print(f"multiinject_k{k}_batched,"
                  f"{np.median(b) * 1e6:.1f},walks={b_rep.rekey_walks} "
                  f"commits={b_rep.manifest_commits}")
            print(f"multiinject_k{k}_sequential,{np.median(s) * 1e6:.1f},"
                  f"speedup={out[f'k{k}']['speedup_wall']:.2f}x")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_push_delta(trials: int) -> dict:
    """§III.C redeployment (this repo's delta-replication tentpole): push a
    freshly-injected 512-leaf checkpoint-style image (8 content layers x 64
    leaves) to a remote that already holds the previous version. Seed
    ``push`` walks every layer, rewrites every descriptor and deep-verifies
    the WHOLE image at the destination (O(image)); ``push_delta``
    negotiates the have-set in batched set-difference exchanges, streams
    only the changed chunks over the pipelined transfer and verifies
    incrementally (O(changed bytes)). k = how many of the image's content
    layers changed (the last k — the checkpoint save shape, where every
    param layer is touched; deeper-prefix edits only add re-keyed
    descriptors, still O(#layers) metadata). Gated claims, recorded per k:
    wall speedup, wire amplification (bytes_sent / changed-chunk bytes,
    must stay within 1.25x), the remote deep-verified ONLY the k new
    layers, and an untimed independent ``verify_image(deep=True)`` at the
    remote passes afterwards.
    """
    from repro.core import (Instruction, LayerStore, diff_image,
                            inject_image_multi, push, push_delta)
    from .scenarios import _edit_chunks, _gen

    n_layers, leaves_per_layer, edits_per_layer = 8, 64, 2
    leaf_bytes = chunk_bytes = 128 << 10
    ins = [Instruction("FROM", "base", "config")]
    payloads = {}
    for i in range(n_layers):
        key = f"layer{i}"
        ins.append(Instruction("COPY", key, "content"))
        payloads[key] = {
            f"l{j:03d}": _gen(1000 + i * leaves_per_layer + j, leaf_bytes)
            for j in range(leaves_per_layer)}
    ins.append(Instruction("CMD", "serve", "config"))

    out = {"n_layers": n_layers, "leaves": n_layers * leaves_per_layer,
           "leaf_bytes": leaf_bytes, "chunk_bytes": chunk_bytes,
           "trials": trials}
    root = tempfile.mkdtemp(prefix="lc_push_")
    try:
        for k in (1, 2, 4, 8):
            keys = [f"layer{i}" for i in range(n_layers - k, n_layers)]
            # registry stores: no build-cache fingerprint sidecar (that is
            # a builder concern; a serving registry never runs COPY checks)
            store = LayerStore(os.path.join(root, f"src{k}"),
                               chunk_bytes=chunk_bytes,
                               record_fingerprints=False)
            current = {key: dict(tree) for key, tree in payloads.items()}
            prov = {key: (lambda v=v: v) for key, v in current.items()}
            store.build_image("app", "v1", ins, prov)
            remote_seed = LayerStore(os.path.join(root, f"rs{k}"),
                                     chunk_bytes=chunk_bytes,
                                     record_fingerprints=False)
            remote_delta = LayerStore(os.path.join(root, f"rd{k}"),
                                      chunk_bytes=chunk_bytes,
                                      record_fingerprints=False)
            push(store, remote_seed, "app", "v1")
            push_delta(store, remote_delta, "app", "v1")

            seed_t, delta_t, amp = [], [], []
            s_stats = d_stats = None
            tag, changed_bytes = "v1", 0
            for tr in range(trials):
                # a few fresh chunk edits per changed layer, applied on top
                # of the running state (never reverting an earlier edit)
                for key in keys:
                    current[key] = dict(current[key])
                    for e in range(edits_per_layer):
                        leaf = f"l{(tr * edits_per_layer + e) % leaves_per_layer:03d}"
                        current[key][leaf] = _edit_chunks(
                            current[key][leaf], 1, chunk_bytes, seed=tr + 1)
                m, _ = store.read_image("app", tag)
                layers = [store.read_layer(lid) for lid in m.layer_ids]
                diffs = diff_image(layers,
                                   {key: current[key] for key in keys})
                new_tag = f"t{tr + 1}"
                inject_image_multi(store, "app", tag, new_tag, diffs)
                changed_bytes = sum(len(e.data) for d in diffs.values()
                                    for e in d.edits)
                tag = new_tag

                t0 = time.perf_counter()
                s_stats = push(store, remote_seed, "app", tag)
                seed_t.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                d_stats = push_delta(store, remote_delta, "app", tag)
                delta_t.append(time.perf_counter() - t0)
                amp.append(d_stats.bytes_sent / max(changed_bytes, 1))
            s, d = np.asarray(seed_t), np.asarray(delta_t)
            amp_median = float(np.median(np.asarray(amp)))
            # the acceptance checks, run INDEPENDENTLY of the push path
            remote_clean = remote_delta.verify_image("app", tag,
                                                     deep=True) == []
            out[f"k{k}"] = {
                "changed_bytes": changed_bytes,
                "seed": {
                    "median_s": float(np.median(s)),
                    "mean_s": float(s.mean()),
                    "bytes_sent": s_stats.bytes_sent,
                    "bytes_deduped": s_stats.bytes_deduped,
                    "layers_deep_verified": s_stats.layers_deep_verified,
                },
                "delta": {
                    "median_s": float(np.median(d)),
                    "mean_s": float(d.mean()),
                    "bytes_sent": d_stats.bytes_sent,
                    "bytes_payload": d_stats.bytes_payload,
                    "bytes_meta": d_stats.bytes_meta,
                    "bytes_deduped": d_stats.bytes_deduped,
                    "layers_deep_verified": d_stats.layers_deep_verified,
                    "layers_rekey_verified": d_stats.layers_rekey_verified,
                    "blobs_hashed_remote": d_stats.blobs_hashed_remote,
                    "wire_amplification": amp_median,
                    "within_budget": bool(amp_median <= 1.25),
                    "remote_deep_verify_clean": bool(remote_clean),
                },
                "speedup_wall": float(np.median(s) / np.median(d)),
            }
            print(f"push_k{k}_seed,{np.median(s) * 1e6:.1f},"
                  f"deep={s_stats.layers_deep_verified} "
                  f"bytes={s_stats.bytes_sent}")
            print(f"push_k{k}_delta,{np.median(d) * 1e6:.1f},"
                  f"speedup={out[f'k{k}']['speedup_wall']:.2f}x "
                  f"amp={amp_median:.3f} "
                  f"deep={d_stats.layers_deep_verified}")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_fanout(trials: int) -> dict:
    """Fan-out replication + sparse serving refresh (the fleet topology):
    one training source feeding N serving replicas with k=8 changed layers
    of the 512-leaf image (8 content layers x 64 leaves) per save. Gated
    claims per N in {2, 4}: ONE negotiation round; the source reads each
    changed blob from its store exactly once regardless of N —
    counter-proved against an instrumented store, and exactly N x fewer
    reads than N sequential ``push_delta`` calls; per-replica wire stays
    within the 1.25x changed-bytes budget; and at the consumer,
    ``Engine.refresh`` device-puts ONLY the changed leaves after a sparse
    ``changed_tensor_paths`` plan, bit-identical to a full reload.
    """
    from repro.ckpt.manager import flatten_tree, unflatten_tree
    from repro.configs import get_smoke_config
    from repro.core import (Instruction, LayerStore, diff_image,
                            inject_image_multi, push_delta,
                            replicate_fanout)
    from repro.serve import Engine, changed_tensor_paths
    from .scenarios import _edit_chunks, _gen

    n_layers, leaves_per_layer, edits_per_layer = 8, 64, 2
    leaf_bytes = chunk_bytes = 128 << 10
    ins = [Instruction("FROM", "base", "config")]
    payloads = {}
    for i in range(n_layers):
        key = f"layer{i}"
        ins.append(Instruction("COPY", key, "content"))
        payloads[key] = {
            f"L{i}/l{j:03d}": _gen(2000 + i * leaves_per_layer + j,
                                   leaf_bytes)
            for j in range(leaves_per_layer)}
    ins.append(Instruction("CMD", "serve", "config"))
    keys = list(payloads)                     # ALL k=8 content layers move

    out = {"n_layers": n_layers, "leaves": n_layers * leaves_per_layer,
           "leaf_bytes": leaf_bytes, "chunk_bytes": chunk_bytes,
           "trials": trials}
    root = tempfile.mkdtemp(prefix="lc_fan_")
    try:
        for N in (2, 4):
            src = LayerStore(os.path.join(root, f"src{N}"),
                             chunk_bytes=chunk_bytes,
                             record_fingerprints=False)
            current = {key: dict(tree) for key, tree in payloads.items()}
            prov = {key: (lambda v=v: v) for key, v in current.items()}
            src.build_image("app", "v1", ins, prov)
            fan_reps = [LayerStore(os.path.join(root, f"f{N}_{i}"),
                                   chunk_bytes=chunk_bytes,
                                   record_fingerprints=False)
                        for i in range(N)]
            seq_reps = [LayerStore(os.path.join(root, f"q{N}_{i}"),
                                   chunk_bytes=chunk_bytes,
                                   record_fingerprints=False)
                        for i in range(N)]
            replicate_fanout(src, fan_reps, "app", "v1")
            for r in seq_reps:
                push_delta(src, r, "app", "v1")

            fan_t, seq_t, amp, ratio = [], [], [], []
            rounds_ok = reads_ok = True
            changed_blobs = changed_bytes = 0
            tag = "v1"
            for tr in range(trials):
                for key in keys:
                    current[key] = dict(current[key])
                    for e in range(edits_per_layer):
                        leaf = [k for k in current[key]][
                            (tr * edits_per_layer + e) % leaves_per_layer]
                        current[key][leaf] = _edit_chunks(
                            current[key][leaf], 1, chunk_bytes, seed=tr + 1)
                m, _ = src.read_image("app", tag)
                layers = [src.read_layer(lid) for lid in m.layer_ids]
                diffs = diff_image(layers,
                                   {key: current[key] for key in keys})
                new_tag = f"t{tr + 1}"
                inject_image_multi(src, "app", tag, new_tag, diffs)
                changed = {e.new_hash for d in diffs.values()
                           for e in d.edits}
                changed_blobs = len(changed)
                changed_bytes = sum(len(e.data) for d in diffs.values()
                                    for e in d.edits)
                prev_tag, tag = tag, new_tag

                # instrumented source: count ACTUAL blob reads during the
                # fan-out (the exactly-once claim is counter-proved, not
                # taken from FanoutStats). The wrapper runs on hash-pool
                # threads — list.append is the GIL-atomic counter.
                reads = []
                orig_read = src.read_blob
                src.read_blob = lambda h: (reads.append(h), orig_read(h))[1]
                t0 = time.perf_counter()
                fan = replicate_fanout(src, fan_reps, "app", tag)
                fan_t.append(time.perf_counter() - t0)
                del src.read_blob
                assert fan.ok, [r.error for r in fan.replicas]
                rounds_ok &= fan.negotiation_rounds == 1
                reads_ok &= (fan.source_blob_reads == changed_blobs ==
                             len(reads))
                amp.append(max(r.stats.bytes_sent for r in fan.replicas)
                           / max(changed_bytes, 1))

                reads = []
                src.read_blob = lambda h: (reads.append(h), orig_read(h))[1]
                t0 = time.perf_counter()
                for r in seq_reps:
                    push_delta(src, r, "app", tag)
                seq_t.append(time.perf_counter() - t0)
                del src.read_blob
                ratio.append(len(reads) / max(changed_blobs, 1))

            # consumer side: sparse refresh at one replica vs full reload.
            # Engine setup and the previous-revision tree are built OUTSIDE
            # the timed windows — each window times exactly one refresh
            # path: store assembly + unflatten + Engine.refresh.
            rep = fan_reps[0]
            changed_paths = changed_tensor_paths(rep, "app", prev_tag, tag)
            prev_tree = unflatten_tree(rep.load_image_payload("app",
                                                              prev_tag))
            eng = Engine(get_smoke_config("yi-6b"), prev_tree)
            t0 = time.perf_counter()
            full_flat = rep.load_image_payload("app", tag)
            eng.refresh(unflatten_tree(full_flat))
            full_s = time.perf_counter() - t0
            want = {k: v.copy() for k, v in full_flat.items()}
            eng.refresh(prev_tree)                          # rewind
            t0 = time.perf_counter()
            sparse_flat = rep.load_image_payload("app", tag,
                                                 names=changed_paths)
            n_put = eng.refresh(unflatten_tree(sparse_flat), changed_paths)
            partial_s = time.perf_counter() - t0

            live = flatten_tree(eng.params)
            identical = set(live) == set(want) and all(
                np.array_equal(np.asarray(live[p]), want[p]) for p in want)

            # worst replica of the worst trial — the budget is a per-push
            # guarantee, so the gate must see the maximum, not the median
            amp_max = float(np.max(np.asarray(amp)))
            f, s = np.asarray(fan_t), np.asarray(seq_t)
            out[f"N{N}"] = {
                "n_replicas": N,
                "changed_bytes": changed_bytes,
                "changed_blobs": changed_blobs,
                "negotiation_rounds": 1 if rounds_ok else -1,
                "source_reads_equal_changed": bool(reads_ok),
                "source_read_ratio_vs_sequential":
                    float(np.median(np.asarray(ratio))),
                "wire_amplification_max": amp_max,
                "within_budget": bool(amp_max <= 1.25),
                "fanout": {"median_s": float(np.median(f)),
                           "mean_s": float(f.mean())},
                "sequential": {"median_s": float(np.median(s)),
                               "mean_s": float(s.mean())},
                "speedup_wall": float(np.median(s) / np.median(f)),
                "refresh": {
                    "leaves_total": n_layers * leaves_per_layer,
                    "leaves_changed": len(changed_paths),
                    "refresh_leaves_partial": int(n_put),
                    "refresh_only_changed": bool(
                        n_put == len(changed_paths) ==
                        len(sparse_flat) < n_layers * leaves_per_layer),
                    "refresh_bit_identical": bool(identical),
                    "partial_s": partial_s,
                    "full_s": full_s,
                },
            }
            print(f"fanout_N{N},{np.median(f) * 1e6:.1f},"
                  f"rounds=1 reads={changed_blobs} amp={amp_max:.3f}")
            print(f"fanout_N{N}_sequential,{np.median(s) * 1e6:.1f},"
                  f"speedup={out[f'N{N}']['speedup_wall']:.2f}x "
                  f"read_ratio={out[f'N{N}']['source_read_ratio_vs_sequential']:.1f}")
            print(f"fanout_N{N}_refresh,{partial_s * 1e6:.1f},"
                  f"leaves={n_put}/{n_layers * leaves_per_layer} "
                  f"identical={identical}")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_relay(trials: int) -> dict:
    """Multi-hop relay replication (the edge-tier topology): one trainer
    feeding a relay that re-fans each k=8-changed-layer save of the
    512-leaf image to C edge children. Gated claims per C in {2, 4}, all
    counter-proved against instrumented stores:

    * the relay reads each changed blob from its PARENT exactly once
      (``FanoutStats.source_blob_reads`` == changed blobs == the
      instrumented count), and — with ``source="inflight"`` — forwards it
      to all C children straight from the wire buffer: ZERO local reads,
      no per-child re-read or re-hash, one negotiation round per tier;
    * when the children lag an already-current relay (the stale arm), each
      owed blob is read from the relay's local store exactly ONCE and
      broadcast — C sequential ``push_delta`` calls cost exactly C x the
      reads;
    * wire per hop (trainer->relay and relay->worst edge) stays within
      1.25x the changed bytes;
    * after the run, every edge's assembled payload is bit-identical to
      the trainer's save and every tier passes an independent deep verify.
    """
    import collections

    from repro.core import (Instruction, LayerStore, RelayNode, diff_image,
                            inject_image_multi, push_delta,
                            replicate_fanout)
    from .scenarios import _edit_chunks, _gen

    n_layers, leaves_per_layer, edits_per_layer = 8, 64, 2
    leaf_bytes = chunk_bytes = 128 << 10
    ins = [Instruction("FROM", "base", "config")]
    payloads = {}
    for i in range(n_layers):
        key = f"layer{i}"
        ins.append(Instruction("COPY", key, "content"))
        payloads[key] = {
            f"L{i}/l{j:03d}": _gen(3000 + i * leaves_per_layer + j,
                                   leaf_bytes)
            for j in range(leaves_per_layer)}
    ins.append(Instruction("CMD", "serve", "config"))
    keys = list(payloads)                     # ALL k=8 content layers move

    out = {"n_layers": n_layers, "leaves": n_layers * leaves_per_layer,
           "leaf_bytes": leaf_bytes, "chunk_bytes": chunk_bytes,
           "trials": trials}
    root = tempfile.mkdtemp(prefix="lc_relay_")

    def instrument(store):
        reads = []
        orig = store.read_blob
        store.read_blob = lambda h: (reads.append(h), orig(h))[1]
        return reads

    try:
        for C in (2, 4):
            src = LayerStore(os.path.join(root, f"src{C}"),
                             chunk_bytes=chunk_bytes,
                             record_fingerprints=False)
            current = {key: dict(tree) for key, tree in payloads.items()}
            prov = {key: (lambda v=v: v) for key, v in current.items()}
            src.build_image("app", "v1", ins, prov)
            # in-flight arm: trainer -> relay -> C edges
            relay = RelayNode(
                LayerStore(os.path.join(root, f"rl{C}"),
                           chunk_bytes=chunk_bytes,
                           record_fingerprints=False),
                children=[LayerStore(os.path.join(root, f"rl{C}e{i}"),
                                     chunk_bytes=chunk_bytes,
                                     record_fingerprints=False)
                          for i in range(C)],
                source="inflight")
            # stale arm: relay store warmed separately, children lag by one
            hot = LayerStore(os.path.join(root, f"hot{C}"),
                             chunk_bytes=chunk_bytes,
                             record_fingerprints=False)
            stale = RelayNode(hot,
                              children=[LayerStore(
                                  os.path.join(root, f"st{C}e{i}"),
                                  chunk_bytes=chunk_bytes,
                                  record_fingerprints=False)
                                  for i in range(C)])
            seq = [LayerStore(os.path.join(root, f"sq{C}e{i}"),
                              chunk_bytes=chunk_bytes,
                              record_fingerprints=False)
                   for i in range(C)]
            assert replicate_fanout(src, [relay], "app", "v1").deep_ok
            push_delta(src, hot, "app", "v1")
            assert replicate_fanout(src, [stale], "app", "v1").deep_ok
            for r in seq:
                push_delta(hot, r, "app", "v1")

            fan_t, seq_t = [], []
            relay_amp, edge_amp = [], []
            parent_reads_ok = inflight_zero_local = True
            stale_once_ok = rounds_ok = True
            stale_ratio = []
            changed_blobs = changed_bytes = 0
            tag = "v1"
            for tr in range(trials):
                for key in keys:
                    current[key] = dict(current[key])
                    for e in range(edits_per_layer):
                        leaf = [k for k in current[key]][
                            (tr * edits_per_layer + e) % leaves_per_layer]
                        current[key][leaf] = _edit_chunks(
                            current[key][leaf], 1, chunk_bytes, seed=tr + 1)
                m, _ = src.read_image("app", tag)
                layers = [src.read_layer(lid) for lid in m.layer_ids]
                diffs = diff_image(layers,
                                   {key: current[key] for key in keys})
                new_tag = f"t{tr + 1}"
                inject_image_multi(src, "app", tag, new_tag, diffs)
                changed_blobs = len({e.new_hash for d in diffs.values()
                                     for e in d.edits})
                changed_bytes = sum(len(e.data) for d in diffs.values()
                                    for e in d.edits)
                tag = new_tag

                # ---- in-flight: one parent read pass, zero local reads
                p_reads = instrument(src)
                l_reads = instrument(relay.store)
                t0 = time.perf_counter()
                fan = replicate_fanout(src, [relay], "app", tag)
                fan_t.append(time.perf_counter() - t0)
                del src.read_blob, relay.store.read_blob
                assert fan.deep_ok, [r.error for r in fan.replicas]
                parent_reads_ok &= (fan.source_blob_reads == changed_blobs
                                    == len(p_reads))
                inflight_zero_local &= (len(l_reads) == 0
                                        and relay.local_blob_reads == 0
                                        and relay.inflight_blobs
                                        == changed_blobs)
                rounds_ok &= (fan.negotiation_rounds == 1
                              and relay.fan.negotiation_rounds == 1)
                relay_amp.append(fan.replicas[0].stats.bytes_sent
                                 / max(changed_bytes, 1))
                edge_amp.append(max(r.stats.bytes_sent
                                    for r in relay.fan.replicas)
                                / max(changed_bytes, 1))

                # ---- stale children: ONE local read per blob for C edges,
                # vs C sequential pushes costing exactly C x the reads
                push_delta(src, hot, "app", tag)
                h_reads = instrument(hot)
                fan2 = replicate_fanout(src, [stale], "app", tag)
                del hot.read_blob
                assert fan2.deep_ok, [r.error for r in fan2.replicas]
                counts = collections.Counter(h_reads)
                stale_once_ok &= (stale.local_blob_reads == changed_blobs
                                  == len(counts)
                                  and max(counts.values()) == 1)
                h_reads = instrument(hot)
                t0 = time.perf_counter()
                for r in seq:
                    push_delta(hot, r, "app", tag)
                seq_t.append(time.perf_counter() - t0)
                del hot.read_blob
                stale_ratio.append(len(h_reads) / max(changed_blobs, 1))

            # edge payloads bit-identical to the trainer's final save
            want = src.load_image_payload("app", tag)
            identical = True
            for child in relay.children + stale.children:
                got = child.store.load_image_payload("app", tag)
                identical &= set(got) == set(want) and all(
                    np.array_equal(got[p], want[p]) for p in want)
                identical &= child.store.verify_image("app", tag,
                                                      deep=True) == []

            f, s = np.asarray(fan_t), np.asarray(seq_t)
            out[f"C{C}"] = {
                "n_children": C,
                "changed_bytes": changed_bytes,
                "changed_blobs": changed_blobs,
                "parent_reads_equal_changed": bool(parent_reads_ok),
                "inflight_zero_local_reads": bool(inflight_zero_local),
                "one_round_per_tier": bool(rounds_ok),
                "stale_one_local_read_per_blob": bool(stale_once_ok),
                "stale_read_ratio_vs_sequential":
                    float(np.median(np.asarray(stale_ratio))),
                # the budget is a per-push guarantee: gate the worst trial
                "relay_hop_amp_max": float(np.max(np.asarray(relay_amp))),
                "edge_hop_amp_max": float(np.max(np.asarray(edge_amp))),
                "within_budget": bool(
                    max(np.max(np.asarray(relay_amp)),
                        np.max(np.asarray(edge_amp))) <= 1.25),
                "edges_bit_identical": bool(identical),
                "relay_fanout": {"median_s": float(np.median(f)),
                                 "mean_s": float(f.mean())},
                "sequential_refan": {"median_s": float(np.median(s)),
                                     "mean_s": float(s.mean())},
            }
            print(f"relay_C{C},{np.median(f) * 1e6:.1f},"
                  f"parent_reads={changed_blobs} local=0 "
                  f"amp={out[f'C{C}']['edge_hop_amp_max']:.3f}")
            print(f"relay_C{C}_stale,{np.median(s) * 1e6:.1f},"
                  f"local_reads={changed_blobs} "
                  f"ratio={out[f'C{C}']['stale_read_ratio_vs_sequential']:.1f}")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_multitenant(trials: int) -> dict:
    """Cross-image blob universe (the fleet-of-fine-tunes topology): T
    tenant images forked from ONE base (shared backbone layers, per-tenant
    adapter), stored and replicated in a single cross-image namespace.
    Gated claims, all counter-proved against instrumented stores:

    * pushing a fresh tenant to a replica that holds only the BASE image
      ships only the adapter delta — ZERO base/backbone blobs are read at
      the source or cross the wire (the sibling image vouches for them);
    * consolidating base + T tenants onto one remote costs, in wire AND
      in remote disk, at most 1.25x (base bytes + sum of adapter bytes) —
      tenants dedup against the base and against each other;
    * cross-image ``gc()`` is exact: removing T-1 tenant images sweeps
      precisely their exclusive adapter blobs, and every blob the base
      (or the surviving tenant) reaches stays on disk.
    """
    from repro.core import Instruction, LayerStore, push_delta, \
        replicate_fanout
    from .scenarios import _gen

    T, R = 4, 2                         # tenants, base-holding replicas
    n_backbone, leaves_per_layer = 4, 8
    leaf_bytes = chunk_bytes = 128 << 10
    adapter_leaves = 2

    ins = [Instruction("FROM", "base", "config")]
    backbone = {}
    for i in range(n_backbone):
        key = f"backbone{i}"
        ins.append(Instruction("COPY", key, "content"))
        backbone[key] = {f"B{i}/l{j:03d}": _gen(7000 + i * 64 + j,
                                                leaf_bytes)
                         for j in range(leaves_per_layer)}
    ins.append(Instruction("COPY", "adapter", "content"))
    ins.append(Instruction("CMD", "serve", "config"))

    def adapter_payload(t):
        return {f"A/l{j}": _gen(9000 + t * 16 + j, leaf_bytes)
                for j in range(adapter_leaves)}

    def image_chunks(store, name, tag="v1"):
        m, _ = store.read_image(name, tag)
        return {h for lid in m.layer_ids
                for rec in store.read_layer(lid).records
                for h in rec.chunks}

    def blob_bytes(store, chunks):
        return sum(len(store.read_blob(h)) for h in chunks)

    def disk_blob_bytes(store):
        total = 0
        for dirpath, _, files in os.walk(os.path.join(store.root, "blobs")):
            total += sum(os.path.getsize(os.path.join(dirpath, f))
                         for f in files)
        return total

    out = {"tenants": T, "replicas": R, "backbone_layers": n_backbone,
           "leaf_bytes": leaf_bytes, "chunk_bytes": chunk_bytes,
           "trials": trials}
    root = tempfile.mkdtemp(prefix="lc_mt_")
    try:
        src = LayerStore(os.path.join(root, "src"),
                         chunk_bytes=chunk_bytes,
                         record_fingerprints=False)
        prov = {key: (lambda v=v: v) for key, v in backbone.items()}
        base_ad = adapter_payload(0)
        prov["adapter"] = lambda: base_ad
        src.build_image("base", "v1", ins, prov)
        base_chunks = image_chunks(src, "base")
        base_bytes = blob_bytes(src, base_chunks)

        tenant_chunks = {}
        for t in range(1, T + 1):
            ad = adapter_payload(t)
            tprov = dict(prov)
            tprov["adapter"] = lambda v=ad: v
            _, _, rep = src.build_image(f"tenant{t}", "v1", ins, tprov,
                                        parent=("base", "v1"))
            assert rep.layers_cached >= n_backbone + 1   # FROM + backbone
            tenant_chunks[t] = image_chunks(src, f"tenant{t}")
        adapter_chunks = {t: tenant_chunks[t] - base_chunks
                          for t in tenant_chunks}
        adapter_bytes = {t: blob_bytes(src, adapter_chunks[t])
                         for t in adapter_chunks}

        # -- fleet arm: per-tenant fan-out to R base-holding replicas ----
        replicas = [LayerStore(os.path.join(root, f"r{i}"),
                               chunk_bytes=chunk_bytes,
                               record_fingerprints=False)
                    for i in range(R)]
        for r in replicas:
            push_delta(src, r, "base", "v1")

        fan_t, amp = [], []
        rounds_ok = zero_base = True
        orig_read = src.read_blob
        for t in range(1, T + 1):
            reads = []
            src.read_blob = lambda h: (reads.append(h), orig_read(h))[1]
            t0 = time.perf_counter()
            fan = replicate_fanout(src, replicas, f"tenant{t}", "v1")
            fan_t.append(time.perf_counter() - t0)
            del src.read_blob
            assert fan.ok, [r.error for r in fan.replicas]
            rounds_ok &= fan.negotiation_rounds == 1
            # the counter-proof: NOT ONE backbone blob was even read
            zero_base &= not (set(reads) & base_chunks)
            zero_base &= set(reads) == adapter_chunks[t]
            amp.append(max(r.stats.bytes_sent for r in fan.replicas)
                       / max(adapter_bytes[t], 1))
        amp_max = float(np.max(np.asarray(amp)))
        out["fleet"] = {
            "negotiation_rounds": 1 if rounds_ok else -1,
            "zero_base_blob_transfers": bool(zero_base),
            "wire_amplification_max": amp_max,
            "within_budget": bool(amp_max <= 1.25),
            "per_tenant_median_s": float(np.median(np.asarray(fan_t))),
            "adapter_bytes": adapter_bytes[1],
            "base_bytes": base_bytes,
        }
        print(f"multitenant_fleet,{np.median(np.asarray(fan_t)) * 1e6:.1f},"
              f"T={T} zero_base={zero_base} amp={amp_max:.3f}")

        # -- consolidation arm: base + T tenants onto ONE empty remote ---
        remote = LayerStore(os.path.join(root, "remote"),
                            chunk_bytes=chunk_bytes,
                            record_fingerprints=False)
        wire = push_delta(src, remote, "base", "v1").bytes_sent
        for t in range(1, T + 1):
            wire += push_delta(src, remote, f"tenant{t}", "v1").bytes_sent
        budget = base_bytes + sum(adapter_bytes.values())
        disk = disk_blob_bytes(remote)
        out["consolidation"] = {
            "wire_total": wire,
            "disk_blob_bytes": disk,
            "budget_bytes": budget,
            "wire_amplification": wire / budget,
            "disk_amplification": disk / budget,
            "wire_within_budget": bool(wire <= 1.25 * budget),
            "disk_within_budget": bool(disk <= 1.25 * budget),
        }
        print(f"multitenant_consolidation,wire={wire},"
              f"amp={wire / budget:.3f} disk_amp={disk / budget:.3f}")

        # -- gc arm: drop T-1 tenants at the remote, sweep exactly -------
        survivors = base_chunks | tenant_chunks[T]
        expected = len(set().union(*(adapter_chunks[t]
                                     for t in range(1, T))) - survivors)
        for t in range(1, T):
            assert remote.remove_image(f"tenant{t}", "v1")
        stats = remote.gc()
        base_ok = all(remote.has_blob(h) for h in survivors)
        out["gc"] = {
            "blobs_swept": stats["blobs_swept"],
            "blobs_expected": expected,
            "exact": bool(stats["blobs_swept"] == expected),
            "base_survives": bool(base_ok),
            "survivors_verify_clean": bool(
                remote.verify_image("base", "v1", deep=True) == [] and
                remote.verify_image(f"tenant{T}", "v1", deep=True) == []),
        }
        print(f"multitenant_gc,swept={stats['blobs_swept']},"
              f"expected={expected} base_survives={base_ok}")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_scrub_repair(trials: int) -> dict:
    """Self-healing loop (scrub -> anti-entropy repair), gated claims all
    counter-proved against instrumented stores:

    * a clean store scrubs to ZERO findings (no false positives);
    * scrub detects 100% of injected at-rest bit flips, attributed to the
      exact flipped blob set;
    * repair from a pristine peer reads ONLY the damaged blobs at the
      source (read-counter proof), stays within the 1.25x wire budget,
      deep-verifies on commit, and restores bit-identical payload bytes;
    * a sliced/resumable scrub pass unions to the same verdict as one
      full pass.
    """
    from repro.core import Instruction, LayerStore, push, repair_image
    from repro.ft.faults import inject_bitrot
    from repro.ft.scrub import load_cursor
    from .scenarios import _gen

    n_layers, leaves_per_layer, flips = 3, 4, 3
    leaf_bytes = chunk_bytes = 64 << 10

    ins = [Instruction("FROM", "base", "config")]
    payloads = {}
    for i in range(n_layers):
        key = f"layer{i}"
        ins.append(Instruction("COPY", key, "content"))
        payloads[key] = {
            f"l{j:03d}": _gen(4000 + i * leaves_per_layer + j, leaf_bytes)
            for j in range(leaves_per_layer)}
    ins.append(Instruction("CMD", "serve", "config"))

    out = {"n_layers": n_layers, "leaves": n_layers * leaves_per_layer,
           "leaf_bytes": leaf_bytes, "chunk_bytes": chunk_bytes,
           "flips": flips, "trials": trials}
    root = tempfile.mkdtemp(prefix="lc_scrub_")
    try:
        src = LayerStore(os.path.join(root, "src"),
                         chunk_bytes=chunk_bytes,
                         record_fingerprints=False)
        prov = {key: (lambda v=v: v) for key, v in payloads.items()}
        src.build_image("app", "v1", ins, prov)
        m, _ = src.read_image("app", "v1")
        chunks = {h for lid in m.layer_ids
                  for rec in src.read_layer(lid).records
                  for h in rec.chunks}
        pristine = {h: src.read_blob(h) for h in chunks}
        store_bytes = sum(len(b) for b in pristine.values())

        clean_t, detect_t, repair_t = [], [], []
        clean_zero = detect_100 = reads_only = True
        within = deep_ok = bit_ok = union_ok = True
        amps, slice_counts = [], []
        for tr in range(trials):
            victim = LayerStore(os.path.join(root, f"v{tr}"),
                                chunk_bytes=chunk_bytes,
                                record_fingerprints=False)
            push(src, victim, "app", "v1")

            # -- clean arm: a healthy store must scrub quiet ------------
            t0 = time.perf_counter()
            rep = victim.scrub(reset=True)
            clean_t.append(time.perf_counter() - t0)
            clean_zero &= bool(rep.clean)

            # -- detection arm: every injected flip found, none extra --
            want = {h for h, _ in inject_bitrot(
                victim.root, seed=100 + tr, count=flips,
                candidates=sorted(chunks))}
            assert len(want) == flips
            t0 = time.perf_counter()
            rep = victim.scrub(reset=True)
            detect_t.append(time.perf_counter() - t0)
            detect_100 &= bool(set(rep.corrupt_blob_hashes) == want)

            # -- repair arm: counter-proof that ONLY damaged bytes move
            reads = []
            orig = src.read_blob
            src.read_blob = lambda h: (reads.append(h), orig(h))[1]
            try:
                t0 = time.perf_counter()
                rr = repair_image(victim, "app", "v1", peers=[src],
                                  scrub_report=rep)
                repair_t.append(time.perf_counter() - t0)
            finally:
                src.read_blob = orig
            reads_only &= bool(set(reads) == want)
            amps.append(rr.wire_amplification)
            within &= bool(rr.wire_amplification <= 1.25)
            deep_ok &= bool(rr.verified_clean)
            victim.purge_quarantine()
            bit_ok &= all(victim.read_blob(h) == pristine[h]
                          for h in chunks)

            # -- sliced arm: resumable slices union to the full verdict
            want2 = {h for h, _ in inject_bitrot(
                victim.root, seed=200 + tr, count=flips,
                candidates=sorted(chunks))}
            merged = victim.scrub(max_items=4, reset=True)
            slices = 1
            while load_cursor(victim.root) != 0:
                merged.merge(victim.scrub(max_items=4))
                slices += 1
            slice_counts.append(slices)
            union_ok &= bool(set(merged.corrupt_blob_hashes) == want2)

        c, d, r = (np.asarray(clean_t), np.asarray(detect_t),
                   np.asarray(repair_t))
        amp_median = float(np.median(np.asarray(amps)))
        out["scrub"] = {
            "median_s": float(np.median(c)),
            "mean_s": float(c.mean()),
            "MBps": store_bytes / max(float(np.median(c)), 1e-12) / 1e6,
            "clean_store_zero_findings": bool(clean_zero),
        }
        out["detect"] = {
            "median_s": float(np.median(d)),
            "detection_100": bool(detect_100),
        }
        out["repair"] = {
            "median_s": float(np.median(r)),
            "reads_only_damaged": bool(reads_only),
            "wire_amplification": amp_median,
            "within_budget": bool(within),
            "deep_verified": bool(deep_ok),
            "bit_identical": bool(bit_ok),
        }
        out["sliced"] = {
            "median_slices": float(np.median(np.asarray(slice_counts))),
            "union_equals_full": bool(union_ok),
        }
        print(f"scrub_clean,{np.median(c) * 1e6:.1f},"
              f"zero_findings={clean_zero} "
              f"MBps={out['scrub']['MBps']:.1f}")
        print(f"scrub_detect,{np.median(d) * 1e6:.1f},"
              f"detection_100={detect_100}")
        print(f"scrub_repair,{np.median(r) * 1e6:.1f},"
              f"amp={amp_median:.3f} reads_only_damaged={reads_only} "
              f"bit_identical={bit_ok}")
        print(f"scrub_sliced,,slices={np.median(np.asarray(slice_counts))}"
              f" union_equals_full={union_ok}")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_squash_pull(trials: int) -> dict:
    """Squashed static delta chains through the passive bundle registry,
    gated claims counter-proved:

    * squashing k=8 per-commit deltas into ONE static bundle stays within
      1.25x of min(sum of per-hop bundles, full bundle) — repeated
      overwrites of the same chunk collapse to the final bytes;
    * the squashed bundle is BIT-identical to replaying the chain
      (``verify_squashed_bundle``: scratch-store apply + deep verify +
      per-chunk byte compare);
    * a follower 8 commits behind converges from plain published files
      with ZERO negotiation round-trips (``DeltaReceiver.negotiate``
      monkeypatch-counted) pulling within 1.25x of the cheapest
      advertised chain, deep-verified and bit-identical at the end.
    """
    from repro.core import (Instruction, LayerStore, PassiveRegistry,
                            inject_payload_update, plan_bundle_chain,
                            push, squash_deltas, verify_squashed_bundle)
    from repro.core.registry import DeltaReceiver
    from repro.serve.engine import CheckpointFollower

    steps, chunk_bytes = 9, 4096
    hops = steps - 1

    def tag(s: int) -> str:
        return f"step-{s:08d}"

    out = {"steps": steps, "hops": hops, "chunk_bytes": chunk_bytes,
           "trials": trials}
    root = tempfile.mkdtemp(prefix="lc_squash_")
    try:
        rng = np.random.default_rng(42)
        src = LayerStore(os.path.join(root, "src"),
                         chunk_bytes=chunk_bytes,
                         record_fingerprints=False)
        state = {"params/w": rng.standard_normal(16384).astype(np.float32),
                 "opt/m": rng.standard_normal(16384).astype(np.float32),
                 "opt/__step__": np.asarray([1], np.int32)}
        ins = [Instruction("FROM", "arch", "config"),
               Instruction("COPY", "state", "content")]
        src.build_image("ckpt", tag(1), ins, {"state": lambda: state})
        # every commit rewrites the SAME hot head of params/w (the bytes a
        # squash collapses) plus a per-step slice of opt/m (the bytes it
        # must keep) — the checkpoint-stream shape the paper's injection
        # path produces
        for s in range(2, steps + 1):
            state = {k: v.copy() for k, v in state.items()}
            state["params/w"][:1024] = rng.standard_normal(1024)
            state["opt/m"][(s - 1) * 1024:s * 1024] += 1.0
            state["opt/__step__"][0] = s
            inject_payload_update(src, "ckpt", tag(s - 1), tag(s),
                                  {"state": state})

        # trainer-cadence publishing: one incremental publish per commit
        # (per-hop chain accumulates in the index), then the lagging-edge
        # advertisement — ONE squashed bundle spanning all 8 hops
        reg = PassiveRegistry(os.path.join(root, "registry"))
        for s in range(2, steps + 1):
            reg.publish_image(src, "ckpt", tag(s), from_tags=[tag(s - 1)])
        index = reg.publish_image(src, "ckpt", tag(steps),
                                  from_tags=[tag(1)])
        ent = {(e.from_tag, e.to_tag): e for e in index.entries}
        per_hop_bytes = sum(ent[(tag(s - 1), tag(s))].size
                            for s in range(2, steps + 1))
        squashed_bytes = ent[(tag(1), tag(steps))].size
        full_bytes = ent[("", tag(steps))].size
        budget = min(per_hop_bytes, full_bytes) * 1.25

        # cheapest ADVERTISED chain for a follower holding only step 1 —
        # the yardstick the pull must stay within 1.25x of
        chain = plan_bundle_chain(index, [tag(1)])
        cheapest = sum(e.size for e in chain)

        m9, _ = src.read_image("ckpt", tag(steps))
        chunks9 = {h for lid in m9.layer_ids
                   for rec in src.read_layer(lid).records
                   for h in rec.chunks}

        squash_t, poll_t = [], []
        neg_rounds = 0
        verified = conv_ok = bit_ok = pulled_ok = True
        hops_applied = pull_bytes = planned_bytes = 0
        for tr in range(trials):
            t0 = time.perf_counter()
            bundle = squash_deltas(src, "ckpt", tag(1), tag(steps))
            squash_t.append(time.perf_counter() - t0)
            if tr == 0:
                verified = verify_squashed_bundle(src, bundle) == []

            # passive-only follower (remote=None): plain files are the
            # ONLY channel, so any negotiate() call would be a lie —
            # counter-proved by counting them
            local = LayerStore(os.path.join(root, f"f{tr}"),
                               chunk_bytes=chunk_bytes,
                               record_fingerprints=False)
            push(src, local, "ckpt", tag(1))
            follower = CheckpointFollower(None, local, image="ckpt",
                                          keep=steps + 2, registry=reg)
            calls = []
            orig = DeltaReceiver.negotiate
            DeltaReceiver.negotiate = \
                lambda self, *a, **k: (calls.append(1),
                                       orig(self, *a, **k))[1]
            try:
                t0 = time.perf_counter()
                upd = follower.poll()
                poll_t.append(time.perf_counter() - t0)
            finally:
                DeltaReceiver.negotiate = orig
            neg_rounds += len(calls)
            assert upd is not None and upd.step == steps
            plan = follower.last_plan
            hops_applied = plan.hops
            pull_bytes = plan.bytes_pulled
            planned_bytes = plan.planned_bytes
            pulled_ok &= bool(pull_bytes <= cheapest * 1.25)
            conv_ok &= local.verify_image("ckpt", tag(steps),
                                          deep=True) == []
            bit_ok &= all(local.read_blob(h) == src.read_blob(h)
                          for h in chunks9)

        sq, pl = np.asarray(squash_t), np.asarray(poll_t)
        out["publish"] = {
            "per_hop_bytes": int(per_hop_bytes),
            "squashed_bytes": int(squashed_bytes),
            "full_bytes": int(full_bytes),
            "collapse_ratio": per_hop_bytes / max(squashed_bytes, 1),
            "budget_ratio": squashed_bytes
            / max(min(per_hop_bytes, full_bytes), 1),
            "squash_within_budget": bool(squashed_bytes <= budget),
            "verified_bit_identical": bool(verified),
            "squash_median_s": float(np.median(sq)),
        }
        out["follower"] = {
            "lag_commits": hops,
            "negotiation_rounds": int(neg_rounds),
            "hops_applied": int(hops_applied),
            "pull_bytes": int(pull_bytes),
            "planned_bytes": int(planned_bytes),
            "cheapest_advertised_bytes": int(cheapest),
            "pull_ratio": pull_bytes / max(cheapest, 1),
            "pulled_within_budget": bool(pulled_ok),
            "converged_deep_verified": bool(conv_ok),
            "bit_identical": bool(bit_ok),
            "poll_median_s": float(np.median(pl)),
        }
        print(f"squash_publish,{np.median(sq) * 1e6:.1f},"
              f"squashed={squashed_bytes}B per_hop={per_hop_bytes}B "
              f"full={full_bytes}B within={out['publish']['squash_within_budget']}"
              f" collapse={out['publish']['collapse_ratio']:.2f}x")
        print(f"squash_verify,,bit_identical={verified}")
        print(f"passive_pull,{np.median(pl) * 1e6:.1f},"
              f"hops={hops_applied} negotiations={neg_rounds} "
              f"pulled={pull_bytes}B cheapest={cheapest}B "
              f"deep_verified={conv_ok} bit_identical={bit_ok}")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_fingerprint(trials: int) -> dict:
    """Change-detector throughput: host SHA-256 vs on-device fingerprint
    (jnp path; the Pallas kernel is the TPU-target implementation)."""
    import hashlib

    import jax.numpy as jnp
    from repro.core import fingerprint_chunks
    arr = np.random.default_rng(0).standard_normal(32 << 18)  # 32 MiB f32
    jarr = jnp.asarray(arr, jnp.float32)
    fingerprint_chunks(jarr).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(trials):
        fingerprint_chunks(jarr).block_until_ready()
    fp_t = (time.perf_counter() - t0) / trials
    data = arr.tobytes()
    t0 = time.perf_counter()
    for _ in range(trials):
        hashlib.sha256(data).hexdigest()
    sha_t = (time.perf_counter() - t0) / trials
    nbytes = len(data)
    out = {"sha256_GBps": nbytes / sha_t / 1e9,
           "fingerprint_GBps": nbytes / fp_t / 1e9,
           "speedup": sha_t / fp_t}
    print(f"chg_detect_sha256,{sha_t * 1e6:.1f},"
          f"{out['sha256_GBps']:.2f}GB/s")
    print(f"chg_detect_fingerprint,{fp_t * 1e6:.1f},"
          f"{out['fingerprint_GBps']:.2f}GB/s")
    return out


def bench_roofline() -> dict:
    """Collect the dry-run artifacts into the §Roofline table."""
    from .roofline_table import build_table
    table = build_table()
    for row in table["rows"][:5]:
        print(f"roofline_{row['arch']}_{row['shape']},,"
              f"dom={row['dominant']} frac={row['roofline_fraction']:.3f}")
    print(f"roofline_cells,,{len(table['rows'])}")
    return table


# Benches with a committed repo-root baseline snapshot: the CI regression
# gate (benchmarks/check_regression.py) compares fresh results/<name>.json
# against BENCH_<name>.json. Baselines are only (re)written under
# --update-baseline so a CI --quick run never clobbers the committed one.
BASELINES = {
    "incremental_save": "BENCH_incremental_save.json",
    "multilayer_inject": "BENCH_multilayer_inject.json",
    "push_delta": "BENCH_push_delta.json",
    "fanout": "BENCH_fanout.json",
    "relay": "BENCH_relay.json",
    "multitenant": "BENCH_multitenant.json",
    "scrub_repair": "BENCH_scrub_repair.json",
    "squash_pull": "BENCH_squash_pull.json",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=30)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--update-baseline", action="store_true",
                    help="snapshot BENCH_*.json baselines at the repo root")
    args = ap.parse_args()
    trials = 5 if args.quick else args.trials

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    os.makedirs(RESULTS, exist_ok=True)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    results = {}
    benches = {
        "scenarios": lambda: bench_scenarios(trials),
        "decompose": lambda: bench_decompose(max(trials // 3, 3)),
        "fallthrough": lambda: bench_fallthrough(max(trials // 3, 3)),
        "ckpt_cadence": lambda: bench_ckpt_cadence(trials),
        "incremental_save": lambda: bench_incremental_save(trials),
        "multilayer_inject": lambda: bench_multilayer_inject(trials),
        "push_delta": lambda: bench_push_delta(max(trials // 3, 5)),
        "fanout": lambda: bench_fanout(max(trials // 3, 5)),
        "relay": lambda: bench_relay(max(trials // 3, 5)),
        "multitenant": lambda: bench_multitenant(max(trials // 3, 3)),
        "scrub_repair": lambda: bench_scrub_repair(max(trials // 3, 3)),
        "squash_pull": lambda: bench_squash_pull(max(trials // 3, 3)),
        "fingerprint": lambda: bench_fingerprint(trials),
        "roofline": bench_roofline,
    }
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        print(f"# --- {name} ---")
        try:
            results[name] = fn()
        except Exception as e:
            import traceback
            traceback.print_exc()
            results[name] = {"error": str(e)}
        with open(os.path.join(RESULTS, f"{name}.json"), "w") as f:
            json.dump(results[name], f, indent=1, default=str)
        if args.update_baseline and name in BASELINES and \
                "error" not in results[name]:
            with open(os.path.join(repo_root, BASELINES[name]), "w") as f:
                json.dump(results[name], f, indent=1, default=str)


if __name__ == "__main__":
    main()
