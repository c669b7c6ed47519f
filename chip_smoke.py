"""Bring-up check of the main path on a TPU, through the entry points a
user calls, at a supported model's published widths (random weights from
a fixed seed).

    python chip_smoke.py                # one chip: mamba2-130m
    python chip_smoke.py --four-chips   # four chips: gemma-2b, 2x2 -> 4x1

One chip (mamba2-130m, batch 8 x seq 1024):

1. train: ``repro.launch.train`` for 10 steps (full save at step 10), a
   follower pulls step 10 and an ``Engine`` serves it, then the trainer
   resumes to step 20 (incremental save, which must go through injection:
   injected layers, one re-key walk, one manifest commit).
2. fingerprint: ``fingerprint_tree_packed`` on the device-resident state
   with the jnp and the Pallas backend (compiled, not interpreted), each
   bit-identical to ``fingerprint_tree_ref`` of the host copy; then one
   fingerprint-prefiltered ``CheckpointManager`` save and its restore.
3. follower: ``CheckpointFollower.poll_and_refresh`` swaps step 20 into the
   serving engine; its params equal ``restore()`` bit for bit and its
   greedy tokens equal those of an engine built from the full reload.
4. serve: ``repro.launch.serve --store`` answers the same requests.

Four chips (gemma-2b at its published widths, cut to 2 layers; batch 8
x seq 1024): train on a 2x2 ("data", "model") mesh and save,
``reshard_restore`` onto a 4x1 mesh (every leaf bit-identical to the
saved host copy), one more step there. Depth is cut because the host save
path runs at tens of MB/s: the full 18-layer state (35 GB with Adam)
would take minutes per save and restore. At full depth a 4x1 layout does
not fit anyway: the sharding rules replicate params over "data" (ZeRO-1
shards only the optimizer).

Every check raises on failure. The wall and compile seconds printed per
phase are a first run on the chip, not benchmark numbers. The last line
of stdout is ``{"ok": true, "device": {...}}``, printed only when every
phase passed and the devices are TPUs.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import obs  # noqa: E402
from repro.ckpt import CheckpointManager, CheckpointPolicy, reshard_restore  # noqa: E402
from repro.ckpt.manager import flatten_tree  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.core import fingerprint_tree_packed  # noqa: E402
from repro.core.fingerprint import fingerprint_tree_ref  # noqa: E402
from repro.data import SyntheticTokens, make_global_batch  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.serve import CheckpointFollower, Engine  # noqa: E402
from repro.train import TrainConfig, make_train_step  # noqa: E402


class Clock:
    """Per-phase wall seconds plus the backend-compile seconds (loads from
    the persistent cache included) and persistent-cache hits that the
    program's compile counter (``repro.obs``) sees while the phase runs."""

    def __init__(self):
        self.start = obs.compiles()

    @property
    def total_compile_s(self) -> float:
        return (obs.compiles() - self.start).compile_s

    @contextlib.contextmanager
    def phase(self, name: str):
        before = obs.compiles()
        t0 = time.perf_counter()
        yield
        c = obs.compiles() - before
        print(f"[smoke] phase {name}: {time.perf_counter() - t0:.2f} s wall, "
              f"{c.compile_s:.2f} s compile "
              f"({c.cache_hits} persistent-cache hits)", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def host_tree(tree) -> dict:
    return {k: np.asarray(v) for k, v in flatten_tree(tree).items()}


def check_same(got, want, what: str) -> None:
    """Bit-for-bit equality of two pytrees, one host leaf at a time."""
    g, w = flatten_tree(got), flatten_tree(want)
    check(sorted(g) == sorted(w), f"{what}: leaf names differ")
    for k in w:
        a, b = np.asarray(g[k]), np.asarray(w[k])
        check(a.shape == b.shape and a.dtype == b.dtype and
              a.tobytes() == b.tobytes(), f"{what}: leaf {k} differs")


def one_chip(work: str, clock: Clock, *, arch: str = "mamba2-130m",
             smoke: bool = False, batch: int = 8, seq: int = 1024,
             prompt_len: int = 64, new_tokens: int = 32,
             interpret: bool = False) -> None:
    """The default run. ``smoke``/``interpret`` and the sizes exist so the
    same flow can be rehearsed on the CPU at a reduced config."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    ckpt, replica = os.path.join(work, "ckpt"), os.path.join(work, "replica")
    train_argv = ["--arch", arch, "--batch", str(batch), "--seq", str(seq),
                  "--ckpt", ckpt, "--ckpt-every", "10"] + \
        (["--smoke"] if smoke else [])
    # the requests repro.launch.serve makes for --batch 4 --prompt-len 64
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (4, prompt_len), 0, cfg.vocab))
    max_len = prompt_len + new_tokens + 8
    follower = CheckpointFollower(remote=ckpt, local=replica)

    with clock.phase("train 0->10 (full save)"):
        run = train.main(train_argv + ["--steps", "10"])
        check(bool(np.isfinite(float(run.metrics["loss"]))), "loss not finite")
        rep = run.manager.last_report
        print(f"[smoke] step-10 save: {rep.layers_built} layers built, "
              f"{rep.bytes_serialized} B serialized, {rep.wall_seconds:.2f} s",
              flush=True)
        check(rep.layers_built > 0 and rep.manifest_commits == 1,
              f"step-10 save is not a full build: {rep}")
    with clock.phase("follower pull step 10 + serve"):
        upd = follower.poll()
        check(upd is not None and upd.step == 10 and upd.full,
              "follower did not pull step 10")
        engine = Engine(cfg, jax.tree.map(jnp.asarray, upd.params), max_len)
        engine.generate(prompts, steps=new_tokens)
    with clock.phase("train 10->20 (incremental save)"):
        run = train.main(train_argv + ["--steps", "20"])
        loss = float(run.metrics["loss"])
        check(bool(np.isfinite(loss)), "loss not finite")
        rep = run.manager.last_report
        print(f"[smoke] step 20 loss {loss:.4f}; save: "
              f"{rep.layers_injected} layers injected, "
              f"{rep.rekey_walks} re-key walk, {rep.manifest_commits} "
              f"manifest commit, {rep.chunks_written} chunks written, "
              f"{rep.wall_seconds:.2f} s", flush=True)
        check(rep.layers_injected > 0 and rep.layers_built == 0 and
              rep.rekey_walks == 1 and rep.manifest_commits == 1,
              f"step-20 save did not go through injection: {rep}")
        mgr = run.manager

    with clock.phase("fingerprint on device (jnp, pallas)"):
        state = {**flatten_tree(run.params, "params"),
                 **flatten_tree(run.opt_state, "opt")}
        ref = fingerprint_tree_ref({k: np.asarray(v)
                                    for k, v in state.items()})
        n_chunks = sum(len(v) for v in ref.values())
        for backend in ("jnp", "pallas"):
            t0 = time.perf_counter()
            got = fingerprint_tree_packed(state, backend=backend,
                                          interpret=interpret)
            dt = time.perf_counter() - t0
            check(sorted(got) == sorted(ref) and
                  all(np.array_equal(got[k], ref[k]) for k in ref),
                  f"{backend} fingerprints differ from the reference")
            print(f"[smoke] fingerprint {backend}: {n_chunks} chunks of "
                  f"{len(state)} leaves equal the reference "
                  f"({dt:.2f} s incl. compile)", flush=True)
    with clock.phase("fingerprint-prefiltered save"):
        fp_mgr = CheckpointManager(
            ckpt, cfg.name, CheckpointPolicy(use_fingerprints=True,
                                             async_write=False),
            image="fingerprinted", store=mgr.store)
        fp_mgr.save(20, run.params, run.opt_state)
        p2 = dict(run.params)
        p2["embed"] = run.params["embed"].at[0, 0].add(1)
        rep = fp_mgr.save(21, p2, run.opt_state)
        print(f"[smoke] fingerprinted save: {rep.chunks_prefiltered} chunks "
              f"prefiltered, {rep.chunks_written} written, "
              f"{rep.bytes_d2h} B of fingerprints D2H", flush=True)
        check(rep.chunks_prefiltered > 0 and rep.layers_injected > 0,
              f"fingerprint prefilter did not run: {rep}")
        p3, o3, step = fp_mgr.restore()
        check(step == 21, "fingerprinted restore step")
        check_same(p3, p2, "fingerprinted restore params")
        check_same(o3, run.opt_state, "fingerprinted restore opt state")

    with clock.phase("follower refresh to step 20 + greedy decode"):
        upd = follower.poll_and_refresh(engine)
        check(upd is not None and upd.step == 20 and not upd.full,
              "follower did not apply a sparse step-20 update")
        want, _, _ = mgr.restore()
        check_same(engine.params, want, "refreshed engine params")
        toks = engine.generate(prompts, steps=new_tokens).tokens
        reload_toks = Engine(cfg, jax.tree.map(jnp.asarray, want),
                             max_len).generate(prompts, steps=new_tokens)
        check(np.array_equal(toks, reload_toks.tokens),
              "refreshed engine and full reload decode differently")
        print(f"[smoke] refresh swapped {engine.last_refresh_leaves} of "
              f"{len(jax.tree.leaves(want))} leaves; greedy tokens match "
              "the full reload", flush=True)
    with clock.phase("repro.launch.serve --store"):
        res = serve.main(["--arch", arch, "--store", ckpt, "--batch", "4",
                          "--prompt-len", str(prompt_len),
                          "--steps", str(new_tokens)] +
                         (["--smoke"] if smoke else []))
        check(np.array_equal(res.tokens, toks),
              "repro.launch.serve answers differently")


@contextlib.contextmanager
def cut_depth(layers: int):
    """``repro.launch.train`` resolves configs with ``layers`` layers (the
    widths stay published) while the context is open."""
    published = train.get_config
    train.get_config = lambda arch: published(arch).replace(n_layers=layers)
    try:
        yield
    finally:
        train.get_config = published


def four_chips(work: str, clock: Clock, *, arch: str = "gemma-2b",
               layers: int = 2, smoke: bool = False, batch: int = 8,
               seq: int = 1024) -> None:
    """Sharded training, save, and resume under another layout."""
    cfg = get_smoke_config(arch) if smoke else \
        get_config(arch).replace(n_layers=layers)
    check(len(jax.devices()) == 4, "--four-chips needs four devices")
    ckpt = os.path.join(work, "ckpt")
    with clock.phase("train 2x2 mesh, 2 steps + save"), cut_depth(layers):
        run = train.main(["--arch", arch, "--mesh", "2x2", "--steps", "2",
                          "--batch", str(batch), "--seq", str(seq),
                          "--ckpt", ckpt, "--ckpt-every", "2"] +
                         (["--smoke"] if smoke else []))
        check(bool(np.isfinite(float(run.metrics["loss"]))), "loss not finite")
        saved = (host_tree(run.params), host_tree(run.opt_state))
        mgr = run.manager
        del run                       # free the 2x2 copy before the 4x1 one
    with clock.phase("reshard_restore 2x2 -> 4x1 + one step"):
        mesh = make_mesh((4, 1), ("data", "model"))
        with jax.set_mesh(mesh):
            bundle = make_train_step(cfg, TrainConfig(), mesh, batch, seq)
            p_sh, o_sh = bundle.in_shardings[:2]
            params, opt, step = reshard_restore(
                mgr, mesh, jax.tree.map(lambda s: s.spec, p_sh),
                jax.tree.map(lambda s: s.spec, o_sh))
            check(step == 2, "restored step")
            check(all(a.sharding == s for a, s in zip(
                jax.tree.leaves(params), jax.tree.leaves(p_sh))),
                "restored params are not on the 4x1 layout")
            check_same(params, saved[0], "resharded params")
            check_same(opt, saved[1], "resharded opt state")
            specs = {k: s.spec for k, s in bundle.in_shardings[2].items()}
            host = SyntheticTokens(cfg.vocab, batch=batch, seq=seq).batch_at(2)
            _, _, metrics = bundle.fn(params, opt,
                                      make_global_batch(mesh, specs, host))
            loss = float(metrics["loss"])
            check(bool(np.isfinite(loss)), "loss not finite after reshard")
            print(f"[smoke] step 3 on the 4x1 mesh: loss {loss:.4f}",
                  flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded gemma-2b path on 4 chips")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"[smoke] device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache: {cache}", flush=True)
    if dev.platform != "tpu":
        print("[smoke] no TPU found: this check runs only on the chip",
              file=sys.stderr)
        return 1
    clock = Clock()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        if args.four_chips:
            four_chips(work, clock)
        else:
            one_chip(work, clock)
    print(f"[smoke] total: {time.perf_counter() - t0:.2f} s wall, "
          f"{clock.total_compile_s:.2f} s compile", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
