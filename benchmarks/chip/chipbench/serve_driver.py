"""A trainer and a following server in one process, one after the other.

Each cycle: one full-parameter train step; ``CheckpointManager.save`` of
the host copy (as ``repro.launch.train`` saves) and ``wait``; a
``CheckpointFollower`` (defaults: sparse, verify, keep 2) runs
``poll_and_refresh`` on the serving ``Engine``; the engine answers the
cycle's greedy requests. ``save_to_served_s`` runs from the save call
until the answers are back, and is the mean over the window's cycles.

Set-up makes the base checkpoint, the follower's first full pull, the
engine and its compiled prefill and decode, and one untimed cycle that
times a cycle; the window runs about ``--seconds`` of cycles.

Correct: after each cycle's refresh the engine's params equal, bit for
bit, the params that cycle saved; every served token is held against the
plain reference's logits over its prompt and the tokens before it.
"""
from __future__ import annotations

import functools
import gc
import os
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import compare
from .harness import RunRecord, rng
from .program import (adam_start, batch_at, block, check_layout,
                      model_config, peak_bytes, spanned_manager)
from .reference import flat, init_weights, logits

FAULTS = ("stale_refresh", "altered_token")


def prompts_at(seed: int, cycle: int, t: Dict[str, Any], vocab: int):
    return rng(seed, 3, cycle).integers(
        0, vocab, (t["requests"], t["prompt_len"]), dtype=np.int32)


def run(cell, seed: int, seconds: float, traced: bool, work: str,
        tracer=None, fault: Optional[str] = None,
        control: bool = False) -> RunRecord:
    from repro.ckpt import CheckpointManager
    from repro.data import make_global_batch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.optim import AdamWConfig
    from repro.serve import CheckpointFollower, Engine
    from repro.train import TrainConfig, make_train_step

    enable_compile_cache()
    c, t = cell.config, cell.traffic
    cfg = model_config(c)
    check_layout(c, cfg)
    B, S = t["batch"], t["seq"]
    o = t["optimizer"]
    tcfg = TrainConfig(adamw=AdamWConfig(
        peak_lr=o["peak_lr"], warmup_steps=o["warmup_steps"],
        decay_steps=o["decay_steps"], min_lr_ratio=o["min_lr_ratio"],
        b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"], grad_clip=o["grad_clip"]))
    rec = RunRecord(cell=cell)
    spans = rec.spans
    reports: List[Any] = []
    Manager = spanned_manager(CheckpointManager, spans, reports)
    ckpt, replica = os.path.join(work, "ckpt"), os.path.join(work, "replica")
    mesh = make_mesh((1, 1), ("data", "model"))
    max_len = t["prompt_len"] + t["new_tokens"] + 8
    kept: List[Dict[str, Any]] = []
    st: Dict[str, Any] = {"step": 0}

    with jax.set_mesh(mesh):
        bundle = make_train_step(cfg, tcfg, mesh, B, S)
        p_sh, o_sh, b_sh = bundle.in_shardings
        bspecs = {k: s.spec for k, s in b_sh.items()}
        st["params"] = jax.device_put(init_weights(c, seed), p_sh)
        st["opt"] = jax.device_put(adam_start(st["params"]), o_sh)
        mgr = Manager(ckpt, cfg.name)
        follower = CheckpointFollower(remote=ckpt, local=replica)
        poll = follower.poll

        def spanned_poll():
            with spans.span("bench.follower_poll"):
                return poll()

        follower.poll = spanned_poll

        def train_and_save():
            batch = make_global_batch(mesh, bspecs, batch_at(
                seed, st["step"], cfg.vocab, B, S))
            st["params"], st["opt"], _ = bundle.fn(st["params"], st["opt"],
                                                   batch)
            st["step"] += 1
            block(st["params"])
            t0 = time.perf_counter()
            host_p = jax.tree.map(np.asarray, st["params"])
            host_o = jax.tree.map(np.asarray, st["opt"])
            mgr.save(st["step"], host_p, host_o)
            mgr.wait()
            return t0, host_p

        # set-up: base checkpoint, first full pull, engine, compiled serve
        train_and_save()
        upd = follower.poll()
        engine = Engine(cfg, jax.tree.map(jnp.asarray, upd.params), max_len)
        refresh = engine.refresh

        def spanned_refresh(params, changed=None, step=None):
            with spans.span("bench.engine_refresh"):
                if fault == "stale_refresh":
                    n = 0
                else:
                    n = refresh(params, changed, step=step)
                block(engine.params)
            return n

        engine.refresh = spanned_refresh
        engine.generate(prompts_at(seed, 0, t, cfg.vocab), t["new_tokens"])

        def cycle(i: int, keep: bool) -> float:
            t0, host_p = train_and_save()
            upd = follower.poll_and_refresh(engine)
            prompts = prompts_at(seed, i, t, cfg.vocab)
            toks = engine.generate(prompts, t["new_tokens"]).tokens
            dt = time.perf_counter() - t0
            rec.attempted += len(prompts)
            if upd is None or upd.step != st["step"]:
                rec.failed += len(prompts)
            k = {"saved": host_p, "served": engine.params,
                 "prompts": prompts, "tokens": toks}
            if fault == "altered_token":
                k = _altered(k, cfg.vocab)
            if keep:
                kept.append(k)
            return dt

        t0 = time.perf_counter()
        for i in range(t["warm_cycles"]):
            cycle(1 + i, keep=False)
        cycle_s = (time.perf_counter() - t0) / t["warm_cycles"]
        cycles = 1 if traced else max(1, round(seconds / cycle_s))
        rec.attempted = rec.failed = 0
        if tracer:
            tracer.start()
        with spans.span("bench.window"):
            spans.mark("window_start")
            lat = [cycle(1 + t["warm_cycles"] + i, keep=True)
                   for i in range(cycles)]
        if tracer:
            rec.trace = tracer.stop()
    rec.end_to_end["save_to_served_s"] = float(np.mean(lat))
    rec.counters.update(cycle_s=cycle_s, cycles=cycles,
                        save_write_s=[r.wall_seconds for k, s, r in reports
                                      if k == "incremental"][-cycles:])
    rec.memory_peak_bytes = peak_bytes()

    # ---- correctness, after the window, with the program's state freed
    mismatch = 0
    for k in kept:
        served = flat(k.pop("served"))
        saved = flat(k["saved"])
        mismatch += len(set(served) ^ set(saved)) + sum(
            not (np.asarray(served[n]).tobytes() == saved[n].tobytes()
                 and served[n].dtype == saved[n].dtype)
            for n in saved if n in served)
    del engine, follower, bundle, st
    gc.collect()
    gaps = [served_gap(c, k) for k in kept]
    rec.counters.update(served_gap=gaps, served_tokens=sum(
        k["tokens"].size for k in kept))
    if control:
        rec.counters["control_gap"] = [control_gap(c, k) for k in kept]
        rec.counters["altered_gap"] = [served_gap(c, _altered(k, cfg.vocab))
                                       for k in kept]
    rec.compared = compare.with_limits(
        {"served_gap": max(gaps), "params_mismatch": float(mismatch)},
        cell.limits)
    return rec


@functools.lru_cache(maxsize=4)
def _logits_fn(config_json: str, precision: str):
    import json
    c = json.loads(config_json)
    return jax.jit(lambda p, tok: logits(c, p, tok, precision))


def reference_logits(c, saved, prompts, tokens, precision: str):
    """Reference logits at the positions that chose each served token."""
    import json
    seq = np.concatenate([prompts, tokens[:, :-1]], axis=1)
    fn = _logits_fn(json.dumps(c, sort_keys=True), precision)
    lg = fn(jax.tree.map(jnp.asarray, saved), jnp.asarray(seq))
    return np.asarray(lg[:, prompts.shape[1] - 1:])


def served_gap(c, k: Dict[str, Any]) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at that position."""
    lg = reference_logits(c, k["saved"], k["prompts"], k["tokens"], "f32")
    picked = np.take_along_axis(lg, k["tokens"][..., None], -1)[..., 0]
    return float((lg.max(-1) - picked).max())


def control_gap(c, k: Dict[str, Any]) -> float:
    """The control: at the same positions, the gap (in the float32
    reference) of the token that the fp8 reference puts first."""
    lg = reference_logits(c, k["saved"], k["prompts"], k["tokens"], "f32")
    low = reference_logits(c, k["saved"], k["prompts"], k["tokens"], "fp8")
    first = low.argmax(-1)
    picked = np.take_along_axis(lg, first[..., None], -1)[..., 0]
    return float((lg.max(-1) - picked).max())


def _altered(k: Dict[str, Any], vocab: int) -> Dict[str, Any]:
    """A served token altered where it is produced (a planted fault)."""
    toks = k["tokens"].copy()
    toks[0, 0] = (toks[0, 0] + 1) % vocab
    return {**k, "tokens": toks}
