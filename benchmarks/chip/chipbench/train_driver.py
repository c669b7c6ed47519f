"""Training with checkpoints on, driven through ``repro.launch.train.main``.

Set-up: the seeded weights enter ``main`` through its restore seam (step
0), and ``main`` trains to the first save (a full build, the base), then a
second call resumes and trains one more save cycle (an injection save),
which times a cycle. Every call of ``main`` builds its own step, as a
restarted job does; the step's program depends on ``--steps`` (the
learning-rate decay), so set-up also runs one step of the window's
program in a call that saves nothing, and the window finds it in the
persistent compile cache.

Window: a ``main`` call resumes from the store (``resume_s``, until its
first step has finished: step build, restore and that step) and trains
whole save cycles, about ``--seconds`` of them, until the last save has
committed (``train_tokens_per_s`` over the steps after the first).

Correct: the program's first three steps (losses, the first gradient as
Adam's first moment holds it, the change of the float32 weights) against
the plain reference from the same seed, and the state restored from the
window's last save against the live state.

Traffic keys (``traffic/<name>.json``): ``batch``, ``seq``, ``ckpt_every``
(steps per save), ``setup_steps`` (where the window's call resumes),
``checked_steps`` (the steps compared), ``optimizer`` (AdamW's settings),
and optionally ``reference_rows``: the rows of each reference gradient
call, whose float32 activations bound the reference's memory (default 2;
a long-sequence cell sets 1 to fit one chip).
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
from .harness import RunRecord, Spans, compile_count
from .program import (Feed, adam_start, batch_at, block, check_layout,
                      model_config, patched, peak_bytes, spanned_manager)
from .reference import flat, init_weights, leaf_norms

FAULTS = ("unchanged", "half_batch", "altered_save")


class Recorder:
    """Wraps the compiled step: keeps what the comparison reads from the
    first steps, and host times at the points the window's metrics use.
    Every step has a global index; main's calls run them in order."""

    def __init__(self, fn, every: int, checked: int, fault: Optional[str]):
        self.fn, self.every, self.checked, self.fault = fn, every, checked, fault
        self.calls = 0
        self.losses: List[Any] = []
        self.m1: Optional[Dict[str, float]] = None
        self.delta: Optional[Dict[str, float]] = None
        self._p0 = None
        self.first_of_call = True
        self.t_first_done: List[float] = []
        self.t_save_step_done: List[float] = []

    def __call__(self, params, opt, batch):
        i = self.calls
        if i == 0:
            self._p0 = jax.tree.map(jnp.copy, params)
        if self.fault == "unchanged":
            keep = (params, opt)
            params, opt = jax.tree.map(jnp.copy, (params, opt))
            _, _, met = self.fn(params, opt, batch)
            params, opt = keep
        else:
            params, opt, met = self.fn(params, opt, batch)
        if i < self.checked:
            self.losses.append(met["loss"])
        if i == 0:
            self.m1 = jax.device_get(leaf_norms(opt["m"]))
        if i == self.checked - 1:
            self.delta = jax.device_get(leaf_norms(jax.tree.map(
                lambda a, b: a - b.astype(jnp.float32), opt["master"],
                self._p0)))
            self._p0 = None
        self.calls += 1
        if self.first_of_call:
            block(params)
            self.t_first_done.append(time.perf_counter())
            self.first_of_call = False
        elif self.calls % self.every == 0:
            block(params)
            self.t_save_step_done.append(time.perf_counter())
        return params, opt, met


class TrainSession:
    """``repro.launch.train.main`` with the benchmark's seams: the seeded
    start enters through ``reshard_restore`` as step 0 of an empty store,
    the seeded feed through ``SyntheticTokens``, and the step each call
    builds is wrapped in one ``Recorder`` that counts steps across calls."""

    def __init__(self, cell, seed: int, store: str, spans: Spans,
                 reports: List[Any], fault: Optional[str] = None):
        from repro.ckpt import CheckpointManager
        from repro.launch import train
        c, t = cell.config, cell.traffic
        self.cell, self.seed, self.spans, self.fault = cell, seed, spans, fault
        self.cfg = model_config(c)
        check_layout(c, self.cfg)
        B, S, self.every = t["batch"], t["seq"], t["ckpt_every"]
        spoil = None
        if fault == "half_batch":
            def spoil(b):      # rows of the first half stand in for the rest
                for k in ("tokens", "labels"):
                    b[k][B // 2:] = b[k][:B // 2]
                return b
        self.feed = Feed(seed, self.cfg.vocab, B, S, spoil)
        manager = spanned_manager(CheckpointManager, spans, reports)
        if fault == "altered_save":
            manager = _altering(manager)
        self.recorder: Optional[Recorder] = None
        self.warm_from: Optional[int] = None
        self.argv = ["--arch", self.cfg.name, "--batch", str(B),
                     "--seq", str(S), "--ckpt", store,
                     "--ckpt-every", str(self.every),
                     "--lr", str(t["optimizer"]["peak_lr"])]
        self.patch = functools.partial(
            patched, train, get_config=lambda arch: self.cfg,
            SyntheticTokens=self.feed, make_train_step=self._make_step,
            reshard_restore=self._restore, CheckpointManager=manager)

    def _make_step(self, *a, **k):
        from repro.train import make_train_step
        bundle = make_train_step(*a, **k)
        if self.warm_from is not None:
            return bundle
        if self.recorder is None:
            self.recorder = Recorder(bundle.fn, self.every,
                                     self.cell.traffic["checked_steps"],
                                     self.fault)
        self.recorder.fn, bundle.fn = bundle.fn, self.recorder
        return bundle

    def _restore(self, mgr, mesh, pspecs, ospecs=None, step=None):
        from repro.ckpt import reshard_restore
        if self.warm_from is not None or mgr.latest_step() is None:
            # the seeded start: step 0, or the last step before a warm-up
            put = functools.partial(_place, mesh)
            params = jax.tree.map(put, init_weights(self.cell.config,
                                                    self.seed), pspecs)
            opt = jax.tree.map(put, adam_start(params), ospecs)
            return params, opt, self.warm_from or 0
        with self.spans.span("bench.restore"):
            out = reshard_restore(mgr, mesh, pspecs, ospecs, step)
            block(out[:2])
        return out

    def main(self, steps: int):
        from repro.launch import train
        if self.recorder is not None:
            self.recorder.first_of_call = True
        with self.patch():
            return train.main(self.argv + ["--steps", str(steps)])

    def warm(self, steps: int) -> None:
        """Compile (or load) the program that ``main(steps)`` runs: one
        step from the seeded start at step ``steps - 1``, saving nothing."""
        from repro.launch import train
        self.warm_from = steps - 1
        try:
            with self.patch():
                train.main(self.argv + ["--steps", str(steps),
                                        "--ckpt-every", str(steps + 1)])
        finally:
            self.warm_from = None

    def readings(self) -> Dict[str, Any]:
        """What the comparison takes from the program's first steps."""
        r = self.recorder
        b1 = self.cell.traffic["optimizer"]["b1"]
        return {"loss": [float(x) for x in r.losses],
                "grad1": {k: float(v) / (1 - b1) for k, v in r.m1.items()},
                "delta": {k: float(v) for k, v in r.delta.items()}}


def run(cell, seed: int, seconds: float, traced: bool, work: str,
        tracer=None, fault: Optional[str] = None) -> RunRecord:
    t = cell.traffic
    B, S, every = t["batch"], t["seq"], t["ckpt_every"]
    rec = RunRecord(cell=cell)
    spans: Spans = rec.spans
    reports: List[Any] = []
    ses = TrainSession(cell, seed, os.path.join(work, "ckpt"), spans,
                       reports, fault)
    main, feed = ses.main, ses.feed
    main(every)
    t0 = time.perf_counter()
    main(t["setup_steps"])
    cycle_s = time.perf_counter() - t0 - spans.done["bench.restore"][-1]
    cycles = 1 if traced else max(1, round(seconds / cycle_s))
    first = t["setup_steps"]
    last = first + cycles * every
    ses.warm(last)
    n_feed = len(feed.requested)
    if tracer:
        tracer.start()
    compiles = compile_count()
    with spans.span("bench.window"):
        t_call = spans.mark("window_start")
        run_out = main(last)
        t_end = spans.mark("window_end")
    compiles = compile_count() - compiles
    if tracer:
        rec.trace = tracer.stop()
    r: Recorder = ses.recorder
    rec.end_to_end["resume_s"] = r.t_first_done[-1] - t_call
    rec.end_to_end["train_tokens_per_s"] = \
        (last - first - 1) * B * S / (t_end - r.t_first_done[-1])
    feeds = [tm for _, tm in feed.requested[n_feed:]]
    done = r.t_save_step_done[-cycles:]
    nxt = [next((f for f in feeds if f > d), t_end) for d in done]
    spans.done["bench.save_stall"] = [b - a for a, b in zip(done, nxt)]
    window_reports = [rep for kind, step, rep in reports if step > first]
    rec.counters.update(
        cycle_s=cycle_s, cycles=cycles, steps=last - first,
        tokens=(last - first - 1) * B * S,
        save_write_s=[rep.wall_seconds for kind, step, rep in reports
                      if kind == "incremental" and step > first],
        window_chunks_written=sum(x.chunks_written for x in window_reports),
        window_bytes_serialized=sum(x.bytes_serialized
                                    for x in window_reports))
    rec.attempted = last
    rec.memory_peak_bytes = peak_bytes()

    # ---- correctness, after the window, with the program's state freed
    mismatch = _store_mismatch(run_out)
    prog = ses.readings()
    del run_out, ses
    gc.collect()
    ref = reference_readings(cell.config, t, seed)
    numbers = compare.train_numbers(prog, ref, mismatch)
    rec.counters["readings"] = dict(numbers, window_compiles=compiles)
    rec.compared = compare.with_limits(numbers, cell.limits)
    return rec


def reference_readings(c, t, seed: int, precision: str = "f32"):
    from .reference import train_reference
    vocab = c.get("vocab_size")
    batches = [batch_at(seed, s, vocab, t["batch"], t["seq"])
               for s in range(t["checked_steps"])]
    return train_reference(c, t["optimizer"], init_weights(c, seed),
                           [(b["tokens"], b["labels"]) for b in batches],
                           precision=precision,
                           row_block=t.get("reference_rows", 2))


def _place(mesh, a, spec):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.device_put(a, NamedSharding(mesh, spec if spec is not None
                                           else P()))


def _store_mismatch(run_out) -> float:
    """Leaves of the state restored from the last save that differ, bit
    for bit, from the live state the window ended with."""
    run_out.manager.wait()
    params, opt, _ = run_out.manager.restore()
    got = {**flat(params, "params"), **flat(opt, "opt")}
    live = {**flat(run_out.params, "params"), **flat(run_out.opt_state, "opt")}
    if sorted(got) != sorted(live):
        return float(max(len(got), len(live)))
    bad = 0
    for k in live:
        a, b = np.asarray(live[k]), np.asarray(got[k])
        bad += not (a.shape == b.shape and a.dtype == b.dtype and
                    a.tobytes() == b.tobytes())
    return float(bad)


def _altering(Manager):
    """A fault for the tests: one value of the saved state is changed
    where the save payload is produced."""

    class Altering(Manager):
        def save(self, step, params, opt_state):
            params = dict(params)
            params["final_norm"] = np.asarray(params["final_norm"]) + 1.0
            return super().save(step, params, opt_state)

    return Altering
