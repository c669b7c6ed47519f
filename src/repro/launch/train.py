"""Training launcher: --arch <id> end-to-end driver.

Wires together the full production stack: mesh, sharded train step,
deterministic data pipeline, incremental (code-injection) checkpointing,
watchdog + restart-resume. ``--smoke`` runs a reduced config
(examples/quickstart.py on the CPU); ``chip_smoke.py`` at the repo root
runs the published-width configs on a TPU — nothing here is CPU-specific.
Called in-process, ``main(argv)`` returns a ``TrainRun``.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \\
        --steps 50 --batch 8 --seq 64 --ckpt /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np

from .. import obs
from ..ckpt import CheckpointManager, CheckpointPolicy, reshard_restore
from ..configs import get_config, get_smoke_config
from ..data import SyntheticTokens, make_global_batch
from ..ft import Watchdog
from ..models import init_params
from ..optim import AdamWConfig, init_opt_state
from ..train import TrainConfig, make_train_step
from .compile_cache import enable_compile_cache
from .mesh import make_mesh


@dataclass
class TrainRun:
    """What ``main`` leaves behind for an in-process caller: the final
    device-resident state, the last step's metrics (None when no step
    ran) and the checkpoint manager, whose ``last_report`` describes the
    last save."""
    params: Any
    opt_state: Any
    metrics: Optional[Dict[str, Any]]
    manager: Optional[CheckpointManager]


def _specs(shardings):
    return jax.tree.map(lambda s: s.spec, shardings)


def _closing_line(totals: Dict[str, Dict[str, float]]) -> str:
    """The run in one line, from its spans: the loop's seconds per step
    outside the saves (the D2H copy and the save call), then per save the
    seconds of each phase of the save."""
    def sec(name: str) -> float:
        return totals.get(name, {}).get("seconds", 0.0)

    steps = int(totals.get("train.loop", {}).get("steps", 0))
    outside = sec("train.loop") - sec("ckpt.d2h") - sec("ckpt.save")
    line = f"[train] {steps} steps, {outside / max(steps, 1):.3f} s/step " \
        "outside saves"
    saves = totals.get("ckpt.save", {}).get("n", 0)
    if saves:
        line += f"; per save ({saves}): " + ", ".join(
            f"{label} {sec(name) / saves:.2f} s" for label, name in (
                ("d2h", "ckpt.d2h"), ("wait", "ckpt.wait"),
                ("diff", "ckpt.diff"), ("write", "ckpt.write"),
                ("commit", "store.commit")))
    return line


def main(argv: Optional[Sequence[str]] = None) -> TrainRun:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mesh", default="1x1",
                    help="DATAxMODEL, e.g. 16x16")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--incremental", action="store_true", default=True)
    ap.add_argument("--full-ckpt", dest="incremental", action="store_false")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--watchdog-s", type=float, default=0.0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    tcfg = TrainConfig(adamw=AdamWConfig(peak_lr=args.lr,
                                         decay_steps=max(args.steps, 10)))

    mgr = None
    if args.ckpt:
        mgr = CheckpointManager(
            args.ckpt, cfg.name,
            CheckpointPolicy(every_steps=args.ckpt_every,
                             incremental=args.incremental,
                             async_write=True))

    ds = SyntheticTokens(cfg.vocab, batch=args.batch, seq=args.seq)
    t_start = time.perf_counter()
    with jax.set_mesh(mesh):
        with obs.span("train.build") as build:
            bundle = make_train_step(cfg, tcfg, mesh, args.batch, args.seq)
        # state is created (or restored) straight into the step's
        # shardings: nothing lands whole on one device first
        p_sh, o_sh = bundle.in_shardings[:2]
        restored = reshard_restore(mgr, mesh, _specs(p_sh), _specs(o_sh)) \
            if mgr else None
        if restored is not None:
            params, opt, start_step = restored
            print(f"[train] resumed from step {start_step}")
        else:
            params = jax.jit(lambda: init_params(cfg, jax.random.PRNGKey(0)),
                             out_shardings=p_sh)()
            opt = jax.jit(init_opt_state, out_shardings=o_sh)(params)
            start_step = 0
        wd = Watchdog(args.watchdog_s, lambda: print("[watchdog] step hung")) \
            if args.watchdog_s > 0 else None
        metrics = None
        bspec = {k: bundle.in_shardings[2][k].spec
                 for k in ("tokens", "labels", "mask")}
        with obs.span("train.loop") as loop:
            loop.count(steps=args.steps - start_step)
            for s in range(start_step, args.steps):
                with jax.profiler.StepTraceAnnotation("train", step_num=s):
                    batch = make_global_batch(mesh, bspec, ds.batch_at(s))
                    if wd:
                        wd.arm()
                    before = obs.compiles() if s == start_step else None
                    params, opt, metrics = bundle.fn(params, opt, batch)
                    if before is not None:
                        # the first call traces, lowers and compiles the
                        # step (or loads it from the persistent cache)
                        c = obs.compiles() - before
                        build.count(trace_s=c.trace_s, lower_s=c.lower_s,
                                    compile_s=c.compile_s,
                                    compiles=c.compiles)
                    if wd:
                        wd.disarm()
                    if (s + 1) % max(1, args.steps // 20) == 0 or \
                            s == start_step:
                        print(f"[train] step {s + 1}: "
                              f"loss={float(metrics['loss']):.4f} "
                              f"lr={float(metrics['lr']):.2e} "
                              f"gnorm={float(metrics['grad_norm']):.3f}")
                    if mgr and (s + 1) % args.ckpt_every == 0:
                        with obs.span("ckpt.d2h", step=s + 1) as d2h:
                            host = jax.tree.map(np.asarray, (params, opt))
                            d2h.count(bytes=sum(
                                a.nbytes for a in jax.tree.leaves(host)))
                        mgr.save(s + 1, *host)
        if mgr:
            mgr.wait()
        print(_closing_line(obs.summary(since=t_start)))
    return TrainRun(params, opt, metrics, mgr)


if __name__ == "__main__":
    main()
