"""Serving launcher: load a layered image (with cross-variant dedup) and
serve batched requests.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \\
        --store /tmp/ckpt --batch 4 --prompt-len 16 --steps 16
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..ckpt import CheckpointManager, CheckpointPolicy
from ..configs import get_config, get_smoke_config
from ..models import init_params
from ..serve import Engine, GenerationResult
from .compile_cache import enable_compile_cache


def main(argv: Optional[Sequence[str]] = None) -> GenerationResult:
    """Serve one batch of synthetic requests; returns the generation (so
    an in-process caller can check the tokens)."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--store", default=None,
                    help="layered checkpoint store to load weights from")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.store:
        mgr = CheckpointManager(args.store, cfg.name,
                                CheckpointPolicy(async_write=False))
        out = mgr.restore()
        if out is None:
            raise SystemExit(f"no checkpoint in {args.store}")
        params = jax.tree.map(jnp.asarray, out[0])
        print(f"[serve] loaded step-{out[2]} from layered store")
    else:
        params = init_params(cfg, jax.random.PRNGKey(0))

    eng = Engine(cfg, params,
                 max_len=args.prompt_len + args.steps + 8)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab))
    t0 = time.perf_counter()
    res = eng.generate(prompts, steps=args.steps,
                       temperature=args.temperature)
    dt = time.perf_counter() - t0
    toks = res.tokens.size
    print(f"[serve] generated {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s)")
    print("[serve] first sequences:", res.tokens[:2, :8].tolist())
    return res


if __name__ == "__main__":
    main()
