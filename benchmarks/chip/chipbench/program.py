"""The seams through which the benchmark drives the program.

The program is imported from the checkout's ``src``. The benchmark hands
it a model configuration built from the configuration file, its seeded
weights and tokens, and takes spans around its calls. The names it
replaces in ``repro.launch.train`` while ``train.main`` runs are the
interface later changes keep: ``get_config``, ``SyntheticTokens``,
``make_train_step``, ``reshard_restore`` and ``CheckpointManager``.
"""
from __future__ import annotations

import contextlib
import os
import sys
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .harness import CHECKOUT, Spans, load_family, rng
from .reference import flat, layout


def import_program():
    """Put the checkout's ``src`` on the path; fails where it is absent."""
    src = os.path.join(CHECKOUT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise ImportError(f"the program is not in this checkout: {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401
    return repro


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file, built by its
    family file (``families/<family>.py``)."""
    return load_family(c["family"]).model_config(c)


def shared_fields(c: Dict[str, Any]) -> Dict[str, Any]:
    """The ``ModelConfig`` fields every family takes alike: the name and
    the precisions the configuration states."""
    prec = c["precision"]
    return dict(name=c["name"], param_dtype=prec["params"],
                compute_dtype=prec["compute"], logit_dtype=prec["logits"])


def check_layout(c: Dict[str, Any], cfg) -> None:
    """The program's parameter tree must be the layout the reference and
    the seeded weights use: same leaves, shapes and dtypes."""
    from repro.models import init_params
    got = flat(jax.eval_shape(lambda: init_params(cfg,
                                                  jax.random.PRNGKey(0))))
    want = layout(c)
    have = {k: (tuple(v.shape), str(v.dtype)) for k, v in got.items()}
    if have != want:
        diff = sorted(set(have.items()) ^ set(want.items()))
        raise RuntimeError(f"parameter layout differs: {diff[:6]}")


def batch_at(seed: int, step: int, vocab: int, batch: int, seq: int
             ) -> Dict[str, np.ndarray]:
    """The training batch of a step: uniform token ids over the
    vocabulary, from the seed and the step alone."""
    toks = rng(seed, 2, step).integers(0, vocab, (batch, seq + 1),
                                       dtype=np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:].copy(),
            "mask": np.ones((batch, seq), np.float32)}


class Feed:
    """Stands in for ``SyntheticTokens``: the same ``batch_at(step)``,
    from the benchmark's seed, with the host time of every request."""

    def __init__(self, seed: int, vocab: int, batch: int, seq: int,
                 spoil: Optional[Callable[[Dict[str, np.ndarray]],
                                          Dict[str, np.ndarray]]] = None):
        self.seed, self.vocab, self.batch, self.seq = seed, vocab, batch, seq
        self.spoil = spoil
        self.requested: List[tuple] = []

    def __call__(self, vocab, batch, seq, seed=0):   # the class's signature
        return self

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        self.requested.append((step, time.perf_counter()))
        b = batch_at(self.seed, step, self.vocab, self.batch, self.seq)
        return self.spoil(b) if self.spoil else b


@contextlib.contextmanager
def patched(module, **names) -> Iterator[None]:
    old = {k: getattr(module, k) for k in names}
    for k, v in names.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


def spanned_manager(base, spans: Spans, reports: List[Any]):
    """``CheckpointManager`` with a span around ``save`` and ``wait``, and
    each save's ``BuildReport`` kept (it is ready on the writer thread)."""

    class Manager(base):
        def save(self, step, params, opt_state):
            with spans.span("bench.ckpt_save_call"):
                return super().save(step, params, opt_state)

        def wait(self):
            with spans.span("bench.ckpt_wait"):
                return super().wait()

        def _save_incremental(self, step, payloads):
            rep = super()._save_incremental(step, payloads)
            reports.append(("incremental", step, rep))
            return rep

        def _save_full(self, step, payloads, fps=None):
            rep = super()._save_full(step, payloads, fps)
            reports.append(("full", step, rep))
            return rep

    return Manager


def block(tree) -> None:
    jax.tree.map(lambda a: a.block_until_ready(), tree)


def adam_start(params):
    """AdamW's starting state, made by the benchmark: float32 master copy
    and zero moments."""
    f32 = jax.tree.map(lambda a: jnp.array(a, jnp.float32, copy=True), params)
    zeros = jax.tree.map(jnp.zeros_like, f32)
    return {"step": jnp.zeros((), jnp.int32), "master": f32, "m": zeros,
            "v": jax.tree.map(jnp.zeros_like, f32)}


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))
