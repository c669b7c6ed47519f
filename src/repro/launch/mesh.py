"""Production mesh construction.

Single pod: (16, 16) = ("data", "model") — 256 chips (TPU v5e pod slice).
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips; the "pod"
axis carries only data parallelism + the inter-pod gradient all-reduce
(DCN-friendly: one collective per step crosses pods).

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run forces 512 host devices *before* first init).
Every axis is ``AxisType.Auto``: shardings come from the step's
in/out_shardings and the activation rules, not from explicit-axis typing.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Elastic-scaling entry: any (data, model) factorization of the
    currently-alive device set (see ft/elastic.py)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
