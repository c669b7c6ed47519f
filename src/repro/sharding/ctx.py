"""Activation-sharding context.

Model code is written once, distribution-agnostic. Inside a step function
the launcher installs a rule table (name -> PartitionSpec); ``constrain``
then pins named activations with with_sharding_constraint. Outside any
context (unit tests, CPU examples) it is a no-op.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Optional

import jax
from jax.sharding import PartitionSpec

_RULES: contextvars.ContextVar[Optional[Dict[str, PartitionSpec]]] = \
    contextvars.ContextVar("activation_rules", default=None)


@contextlib.contextmanager
def activation_ctx(rules: Dict[str, PartitionSpec]):
    token = _RULES.set(rules)
    try:
        yield
    finally:
        _RULES.reset(token)


def constrain(x: jax.Array, name: str) -> jax.Array:
    rules = _RULES.get()
    if rules is None or name not in rules:
        return x
    spec = rules[name]
    if spec is None:
        return x
    # pad the spec with None up to the array rank
    spec = PartitionSpec(*(tuple(spec) + (None,) * (x.ndim - len(spec))))
    return jax.lax.with_sharding_constraint(x, spec)
