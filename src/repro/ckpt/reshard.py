"""Elastic reshard-restore: load a checkpoint onto a DIFFERENT mesh.

Checkpoints store logical (unsharded) tensors chunk-addressed, so restoring
onto any mesh is a placement decision, not a data transformation: each
device materializes its shard by assembling only the chunks that overlap
its slice (here: full assembly + device_put, single-process; the chunk
store is what makes the per-host read O(shard) at real scale).

This is the node-failure story: lose devices -> rebuild a smaller mesh ->
reshard-restore -> continue (examples/elastic_restart.py).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from .manager import CheckpointManager


def reshard_restore(mgr: CheckpointManager, mesh: Mesh, param_spec_tree,
                    opt_spec_tree=None, step: Optional[int] = None):
    """Restore + place: returns (params, opt_state, step) with leaves
    device_put against the given mesh/specs."""
    with obs.span("ckpt.restore"):
        out = mgr.restore(step)
        if out is None:
            return None
        params, opt_state, saved_step = out

        def place(tree, specs):
            if specs is None:
                return jax.tree.map(jax.device_put, tree)
            return jax.tree.map(
                lambda a, s: jax.device_put(
                    a, NamedSharding(mesh, s if s is not None else P())),
                tree, specs)

        # the transfers are dispatched here and finish asynchronously
        with obs.span("ckpt.place"):
            params = place(params, param_spec_tree)
            if opt_spec_tree is not None:
                opt_state = place(opt_state, opt_spec_tree)
    return params, opt_state, saved_step
