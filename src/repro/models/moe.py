"""Mixture-of-Experts — sort-based capacity dispatch (Switch/Mixtral style).

The dispatch is *token-local*: it routes whatever token set it is given into
an (E, C, d) capacity buffer via sort + scatter, runs the expert FFNs as
batched einsums, and scatters results back. Under the production mesh the
block is invoked inside shard_map over the data axis (each data shard routes
its own tokens — no cross-device scatter), with expert weights TP-sharded on
their hidden dim over the model axis (psum over 'model' happens on the
*output* projection, same collective pattern as a dense TP FFN).

Tokens over capacity are dropped (standard capacity-factor routing); the
router aux loss (load-balancing, Switch eq. 4) is returned for the train
loss.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def route(x: jax.Array, w_router: jax.Array, top_k: int
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (T, d) -> (weights (T,k), experts (T,k) int32, aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, experts = jax.lax.top_k(probs, top_k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # Switch load-balance aux: E * sum_e f_e * p_e
    E = w_router.shape[-1]
    me = probs.mean(axis=0)                                  # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[experts.reshape(-1)].add(
        jnp.ones_like(experts.reshape(-1), jnp.float32))
    ce = ce / jnp.maximum(ce.sum(), 1.0)
    aux = E * jnp.sum(me * ce)
    return gate, experts, aux


def dispatch_indices(experts: jax.Array, n_experts: int, capacity: int
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sort-based capacity assignment.

    experts: (T, k) int32 -> returns (slot (T*k,), keep (T*k,), token (T*k,))
    where slot = expert * capacity + position-within-expert for kept entries.
    """
    T, k = experts.shape
    flat = experts.reshape(-1)                               # (T*k,)
    order = jnp.argsort(flat, stable=True)                   # group by expert
    sorted_e = flat[order]
    # position within expert = index - start offset of that expert
    ones = jnp.ones_like(sorted_e)
    pos_in_sorted = jnp.cumsum(ones) - 1
    counts = jnp.zeros((n_experts,), jnp.int32).at[sorted_e].add(1)
    starts = jnp.cumsum(counts) - counts                     # exclusive
    pos_in_expert = pos_in_sorted - starts[sorted_e]
    keep_sorted = pos_in_expert < capacity
    slot_sorted = sorted_e * capacity + jnp.minimum(pos_in_expert,
                                                    capacity - 1)
    inv = jnp.argsort(order, stable=True)                    # undo sort
    return slot_sorted[inv], keep_sorted[inv], jnp.arange(T * k) // k


def moe_ffn(x: jax.Array, w_router: jax.Array, w_gate: jax.Array,
            w_up: jax.Array, w_down: jax.Array, *, top_k: int,
            capacity_factor: float = 1.25, act: str = "swiglu",
            psum_axis: Optional[str] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (T, d); expert weights: (E, d, f) / (E, f, d).

    Returns (out (T, d), aux_loss). If ``psum_axis`` is given the caller is
    inside shard_map and w_down's output is partial-summed over that axis.
    """
    T, d = x.shape
    E = w_router.shape[-1]
    capacity = max(1, int(T * top_k * capacity_factor / E))

    gate, experts, aux = route(x, w_router, top_k)
    slot, keep, token = dispatch_indices(experts, E, capacity)

    # scatter tokens into the capacity buffer (dropped tokens write nowhere)
    buf = jnp.zeros((E * capacity, d), x.dtype)
    contrib = jnp.where(keep[:, None], x[token], 0).astype(x.dtype)
    safe_slot = jnp.where(keep, slot, E * capacity - 1)
    buf = buf.at[safe_slot].add(jnp.where(keep[:, None], contrib, 0))
    buf = buf.reshape(E, capacity, d)

    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(x.dtype))
    if act == "swiglu":
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(g, approximate=True) * u
    y = jnp.einsum("ecf,efd->ecd", h, w_down.astype(x.dtype))
    if psum_axis is not None:
        y = jax.lax.psum(y, psum_axis)
    y = y.reshape(E * capacity, d)

    # gather back with routing weights
    picked = jnp.where(keep[:, None], y[safe_slot], 0)
    weighted = picked * jnp.where(keep, gate.reshape(-1), 0)[:, None] \
        .astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[token].add(weighted)
    return out, aux


def moe_ffn_reference(x, w_router, w_gate, w_up, w_down, *, top_k,
                      act="swiglu"):
    """Dense oracle: every token through its top-k experts, no capacity
    drops. Tests compare moe_ffn against this with capacity_factor large
    enough that nothing drops."""
    gate, experts, aux = route(x, w_router, top_k)
    T, d = x.shape
    E = w_router.shape[-1]
    out = jnp.zeros((T, d), jnp.float32)
    for e in range(E):
        g = jnp.einsum("td,df->tf", x, w_gate[e].astype(x.dtype))
        u = jnp.einsum("td,df->tf", x, w_up[e].astype(x.dtype))
        h = jax.nn.silu(g) * u if act == "swiglu" else \
            jax.nn.gelu(g, approximate=True) * u
        y = jnp.einsum("tf,fd->td", h, w_down[e].astype(x.dtype))
        w_e = jnp.sum(jnp.where(experts == e, gate, 0.0), axis=-1)
        out = out + y.astype(jnp.float32) * w_e[:, None]
    return out.astype(x.dtype), aux


# ======================================================= held experts
# DeepSeek-V2's routed experts under expert parallelism: the router scores
# every routed expert, each token takes the greedy top-k of the softmax
# scores as its gates (not renormalised, times a scaling factor), and this
# chip computes the part of the result that the experts it holds give,
# for every assignment they receive: no capacity, nothing dropped.


def route_topk(x: jax.Array, w_router: jax.Array, top_k: int,
               scaling: float = 1.0
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (T, d) -> (gates (T,k) f32, experts (T,k) int32, scores (T,E))."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    scores = jax.nn.softmax(logits, axis=-1)
    gate, experts = jax.lax.top_k(scores, top_k)
    return gate * scaling, experts, scores


def seq_balance_loss(scores: jax.Array, experts: jax.Array) -> jax.Array:
    """DeepSeek-V2's sequence-wise balance loss. scores: (B, S, E) softmax
    scores; experts: (B, S, k) chosen experts. Per sequence,
    f_e = E / (k S) * #{t : e chosen at t} and P_e = mean_t s_{t,e}; the
    loss is the mean over sequences of sum_e f_e P_e."""
    B, S, E = scores.shape
    k = experts.shape[-1]
    counts = jax.nn.one_hot(experts.reshape(B, S * k), E,
                            dtype=jnp.float32).sum(1)            # (B, E)
    f = counts * (E / (k * S))
    return jnp.mean(jnp.sum(f * scores.mean(1), axis=-1))


@jax.custom_vjp
def _permute(x: jax.Array, order: jax.Array, inverse: jax.Array
             ) -> jax.Array:
    """Rows of x in ``order``; the gradient is a gather by ``inverse``
    (no scatter)."""
    return x[order]


def _permute_fwd(x, order, inverse):
    return x[order], inverse


def _permute_bwd(inverse, g):
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def held_experts(x: jax.Array, gate: jax.Array, experts: jax.Array,
                 w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                 offset: int = 0) -> Tuple[jax.Array, jax.Array]:
    """sum over the chosen experts held here of gate * SwiGLU_e(x).

    x: (T, d); gate, experts: (T, k) over all routed experts; w_*: the
    held experts' weights (E, d, f) / (E, f, d), experts
    [offset, offset + E). The assignments are sorted by held expert (the
    others last) and run as grouped matmuls (``lax.ragged_dot``) over the
    groups' sizes, so the work follows the assignments received. Returns
    (out (T, d), assignments per held expert (E,) int32)."""
    T, d = x.shape
    k = experts.shape[-1]
    E = w_gate.shape[0]
    local = experts.reshape(-1) - offset
    held = (local >= 0) & (local < E)
    key = jnp.where(held, local, E)
    order = jnp.argsort(key, stable=True)
    inverse = jnp.argsort(order)
    sizes = jnp.bincount(key, length=E + 1)[:E].astype(jnp.int32)
    # The grouped matmul leaves the rows past the groups undefined on the
    # TPU, in its result and in the gradient of its left operand. Select,
    # never multiply, them away on both sides of each matmul, so that
    # neither the result nor the gradient of x reads them.
    in_groups = (jnp.arange(T * k) < sizes.sum())[:, None]
    xs = _permute(jnp.repeat(x, k, axis=0), order, inverse)      # (T*k, d)
    xs = jnp.where(in_groups, xs, 0)
    dt = x.dtype

    def grouped(lhs, w):
        return jnp.where(in_groups,
                         jax.lax.ragged_dot(lhs, w.astype(dt), sizes), 0)

    y = grouped(jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up),
                w_down)
    y = _permute(y, inverse, order)                 # assignment order
    y = jnp.where(held[:, None], y, 0).reshape(T, k, d)
    w = jnp.where(held, gate.reshape(-1), 0.0).reshape(T, k)
    out = jnp.einsum("tkd,tk->td", y, w.astype(dt),
                     preferred_element_type=jnp.float32)
    return out.astype(dt), sizes
