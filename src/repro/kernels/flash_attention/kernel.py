"""Flash attention forward — Pallas TPU kernel.

TPU adaptation (not a CUDA port): the grid is (B, Hq, nQ, nK) with the KV
dimension innermost and SEQUENTIAL ("arbitrary" semantics) so the online
softmax accumulators (m, l, acc) live in VMEM scratch across KV steps; the
MXU sees (q_block x D) @ (D x kv_block) matmuls with both dims multiples of
128 (q_block/kv_block default 512/512, D >= 64). HBM->VMEM movement is
expressed with BlockSpecs: each grid step stages exactly one q block and
one kv block; Pallas double-buffers the streams automatically.

Causal skipping: blocks strictly above the diagonal are masked (their loads
still stream; the TPU cost model makes skipping loads via scalar prefetch a
second-order win at these block sizes — documented in DESIGN.md).

GQA is native: the q-head grid index maps to kv head h // G in the BlockSpec
index_map, so KV is never repeated in memory.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
               scale: float, causal: bool, window: Optional[int],
               q_block: int, kv_block: int, n_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (qb, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (kb, D)
    v = v_ref[0, 0].astype(jnp.float32)                  # (kb, Dv)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (qb, kb)

    q_pos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32,
                                                    s.shape, 0)
    kv_pos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32,
                                                      s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= q_pos >= kv_pos
    if window is not None:
        mask &= q_pos - kv_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]                                   # (qb,)
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * alpha + p.sum(axis=1)
    acc_sc[...] = acc_sc[...] * alpha[:, None] + \
        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0, 0] = (acc_sc[...] /
                       jnp.maximum(l_sc[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: Optional[int] = None,
                        scale: Optional[float] = None,
                        q_block: int = 512, kv_block: int = 512,
                        interpret: bool = False) -> jax.Array:
    """q: (B, Hq, S, D); k/v: (B, KVH, S, D). Returns (B, Hq, S, D)."""
    B, Hq, S, D = q.shape
    KVH = k.shape[1]
    Dv = v.shape[-1]
    G = Hq // KVH
    scale = scale if scale is not None else D ** -0.5
    q_block = min(q_block, S)
    while S % q_block:
        q_block //= 2
    kv_block = min(kv_block, S)
    while S % kv_block:
        kv_block //= 2
    nq, nk = S // q_block, S // kv_block

    grid = (B, Hq, nq, nk)
    kern = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, n_kv=nk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_block, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kv_block, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, kv_block, Dv),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, Dv),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
