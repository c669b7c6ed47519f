"""Mamba-2 (SSD — state-space duality) in pure jnp.

Chunked SSD algorithm (Dao & Gu 2024): the sequence is split into chunks of
length Q; within a chunk the recurrence is computed as a masked
attention-like quadratic form (MXU-friendly), and a (B, H, P, N) state is
carried across chunks with a lax.scan. Einsums keep the head-dim P as a free
axis so TP sharding over P is local.

``ssd_reference`` is the exact sequential recurrence (the oracle for both
the chunked path and the kernels/ssd_scan Pallas kernel).

Shapes:
    x   (B, S, H, P)    inputs per head
    dt  (B, S, H)       softplus-ed step sizes
    A   (H,)            negative decay rates
    Bc  (B, S, G, N)    input projections (groups broadcast over heads)
    Cc  (B, S, G, N)    output projections
    D   (H,)            skip connection
state: (B, H, P, N) float32.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _expand_groups(t: jax.Array, n_heads: int) -> jax.Array:
    """(B, ..., G, N) -> (B, ..., H, N) by repeating each group."""
    G = t.shape[-2]
    if G == n_heads:
        return t
    return jnp.repeat(t, n_heads // G, axis=-2)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, Bc: jax.Array,
                Cc: jax.Array, D: jax.Array, chunk: int = 128,
                h0: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    B, S, H, P = x.shape
    N = Bc.shape[-1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    nc = S // chunk

    G = Bc.shape[-2]
    rep = H // G
    dtf = dt.astype(jnp.float32)
    da = dtf * A.astype(jnp.float32)    # (B, S, H) — log-decay per step

    # reshape into chunks (B/C stay GROUPED — 1/rep the bytes of expansion)
    def ck(t):
        return t.reshape(B, nc, chunk, *t.shape[2:])
    xc, dtc = ck(x), ck(dtf)
    Bcc, Ccc = ck(Bc), ck(Cc)
    L = jnp.cumsum(ck(da), axis=2)      # (B, nc, Q, H) inclusive cum log-decay

    @jax.checkpoint     # recompute chunk internals in backward: saves only
    def body(h, inp):   # the (B,H,P,N) carry per chunk, not the QxQ scores
        xq, dtq, Bq, Cq, Lq = inp
        Bf, Cf = Bq.astype(jnp.float32), Cq.astype(jnp.float32)
        xf = xq.astype(jnp.float32)
        # intra-chunk quadratic form, grouped:
        # scores_hij = (C_gi . B_gj) * exp(L_hi - L_hj) * dt_hj  for i >= j
        cb = jnp.einsum("bign,bjgn->bgij", Cf, Bf)         # (B, G, i, j)
        decay = Lq[:, :, None, :] - Lq[:, None, :, :]      # (B, i, j, H)
        ii = jnp.arange(Lq.shape[1])
        causal = (ii[:, None] >= ii[None, :])[None, :, :, None]
        # mask BEFORE exp: above the diagonal decay > 0 and exp overflows
        # to inf once a chunk's decay sum passes ~88; where(mask, inf, 0)
        # is fine forward but its gradient is 0 * inf = NaN
        M = jnp.exp(jnp.where(causal, decay, -jnp.inf)) * \
            dtq[:, None, :, :]                             # (B, i, j, H)
        M = M.transpose(0, 3, 1, 2)                        # (B, H, i, j)
        cb_h = jnp.repeat(cb, rep, axis=1) if rep > 1 else cb  # (B,H,i,j)
        y_intra = jnp.einsum("bhij,bjhp->bihp", cb_h * M, xf)
        # inter-chunk: contribution of the incoming state
        y_inter = jnp.einsum("bign,bih,bhpn->bihp",
                             Cf, jnp.exp(Lq), h) if G == 1 else \
            jnp.einsum("bihn,bhpn->bihp",
                       jnp.repeat(Cf, rep, axis=2) *
                       jnp.exp(Lq)[..., None], h)
        # state update: h' = exp(L_Q) h + sum_j exp(L_Q - L_j) dt_j B_j x_j
        Lq_last = Lq[:, -1][:, None]                       # (B, 1, H)
        w = jnp.exp(Lq_last - Lq) * dtq                    # (B, Q, H)
        h_new = jnp.exp(Lq_last[:, 0])[..., None, None] * h + \
            (jnp.einsum("bjgn,bjh,bjhp->bhpn", Bf, w, xf) if G == 1 else
             jnp.einsum("bjhn,bjhp->bhpn",
                        jnp.repeat(Bf, rep, axis=2) * w[..., None], xf))
        return h_new, (y_intra + y_inter)

    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)
    # scan over chunks
    xs = (xc.transpose(1, 0, 2, 3, 4), dtc.transpose(1, 0, 2, 3),
          Bcc.transpose(1, 0, 2, 3, 4), Ccc.transpose(1, 0, 2, 3, 4),
          L.transpose(1, 0, 2, 3))
    h_final, ys = jax.lax.scan(body, h0, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S, H, P)
    y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), h_final


def ssd_reference(x, dt, A, Bc, Cc, D, h0=None):
    """Exact sequential recurrence — oracle (small shapes only)."""
    B, S, H, P = x.shape
    N = Bc.shape[-1]
    Bh = _expand_groups(Bc, H).astype(jnp.float32)
    Ch = _expand_groups(Cc, H).astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    h = jnp.zeros((B, H, P, N), jnp.float32) if h0 is None else h0

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp       # (B,H,P) (B,H) (B,H,N) (B,H,N)
        a = jnp.exp(dt_t * A.astype(jnp.float32))          # (B,H)
        h = h * a[..., None, None] + jnp.einsum(
            "bhn,bhp->bhpn", B_t * dt_t[..., None], x_t)
        y = jnp.einsum("bhpn,bhn->bhp", h, C_t)
        return h, y

    xs = (xf.transpose(1, 0, 2, 3), dtf.transpose(1, 0, 2),
          Bh.transpose(1, 0, 2, 3), Ch.transpose(1, 0, 2, 3))
    h, ys = jax.lax.scan(step, h, xs)
    y = ys.transpose(1, 0, 2, 3) + xf * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), h


def ssd_decode_step(h, x_t, dt_t, A, B_t, C_t, D):
    """One-token recurrence. h: (B,H,P,N) f32; x_t: (B,H,P); dt_t: (B,H);
    B_t/C_t: (B,G,N). Returns (h', y (B,H,P))."""
    H = x_t.shape[1]
    B_t = _expand_groups(B_t, H).astype(jnp.float32)
    C_t = _expand_groups(C_t, H).astype(jnp.float32)
    dtf = dt_t.astype(jnp.float32)
    xf = x_t.astype(jnp.float32)
    a = jnp.exp(dtf * A.astype(jnp.float32))
    h = h * a[..., None, None] + jnp.einsum("bhn,bhp->bhpn",
                                            B_t * dtf[..., None], xf)
    y = jnp.einsum("bhpn,bhn->bhp", h, C_t) + xf * \
        D.astype(jnp.float32)[None, :, None]
    return h, y.astype(x_t.dtype)


# ------------------------------------------------------------------ conv1d
def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B, S, *C); w: (*C, K); b: (*C,).

    The channel block *C may be multi-dim (e.g. (H, P)) so TP sharding on a
    channel sub-axis stays structural.
    """
    K = w.shape[-1]
    S = x.shape[1]
    pad = [(0, 0), (K - 1, 0)] + [(0, 0)] * (x.ndim - 2)
    xp = jnp.pad(x, pad)
    y = sum(xp[:, k:k + S] * w[..., k].astype(x.dtype) for k in range(K))
    return y + b.astype(x.dtype)


def causal_conv_step(state: jax.Array, x_t: jax.Array, w: jax.Array,
                     b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """state: (B, K-1, *C) last inputs; x_t: (B, *C). -> (state', y)."""
    full = jnp.concatenate([state, x_t[:, None]], axis=1)   # (B, K, *C)
    wt = jnp.moveaxis(w, -1, 0).astype(x_t.dtype)           # (K, *C)
    y = jnp.sum(full * wt[None], axis=1) + b.astype(x_t.dtype)
    return full[:, 1:], y
