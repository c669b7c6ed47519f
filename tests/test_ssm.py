"""SSD (Mamba-2) math: chunked vs sequential, conv, decode continuation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.ssm import (causal_conv, causal_conv_step, ssd_chunked,
                              ssd_decode_step, ssd_reference)


def rand_inputs(key, B, S, H, P, G, N):
    ks = jax.random.split(key, 6)
    return (jax.random.normal(ks[0], (B, S, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))),
            -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5),
            jax.random.normal(ks[3], (B, S, G, N)) * 0.3,
            jax.random.normal(ks[4], (B, S, G, N)) * 0.3,
            jax.random.normal(ks[5], (H,)) * 0.1)


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (2, 64, 3, 8, 1, 16, 8), (2, 64, 3, 8, 1, 16, 64),
    (1, 96, 4, 16, 2, 8, 32), (2, 33, 5, 4, 1, 8, 16),   # ragged S
])
def test_chunked_matches_sequential(B, S, H, P, G, N, chunk):
    x, dt, A, Bc, Cc, D = rand_inputs(jax.random.PRNGKey(0), B, S, H, P, G, N)
    y_ref, h_ref = ssd_reference(x, dt, A, Bc, Cc, D)
    y, h = ssd_chunked(x, dt, A, Bc, Cc, D, chunk=chunk)
    assert np.abs(np.asarray(y - y_ref)).max() < 2e-5
    assert np.abs(np.asarray(h - h_ref)).max() < 2e-5


def test_chunked_grad_finite_under_strong_decay():
    """A chunk whose summed log-decay passes ~88 overflows exp above the
    causal diagonal; the mask must keep that inf out of the gradient."""
    B, S, H, P, G, N = 1, 64, 2, 4, 1, 8
    x, dt, A, Bc, Cc, D = rand_inputs(jax.random.PRNGKey(3), B, S, H, P, G, N)
    A = jnp.full((H,), -8.0)          # |sum dt*A| over 64 steps >> 88

    def loss(dt):
        return ssd_chunked(x, dt, A, Bc, Cc, D, chunk=64)[0].sum()

    assert np.isfinite(np.asarray(jax.grad(loss)(dt))).all()


def test_decode_continues_prefill_state():
    B, S, H, P, G, N = 2, 48, 3, 8, 1, 16
    x, dt, A, Bc, Cc, D = rand_inputs(jax.random.PRNGKey(1), B, S, H, P, G, N)
    y_full, h_full = ssd_reference(x, dt, A, Bc, Cc, D)
    # prefill on first S-4, then 4 decode steps
    Sp = S - 4
    _, h = ssd_chunked(x[:, :Sp], dt[:, :Sp], A, Bc[:, :Sp], Cc[:, :Sp], D,
                       chunk=16)
    ys = []
    for t in range(Sp, S):
        h, y_t = ssd_decode_step(h, x[:, t], dt[:, t], A, Bc[:, t],
                                 Cc[:, t], D)
        ys.append(y_t)
    y_dec = jnp.stack(ys, axis=1)
    assert np.abs(np.asarray(y_dec - y_full[:, Sp:])).max() < 2e-5
    assert np.abs(np.asarray(h - h_full)).max() < 2e-5


def test_conv_train_vs_step():
    B, S, H, P, K = 2, 40, 3, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (B, S, H, P))
    w = jax.random.normal(ks[1], (H, P, K)) * 0.3
    b = jax.random.normal(ks[2], (H, P)) * 0.1
    y = causal_conv(x, w, b)
    st = jnp.zeros((B, K - 1, H, P))
    outs = []
    for t in range(S):
        st, yt = causal_conv_step(st, x[:, t], w, b)
        outs.append(yt)
    assert np.abs(np.asarray(jnp.stack(outs, 1) - y)).max() < 1e-5
