"""The train step's reported loss."""
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models import init_params, loss_fn
from repro.optim import init_opt_state
from repro.train import TrainConfig, make_train_step


def test_microbatched_step_reports_the_mean_loss():
    cfg = dataclasses.replace(get_smoke_config("yi-6b"),
                              param_dtype="float32", compute_dtype="float32")
    B, S = 4, 16
    key = jax.random.PRNGKey(3)
    params = init_params(cfg, key)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1),
             "mask": jnp.ones((B, S), jnp.float32)}
    loss = jax.jit(lambda p, b: loss_fn(cfg, p, b)[0])
    halves = [float(loss(params, jax.tree.map(lambda a: a[i:i + 2], batch)))
              for i in (0, 2)]
    assert abs(halves[0] - halves[1]) > 0.05     # the two are told apart
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        step = make_train_step(cfg, TrainConfig(microbatches=2), mesh, B, S)
        _, _, metrics = step.fn(params, init_opt_state(params), batch)
    assert abs(float(metrics["loss"]) - sum(halves) / 2) < 1e-5
