"""Distribution tests on 8 forced host devices (subprocess: the main test
process must keep 1 device for everything else)."""
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.models import init_params, loss_fn
        from repro.optim import init_opt_state
        from repro.train import TrainConfig, make_train_step

        cfg = get_smoke_config("yi-6b")
        mesh = make_mesh((2, 4), ("data", "model"))
        B, S = 8, 32
        key = jax.random.PRNGKey(0)
        params = init_params(cfg, key)
        opt = init_opt_state(params)
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
        batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1),
                 "mask": jnp.ones((B, S), jnp.float32)}
        # unsharded reference loss
        ref_loss = float(loss_fn(cfg, params, batch)[0])
        with jax.set_mesh(mesh):
            bundle = make_train_step(cfg, TrainConfig(microbatches=1),
                                     mesh, B, S)
            p2, o2, metrics = bundle.fn(params, opt, batch)
        got = float(metrics["loss"])
        assert abs(got - ref_loss) < 5e-2, (got, ref_loss)
        assert np.isfinite(float(metrics["grad_norm"]))
        print("OK", got, ref_loss)
    """)
    assert "OK" in out


def test_microbatched_equals_full_batch_grads():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.models import init_params
        from repro.optim import init_opt_state
        from repro.train import TrainConfig, make_train_step

        cfg = get_smoke_config("musicgen-medium")
        mesh = make_mesh((2, 4), ("data", "model"))
        B, S = 8, 16
        key = jax.random.PRNGKey(1)
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
        outs = []
        for nm in (1, 4):
            # fresh state per run (bundle.fn donates its inputs), created
            # OUTSIDE the mesh context so jit reshards uncommitted arrays
            params = init_params(cfg, key)
            opt = init_opt_state(params)
            batch = {"tokens": tokens,
                     "labels": jnp.roll(tokens, -1, 1),
                     "mask": jnp.ones((B, S), jnp.float32)}
            with jax.set_mesh(mesh):
                bundle = make_train_step(cfg, TrainConfig(microbatches=nm),
                                         mesh, B, S)
                p2, _, m = bundle.fn(params, opt, batch)
            outs.append(p2)
        d = max(float(jnp.abs(a.astype(jnp.float32) -
                              b.astype(jnp.float32)).max())
                for a, b in zip(jax.tree.leaves(outs[0]),
                                jax.tree.leaves(outs[1])))
        assert d < 3e-2, d    # bf16 params; microbatch loss-mean != exact
        print("OK", d)
    """)
    assert "OK" in out


def test_compressed_psum_matches_mean():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.optim import compressed_psum

        mesh = make_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 4096))
        err = jnp.zeros((8, 4096))

        def f(g, e):
            mean, new_e = compressed_psum(g[0], e[0], ("data",))
            return mean[None], new_e[None]

        fm = jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")))
        mean, new_err = fm(g, err)
        ref = jnp.mean(g, axis=0)
        got = np.asarray(mean[0])
        scale = float(jnp.abs(g).max()) / 127.0
        assert np.abs(got - np.asarray(ref)).max() < 2 * scale
        # error feedback: err ~= what quantization lost
        assert np.isfinite(np.asarray(new_err)).all()
        print("OK")
    """)
    assert "OK" in out


def test_multipod_mesh_and_decode_cell():
    """End-to-end mini dry-run inside the test suite (64 fake devices)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.models import init_cache, init_params
        from repro.train import make_decode_step

        cfg = get_smoke_config("mixtral-8x7b")
        mesh = make_mesh((2, 4, 8), ("pod", "data", "model"))
        B, C = 8, 64
        with jax.set_mesh(mesh):
            bundle = make_decode_step(cfg, mesh, B, C)
            pshape = bundle.abstract_inputs[0]
            cshape = bundle.abstract_inputs[1]
            toks = jax.ShapeDtypeStruct((B,), jnp.int32)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            compiled = bundle.fn.lower(pshape, cshape, toks, pos).compile()
            ca = compiled.cost_analysis()
            print("OK", ca.get("flops", 0) > 0)
    """, n=64)
    assert "OK True" in out


def test_moe_local_shard_map_matches_unsharded():
    """granite-style fully-local MoE (shard_map + replicated experts) must
    compute the same loss as the unsharded model (capacity effects differ
    only when shards drop different tokens — use ample capacity)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.models import init_params, loss_fn
        from repro.sharding.ctx import activation_ctx
        from repro.sharding.rules import (Recipe, activation_rules,
                                          batch_specs, param_specs_tree)
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_smoke_config("granite-moe-3b-a800m").replace(
            capacity_factor=8.0)
        mesh = make_mesh((2, 4), ("data", "model"))
        B, S = 8, 32
        key = jax.random.PRNGKey(0)
        params = init_params(cfg, key)
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
        batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1),
                 "mask": jnp.ones((B, S), jnp.float32)}
        ref = float(loss_fn(cfg, params, batch)[0])

        recipe = Recipe("sp", "train")   # the granite full-config recipe
        arules = activation_rules(cfg, recipe, mesh, B)
        assert arules.get("moe_local") is not None, "moe_local rule missing"
        pspec = param_specs_tree(cfg, recipe, mesh,
                                 jax.eval_shape(lambda: params))
        named = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                             is_leaf=lambda x: isinstance(x, P))

        def f(p, b):
            with activation_ctx(arules):
                return loss_fn(cfg, p, b)[0]

        with jax.set_mesh(mesh):
            got = float(jax.jit(f, in_shardings=(named, {
                k: NamedSharding(mesh, s) for k, s in
                batch_specs(cfg, recipe, mesh, B).items()}))(params, batch))
        assert abs(got - ref) < 5e-2, (got, ref)
        print("OK", got, ref)
    """)
    assert "OK" in out
