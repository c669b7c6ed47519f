"""DeepSeek-V2-Lite's pieces against direct transcriptions: YaRN's
frequencies and scale, dropless routing over the held experts (with the
grouped matmul's rows past its groups undefined, as on the TPU), the shares
of an expert-parallel layer adding up to the whole layer, the
sequence-wise balance loss, the counters of the train step and loop, and
the checkpoint's binning of the leading dense layers."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, get_smoke_config
from repro.models import init_params
from repro.models.blocks import _routed_ffn
from repro.models.layers import yarn_freqs, yarn_mscale
from repro.models.moe import held_experts, route_topk, seq_balance_loss

ARCH = "deepseek-v2-lite"


def published_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's inv_freq, line by line, float64."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / \
            (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def test_yarn_frequencies_and_scale_follow_the_published_code():
    cfg = get_config(ARCH)
    got = yarn_freqs(cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn_factor,
                     cfg.yarn_original_len, cfg.yarn_beta_fast,
                     cfg.yarn_beta_slow)
    want = published_yarn(64, 10000.0, 40.0, 4096, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # correction range [10, 23]: the fastest ten pairs extrapolate, the
    # slowest interpolate by the factor
    np.testing.assert_allclose(got[:10], 10000.0 ** (-np.arange(0, 20, 2)
                                                     / 64), rtol=1e-6)
    np.testing.assert_allclose(got[23:], 10000.0 ** (-np.arange(46, 64, 2)
                                                     / 64) / 40, rtol=1e-6)
    m = yarn_mscale(40.0, 0.707)
    assert m == pytest.approx(1.26080, abs=1e-5)
    assert m * m == pytest.approx(1.58963, abs=1e-5)
    assert yarn_mscale(1.0, 0.707) == 1.0


def expert_weights(key, E, d, f):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (E, d, f)) * d ** -0.5,
            jax.random.normal(ks[1], (E, d, f)) * d ** -0.5,
            jax.random.normal(ks[2], (E, f, d)) * f ** -0.5)


def dense_oracle(x, gate, experts, wg, wu, wd, offset):
    """Every token through every held expert, weighted by its gate where
    the expert was chosen."""
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(wg.shape[0]):
        y = (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
        w = jnp.sum(jnp.where(experts == offset + e, gate, 0.0), -1)
        out = out + y * w[:, None]
    return out


@pytest.mark.parametrize("offset", [0, 8, 56])
def test_held_experts_are_dropless_and_follow_the_dense_oracle(offset):
    T, d, f, E, E_all, k = 96, 16, 32, 8, 64, 6
    key = jax.random.PRNGKey(offset)
    x = jax.random.normal(key, (T, d))
    w_router = jax.random.normal(jax.random.fold_in(key, 1), (d, E_all))
    wg, wu, wd = expert_weights(jax.random.fold_in(key, 2), E, d, f)
    gate, experts, scores = route_topk(x, w_router, k, scaling=1.0)
    # gates are the top-k softmax scores, not renormalised
    np.testing.assert_allclose(gate, jnp.sort(scores, -1)[:, ::-1][:, :k],
                               rtol=1e-6)
    assert float(gate.sum(-1).max()) < 1.0
    out, sizes = held_experts(x, gate, experts, wg, wu, wd, offset)
    want = dense_oracle(x, gate, experts, wg, wu, wd, offset)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    local = np.asarray(experts) - offset
    np.testing.assert_array_equal(
        sizes, np.bincount(local[(local >= 0) & (local < E)], minlength=E))


RAGGED_DOT = jax.lax.ragged_dot


def undefined_past_the_groups(lhs, rhs, group_sizes):
    """lax.ragged_dot whose rows past the groups are NaN in the result and
    in the gradient of lhs, as the TPU leaves them undefined."""
    past = (jnp.arange(lhs.shape[0]) >= group_sizes.sum())[:, None]

    @jax.custom_vjp
    def rd(lhs, rhs):
        return jnp.where(past, jnp.nan, RAGGED_DOT(lhs, rhs, group_sizes))

    def fwd(lhs, rhs):
        return rd(lhs, rhs), (lhs, rhs)

    def bwd(res, ct):
        _, vjp = jax.vjp(lambda a, b: RAGGED_DOT(a, b, group_sizes), *res)
        d_lhs, d_rhs = vjp(jnp.where(past, 0.0, ct))
        return jnp.where(past, jnp.nan, d_lhs), d_rhs

    rd.defvjp(fwd, bwd)
    return rd(lhs, rhs)


def test_rows_past_the_groups_reach_neither_result_nor_gradient(
        monkeypatch):
    """With the grouped matmul's rows past the groups undefined (NaN here),
    the result and every gradient are finite and equal the oracle's."""
    T, d, f, E, E_all, k = 48, 16, 32, 8, 64, 6
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (T, d))
    w_router = jax.random.normal(jax.random.fold_in(key, 1), (d, E_all))
    wg, wu, wd = expert_weights(jax.random.fold_in(key, 2), E, d, f)
    gate, experts, _ = route_topk(x, w_router, k)
    ct = jax.random.normal(jax.random.fold_in(key, 3), (T, d))

    def loss(held_fn, *args):
        out = held_fn(*args[:2], experts, *args[2:], 0)
        if isinstance(out, tuple):
            out = out[0]
        return jnp.sum(out * ct)

    want = jax.grad(loss, argnums=(1, 2, 3, 4, 5))(
        dense_oracle, x, gate, wg, wu, wd)
    monkeypatch.setattr(jax.lax, "ragged_dot", undefined_past_the_groups)
    got = jax.grad(loss, argnums=(1, 2, 3, 4, 5))(
        held_experts, x, gate, wg, wu, wd)
    for g, w in zip(got, want):
        assert bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_a_skewed_router_drops_nothing():
    """Every token picks the same held experts: far past any capacity
    factor, and every assignment is still computed."""
    T, d, f, E, k = 64, 8, 16, 8, 6
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (T, d))
    wg, wu, wd = expert_weights(key, E, d, f)
    experts = jnp.tile(jnp.arange(k, dtype=jnp.int32), (T, 1))
    gate = jnp.full((T, k), 0.1)
    out, sizes = held_experts(x, gate, experts, wg, wu, wd, 0)
    np.testing.assert_array_equal(sizes, [T] * k + [0] * (E - k))
    np.testing.assert_allclose(
        out, dense_oracle(x, gate, experts, wg, wu, wd, 0),
        rtol=1e-4, atol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Offsets 0, 8, ..., 56 of a 64-expert layer: the held parts of the
    eight shares, with the shared experts counted once, are the whole
    layer (float32)."""
    cfg = get_smoke_config(ARCH).replace(
        n_experts=64, n_routed_experts=64, top_k=6, expert_offset=0,
        param_dtype="float32", compute_dtype="float32")
    p = jax.tree.map(lambda a: a[0],
                     init_params(cfg, jax.random.PRNGKey(3))["blocks"])
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 12, cfg.d_model))
    whole, aux = _routed_ffn(cfg, p, h)
    shared = whole - _routed_ffn(cfg, dict(p, shared_down=jnp.zeros_like(
        p["shared_down"])), h)[0]
    total, assigned = shared, 0.0
    for off in range(0, 64, 8):
        share_cfg = cfg.replace(n_experts=8, expert_offset=off)
        share_p = dict(p, **{k: p[k][off:off + 8]
                             for k in ("w_gate", "w_up", "w_down")})
        y, a = _routed_ffn(share_cfg, share_p, h)
        total = total + (y - shared)
        assigned += float(a["assignments"])
        # every chip computes the router and its balance loss alike
        assert float(a["balance"]) == pytest.approx(float(aux["balance"]))
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    assert assigned == float(aux["assignments"]) == 2 * 12 * 6


def published_seq_aux(scores, topk_idx, E, k):
    """DeepSeek-V2's MoEGate seq_aux branch (scatter_add form), numpy."""
    B, S, _ = scores.shape
    ce = np.zeros((B, E))
    for b in range(B):
        for e in topk_idx[b].reshape(-1):
            ce[b, e] += 1.0
    ce /= S * k / E
    return float((ce * scores.mean(1)).sum(1).mean())


def test_sequence_wise_balance_loss():
    B, S, E, k = 3, 10, 16, 4
    logits = jax.random.normal(jax.random.PRNGKey(5), (B, S, E))
    scores = jax.nn.softmax(logits, -1)
    _, idx = jax.lax.top_k(scores, k)
    got = float(seq_balance_loss(scores, idx))
    assert got == pytest.approx(
        published_seq_aux(np.asarray(scores), np.asarray(idx), E, k),
        rel=1e-5)
    # a uniform router with an even assignment reads exactly 1
    flat_scores = jnp.full((1, E, E), 1.0 / E)
    even = jnp.arange(E)[:, None].repeat(k, 1)[None] % E
    assert float(seq_balance_loss(flat_scores, even)) == pytest.approx(1.0)


def smoke_batch(cfg, B, S, seed=0):
    t = np.random.default_rng(seed).integers(0, cfg.vocab, (B, S + 1),
                                             dtype=np.int32)
    return {"tokens": t[:, :-1], "labels": t[:, 1:],
            "mask": np.ones((B, S), np.float32)}


def test_step_counters_add_up_over_microbatches():
    from repro.launch.mesh import make_mesh
    from repro.optim import init_opt_state
    from repro.train import TrainConfig, make_train_step
    cfg = get_smoke_config(ARCH)
    mesh = make_mesh((1, 1), ("data", "model"))
    batch = smoke_batch(cfg, 4, 16)
    out = {}
    with jax.set_mesh(mesh):
        for n in (1, 2):
            bundle = make_train_step(cfg, TrainConfig(microbatches=n), mesh,
                                     4, 16)
            params = init_params(cfg, jax.random.PRNGKey(0))
            _, _, met = bundle.fn(params, init_opt_state(params), batch)
            out[n] = met
    # 4 MoE layers, top-3 of 8 routed experts, 4 held
    assert 0 < float(out[1]["moe_assignments"]) <= 4 * 16 * 3 * 4
    assert float(out[2]["moe_assignments"]) == \
        float(out[1]["moe_assignments"])
    # each microbatch's busiest expert: together at least the whole
    # batch's
    assert float(out[1]["moe_max_load"]) <= float(out[2]["moe_max_load"]) \
        <= float(out[2]["moe_assignments"])
    assert float(out[2]["loss"]) == pytest.approx(float(out[1]["loss"]),
                                                  rel=1e-2)


def test_train_loop_records_the_moe_span_at_each_save(tmp_path):
    from repro.launch import train
    t0 = obs.records("train.moe")[-1].end if obs.records("train.moe") \
        else 0.0
    run = train.main(["--arch", ARCH, "--smoke", "--steps", "4",
                      "--batch", "2", "--seq", "16", "--ckpt",
                      str(tmp_path / "ckpt"), "--ckpt-every", "2"])
    spans = [s for s in obs.records("train.moe") if s.start > t0]
    assert [s.ids["step"] for s in spans] == [2, 4]
    for s in spans:
        assert s.counts["tokens"] == 32
        assert 0 < s.counts["assignments_held"] <= 32 * 3 * 4
        # the busiest held expert of each layer takes at least the mean
        assert s.counts["max_expert_load"] >= \
            s.counts["assignments_held"] / 4
    # the leading dense layer is saved with the blocks
    payloads = run.manager._payloads(run.params, run.opt_state, 4)
    assert any(k.startswith("params/dense_blocks/")
               for k in payloads["params/blocks"])
    assert sorted(payloads["params/head"]) == ["params/final_norm",
                                               "params/lm_head"]
    params, _, _ = run.manager.restore()
    assert sorted(params) == sorted(run.params)


def test_dense_model_payloads_keep_their_bins():
    cfg = get_smoke_config("yi-6b")
    from repro.ckpt import CheckpointManager
    params = jax.tree.map(np.asarray, init_params(cfg, jax.random.PRNGKey(0)))
    bins = CheckpointManager._payloads(None, params, {}, 0)
    assert sorted(bins["params/head"]) == ["params/final_norm",
                                           "params/lm_head"]
    assert sorted(bins["params/embed"]) == ["params/embed"]
    assert all(k.startswith("params/blocks/") for k in bins["params/blocks"])
    assert len(bins["params/blocks"]) == len(params["blocks"])
