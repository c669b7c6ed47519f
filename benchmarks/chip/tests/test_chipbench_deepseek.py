"""The ``deepseek_v2`` family (DeepSeek-V2-Lite): its layout is the
program's at published widths, its FLOP count is the hand count, the
program's MLA layer, loss and gradients follow the reference on seeded
weights, and the cell runs through the driver at toy size on the CPU."""
import copy
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_smoke import BENCH, PREC
from chipbench.flops import train_flops_per_token
from chipbench.harness import Cell, find_cell, load_benchmark, load_family
from chipbench.program import check_layout, import_program, model_config
from chipbench.reference import flat, init_weights, loss_sum

CELL = "deepseek-v2-lite-5L.train-4k"
SEED = 2 ** 33 + 11
with open(os.path.join(BENCH, "configs", "deepseek-v2-lite-5L.json")) as f:
    PUBLISHED = json.load(f)
# published keys at toy widths: 1 dense + 4 MoE layers, 4 of 8 experts
SMOKE = dict(PUBLISHED, name="deepseek-smoke", hidden_size=64,
             intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, kv_lora_rank=16, moe_intermediate_size=32,
             n_routed_experts=4, n_routed_experts_total=8,
             num_experts_per_tok=3, vocab_size=199, precision=PREC)
F32 = dict(PREC, params="float32", compute="float32")


def family():
    return load_family("deepseek_v2")


def test_layout_is_the_programs_at_published_widths():
    import_program()
    check_layout(PUBLISHED, model_config(PUBLISHED))      # shapes only
    lay = family().layout(PUBLISHED)
    assert lay["blocks/wq"][0] == (4, 2048, 16, 192)
    assert lay["blocks/wkv_a"][0] == (4, 2048, 576)
    assert lay["blocks/router"] == ((4, 2048, 64), "float32")
    assert lay["blocks/w_gate"][0] == (4, 8, 2048, 1408)
    assert lay["blocks/w_down"][0] == (4, 8, 1408, 2048)
    assert lay["blocks/shared_up"][0] == (4, 2048, 2816)
    assert lay["dense_blocks/w_gate"][0] == (1, 2048, 10944)
    assert sum(int(np.prod(s)) for s, _ in lay.values()) == 535_060_992


def test_flops_per_token_by_hand():
    # per layer: q 2048x16x192, kv_a 2048x576, kv_b 512x16x256, o 2048x2048
    proj = 2 * (2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048)
    attn = 2 * 16 * (192 + 128) * 4097 / 2
    dense = 2 * 3 * 2048 * 10944
    # router 64 wide; 2 shared + 6 x 8 / 64 held expert passes of 1408
    moe = 2 * 2048 * 64 + 2 * 3 * 2048 * 1408 * (2 + 0.75)
    head = 2 * 2048 * 12800
    per_token = proj * 5 + attn * 5 + dense + 4 * moe + head
    assert family().forward_per_token(PUBLISHED, 4096) == per_token
    assert train_flops_per_token(PUBLISHED, 4096) == 3 * per_token
    assert round(per_token / 1e6, 1) == 620.8


def f32_tree(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def program_cfg(c):
    import_program()
    return model_config(dict(c, precision=F32))


def tokens(c, b=2, s=16):
    t = np.random.default_rng(3).integers(0, c["vocab_size"], (b, s + 1),
                                          dtype=np.int32)
    return jnp.asarray(t[:, :-1]), jnp.asarray(t[:, 1:])


def test_mla_layer_without_query_lora_follows_the_reference():
    from repro.models.blocks import apply_block
    cfg = program_cfg(SMOKE).dense_lead()
    params = f32_tree(init_weights(SMOKE, SEED))
    layer = jax.tree.map(lambda a: a[0], params["dense_blocks"])
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64))
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    got, _, _ = apply_block(cfg, layer, x, pos)
    z = family().dims(SMOKE)
    want, _ = family()._layer(z, layer, x, "f32", routed=False)
    # float32 on both sides; the gap is summation order
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("offset,q_block", [(0, 1024), (4, 8)])
def test_smoke_model_loss_and_gradients_follow_the_reference(
        offset, q_block, monkeypatch):
    """Loss and every leaf's gradient, in float32 on both sides: the loss
    to 1e-5 and each gradient's norm to 1e-4 of the leaf's (round-off of
    a 5-layer float32 model; a left-out term or a wrong gate moves them
    by far more). The reference's attention in one block of queries and
    in two."""
    from repro.models import loss_fn
    monkeypatch.setattr(family(), "Q_BLOCK", q_block)
    c = dict(SMOKE, expert_offset=offset)
    cfg = program_cfg(c)
    params = f32_tree(init_weights(c, SEED))
    tok, lab = tokens(c)
    batch = {"tokens": tok, "labels": lab,
             "mask": jnp.ones(tok.shape, jnp.float32)}
    (loss, met), g = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
    n = tok.size
    ref_loss, ref_g = jax.value_and_grad(
        lambda p: loss_sum(c, p, tok, lab) / n)(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    for k, want in flat(ref_g).items():
        got = flat(g)[k]
        gap = float(jnp.linalg.norm(got - want))
        assert gap <= 1e-4 * float(jnp.linalg.norm(want)) + 1e-9, k
    # the balance term and the counters are read
    assert float(met["aux"]) > 0
    assert float(met["moe_assignments"]) <= tok.size * 3 * 4


def test_fp8_control_moves_the_loss():
    c = SMOKE
    params = init_weights(c, SEED)
    tok, lab = tokens(c)
    a = float(loss_sum(c, params, tok, lab))
    b = float(loss_sum(c, params, tok, lab, "fp8"))
    assert abs(a - b) > 1e-3 * abs(a)


# ------------------------------------------------------------------ cell
spec = importlib.util.spec_from_file_location("chipbench_run",
                                              os.path.join(BENCH, "run.py"))
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)


def smoke_cell():
    cell = find_cell(load_benchmark(), CELL)
    traffic = dict(cell.traffic, batch=4, seq=32, ckpt_every=2,
                   setup_steps=4)
    return Cell(cell.name, cell.chips, copy.deepcopy(SMOKE), traffic,
                cell.limits, cell.end_to_end, cell.per_layer)


def execute(fault=None, trace=0):
    args = run.parse(["--workload", CELL, "--seed", str(SEED),
                      "--seconds", "1", "--trace", str(trace)])
    sel = dict(device_plane=lambda n: n == "/host:CPU",
               op_line=lambda n: n.startswith("tf_XLA"),
               module_line=lambda n: False)
    return run.execute(args, require_tpu=False, fault=fault,
                       cell=smoke_cell(), tracer_selectors=sel)


@pytest.fixture
def no_compile_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_traced_run_is_correct_and_reads_the_moe_counters(no_compile_cache):
    out, rec = execute(trace=1)
    assert out["correct"], out["compared"]
    m = out["metrics"]
    # 4 x 32 tokens, top-3 of 8, 4 of them held: 48 per held expert
    # and layer on average
    assert 0 < m["moe_tokens_per_expert"]["value"] <= 4 * 32 * 3 / 4
    assert m["moe_load_ratio"]["value"] >= 1.0
    assert {"save_stall_s", "save_write_s", "restore_s"} <= set(m)


def test_half_batch_fault_is_not_correct(no_compile_cache):
    out, _ = execute(fault="half_batch")
    assert not out["correct"], out["compared"]
