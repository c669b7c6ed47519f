"""Plain references of the model families, their seeded weights, and
AdamW: what a cell's ``correct`` is decided against.

Nothing here, and nothing in a family's reference, imports the program.
Each family's forward pass is in ``families/<family>.py`` (found by the
configuration's ``family`` key, ``harness.load_family``), and follows its
published description in straightforward ``jax.numpy``, in float32 with
every matrix product at ``Precision.HIGHEST`` through ``mm``. This module
keeps what all families share: the seeded weights, the matrix product and
its control, RMSNorm, rotary embeddings, the decoder frame (embedding,
stacked layers, final norm, output head), the loss, AdamW and the
training reference.

The loss is the mean next-token cross-entropy over the configuration's
vocabulary. The embedding rows above the vocabulary (padding) are never
looked up and their logits are left out of the softmax.

The parameter tree is the layout the program takes (nested dicts, layers
stacked on a leading axis); ``layout`` states it, and the weights are made
from the seed by ``init_weights`` alone.

``precision="fp8"`` is the control: every matrix product's operands are
first rounded to float8 (e4m3, one scale per tensor), the step below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .harness import load_family, seed_words

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------- layout
def layout(c: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """'/'-joined leaf path -> (shape, dtype) of the parameter tree."""
    return load_family(c["family"]).layout(c)


def padded_vocab(c: Dict[str, Any], vocab: int) -> int:
    m = c.get("pad_vocab_size_multiple", 256)
    return -(-vocab // m) * m


def decoder_layout(z: Dict[str, Any], blocks: Dict[str, Tuple[int, ...]],
                   f32_leaves) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """The embedding (``Vp`` rows), final norm and, untied, output head,
    and each of ``blocks`` stacked over ``L`` layers; the leaves named in
    ``f32_leaves`` are float32, the others bfloat16."""
    L, d = z["L"], z["d"]
    out: Dict[str, Tuple[Tuple[int, ...], str]] = {
        "embed": ((z["Vp"], d), "bfloat16"),
        "final_norm": ((d,), "float32"),
    }
    if not z["tied"]:
        out["lm_head"] = ((d, z["Vp"]), "bfloat16")
    for k, shape in blocks.items():
        out[f"blocks/{k}"] = ((L,) + shape,
                              "float32" if k in f32_leaves else "bfloat16")
    return out


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    root: Dict[str, Any] = {}
    for path, v in flat.items():
        node = root
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return root


def flat(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k in sorted(tree):
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(tree[k], dict):
            out.update(flat(tree[k], path))
        else:
            out[path] = tree[k]
    return out


# --------------------------------------------------------------- weights
def leaf_init(name: str, shape, key, c: Dict[str, Any]) -> jax.Array:
    """Values of one leaf, unless its family file has a ``leaf_init`` of
    its own: fan-in scaled normals for the matrices, and the published
    Mamba-2 ranges for its decay, step and skip parameters."""
    base = name.split("/")[-1]
    n = jax.random.normal(key, shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    per_layer = shape[1:] if name.startswith("blocks/") else shape
    if base in ("norm", "gate_norm", "attn_norm", "mlp_norm", "final_norm"):
        return 1.0 + 0.1 * n
    if base == "A_log":
        return jnp.log(1.0 + 15.0 * u)                  # A in [-16, -1]
    if base == "dt_bias":                               # dt in [1e-3, 1e-1]
        dt = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
        return dt + jnp.log(-jnp.expm1(-dt))            # inverse softplus
    if base == "D":
        return 0.5 + u
    if base.startswith("conv_") and base.endswith("_b"):
        return 0.1 * n
    if base.startswith("conv_"):
        return n / math.sqrt(per_layer[-1])
    if base == "embed":
        return 0.02 * n if c.get("tie_embeddings") else n
    if base in ("wo", "out_proj"):
        return n / math.sqrt(per_layer[0] * per_layer[1])
    return n / math.sqrt(per_layer[0])


def init_weights(c: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The cell's weights from the seed, made on the device in one jitted
    call, in the dtypes they are trained and served in."""
    lay = layout(c)
    names = sorted(lay)
    init = getattr(load_family(c["family"]), "leaf_init", leaf_init)

    def make(words):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
        keys = jax.random.split(key, len(names))
        return nest({nm: init(nm, lay[nm][0], k, c)
                     .astype(jnp.dtype(lay[nm][1]))
                     for nm, k in zip(names, keys)})

    words = jnp.asarray(seed_words(seed, 1), jnp.uint32)
    return jax.jit(make)(words)


# ---------------------------------------------------------------- maths
def _fp8(x: jax.Array) -> jax.Array:
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(eq: str, a, b, precision: str):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rms(x, w, eps, axes=(-1,)):
    var = jnp.mean(x * x, axis=axes, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x: (b, S, H, D); rotate-half pairs (i, i + D/2)."""
    S, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = np.arange(S)[:, None] * freqs[None]                # (S, D/2)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits(c: Dict[str, Any], params: Dict[str, Any], tokens,
           precision: str = "f32") -> jax.Array:
    """(b, S) tokens -> (b, S, V) float32 logits over the vocabulary."""
    return load_family(c["family"]).logits(c, params, tokens, precision)


def decoder_logits(z: Dict[str, Any], layer, params: Dict[str, Any], tokens,
                   precision: str) -> jax.Array:
    """Embedding, ``layer(z, layer_params, x, precision)`` scanned over the
    stacked blocks (rematerialised), final RMSNorm and the output head (the
    transposed embedding where tied), in float32."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = jnp.take(p["embed"], tokens, axis=0)

    def body(h, lp):
        return layer(z, lp, h, precision), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, p["blocks"])
    x = rms(x, p["final_norm"], z["eps"])
    head = p["embed"].T if z["tied"] else p["lm_head"]
    return mm("bsd,dv->bsv", x, head[:, :z["V"]], precision)


def loss_sum(c, params, tokens, labels, precision="f32"):
    """The summed loss the reference trains on: the family's ``loss_sum``
    where its file has one, else ``cross_entropy_sum``."""
    fam = load_family(c["family"])
    if hasattr(fam, "loss_sum"):
        return fam.loss_sum(c, params, tokens, labels, precision)
    return cross_entropy_sum(c, params, tokens, labels, precision)


def cross_entropy_sum(c, params, tokens, labels, precision="f32"):
    lg = logits(c, params, tokens, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


# ----------------------------------------------------------------- adamw
def adamw(opt: Dict[str, float], step: int, params, m, v, grads):
    """One AdamW step (decoupled weight decay on every leaf, global-norm
    clipping, bias correction, linear warmup) on float32 trees."""
    if step <= opt["warmup_steps"] and opt["warmup_steps"] > 0:
        lr = opt["peak_lr"] * step / opt["warmup_steps"]
    else:
        prog = min(max((step - opt["warmup_steps"]) /
                       max(opt["decay_steps"] - opt["warmup_steps"], 1),
                       0.0), 1.0)
        lr = opt["peak_lr"] * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"])
                               * 0.5 * (1 + math.cos(math.pi * prog)))
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    b1, b2 = opt["b1"], opt["b2"]
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    g = jax.tree.map(lambda a: a * scale, grads)
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    params = jax.tree.map(
        lambda w, a, b: w - lr * ((a / bc1) / (jnp.sqrt(b / bc2) +
                                               opt["eps"]) +
                                  opt["weight_decay"] * w), params, m, v)
    return params, m, v, g


def leaf_norms(tree) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for k, a in flat(tree).items()}


@functools.lru_cache(maxsize=8)
def _train_fns(config_json: str, opt_json: str, precision: str):
    """The reference's jitted gradient, accumulation and update, built once
    per configuration so that many seeds in one process compile once."""
    c, opt = json.loads(config_json), json.loads(opt_json)
    grad_fn = jax.jit(jax.value_and_grad(
        functools.partial(loss_sum, c, precision=precision)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0)
    update = jax.jit(functools.partial(_update, opt), static_argnums=(0, 1),
                     donate_argnums=(2, 3, 4))
    return grad_fn, add, update


def _update(opt, n, step, params, m, v, grads):
    grads = jax.tree.map(lambda a: a / n, grads)
    params, m, v, g = adamw(opt, step, params, m, v, grads)
    return params, m, v, leaf_norms(g)


def train_reference(c: Dict[str, Any], opt: Dict[str, float], params,
                    batches: List[Tuple[np.ndarray, np.ndarray]],
                    precision: str = "f32", row_block: int = 2
                    ) -> Dict[str, Any]:
    """Follow ``len(batches)`` training steps from ``params``.

    Returns each step's mean loss, the per-leaf norms of the first
    (clipped) gradient as the optimizer takes it, and the per-leaf norms of
    the change of the float32 weights after the last step. The gradient is
    accumulated over blocks of ``row_block`` rows so that it fits, and the
    float32 state is updated in place."""
    grad_fn, add, update = _train_fns(json.dumps(c, sort_keys=True),
                                      json.dumps(opt, sort_keys=True),
                                      precision)
    p = jax.tree.map(lambda a: jnp.array(a, jnp.float32, copy=True), params)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, g1 = [], None
    for i, (tok, lab) in enumerate(batches):
        total, grads = 0.0, None
        for r in range(0, tok.shape[0], row_block):
            ls, g = grad_fn(p, jnp.asarray(tok[r:r + row_block]),
                            jnp.asarray(lab[r:r + row_block]))
            total = total + ls
            grads = g if grads is None else add(grads, g)
            del g
        p, m, v, gnorms = update(float(tok.size), i + 1, p, m, v, grads)
        del grads
        losses.append(float(total) / tok.size)
        if i == 0:
            g1 = {k: float(x) for k, x in gnorms.items()}
    delta = {k: float(x) for k, x in leaf_norms(
        jax.tree.map(lambda a, b: a - b.astype(jnp.float32), p,
                     params)).items()}
    return {"loss": losses, "grad1": g1, "delta": delta}
