"""Compile-only checks against the TPU v5e compiler, with no chip attached.

Interpret mode runs a Pallas kernel's body as jnp on the CPU, so it cannot
see what the chip's compiler refuses: block shapes off the (8, 128)
tiling, reductions Mosaic does not lower, more VMEM than a kernel may use,
a program that does not fit HBM. These tests compile the fingerprint
programs of the save path at real widths for a described v5e and check
that the kernel is really in the executable (``tpu_custom_call``).

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.ckpt.manager import flatten_tree
from repro.configs import get_config
from repro.core.fingerprint import (_fingerprint_packed, chunk_geometry,
                                    tree_pack_index)
from repro.kernels.fingerprint.kernel import fingerprint_lanes
from repro.models import init_params
from repro.optim import init_opt_state

HBM_BYTES = 16 * 10 ** 9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one: keep them out of any persistent cache the environment sets
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


@pytest.mark.parametrize("rows,lanes", [
    (16, 1 << 19),     # 1 MiB bf16 chunks: one uint32 lane per element
    (16, 1 << 18),     # 1 MiB f32 chunks
    (3, 37),           # fewer rows than a block, lanes off the 128 tiling
])
def test_fingerprint_kernel_compiles(one_chip, rows, lanes):
    u = _sds((rows, lanes), jnp.uint32, one_chip)
    w = _sds((rows,), jnp.int32, one_chip)
    compiled = jax.jit(lambda u, w: fingerprint_lanes(u, widths=w)) \
        .lower(u, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _packed(tree, chunk_bytes, backend, sharding):
    """Compile what ``fingerprint_tree_packed`` dispatches for ``tree``
    (a flat dict of ShapeDtypeStructs)."""
    names = list(tree)
    _, total_chunks, max_lanes = tree_pack_index(tree, chunk_bytes)
    geom = tuple(chunk_geometry(tuple(tree[n].shape), str(tree[n].dtype),
                                chunk_bytes) for n in names)
    leaves = tuple(_sds(tree[n].shape, tree[n].dtype, sharding)
                   for n in names)
    compiled = _fingerprint_packed.lower(leaves, geom, max_lanes, backend,
                                         False).compile()
    return compiled, total_chunks


def test_packed_mixed_widths_compiles(one_chip):
    """bf16 and f32 leaves (different lanes per chunk) in one buffer whose
    row count is not a multiple of the kernel's 8-row block."""
    tree = {"bf16": jax.ShapeDtypeStruct((3 << 19,), jnp.bfloat16),
            "f32": jax.ShapeDtypeStruct((5 << 18,), jnp.float32),
            "f32_ragged": jax.ShapeDtypeStruct((1000, 3), jnp.float32),
            "i8": jax.ShapeDtypeStruct((4096,), jnp.int8)}
    compiled, total_chunks = _packed(tree, 1 << 20, "pallas", one_chip)
    assert total_chunks % 8
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_packed_mamba2_state_fits_one_chip(one_chip, backend):
    """The whole mamba2-130m train state (params + Adam) at its published
    widths, as the save path fingerprints it: compiles and fits in HBM."""
    cfg = get_config("mamba2-130m")
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(init_opt_state, params)
    tree = {**flatten_tree(params, "params"), **flatten_tree(opt, "opt")}
    tree["opt/__step__"] = jax.ShapeDtypeStruct((1,), jnp.int32)
    compiled, _ = _packed(tree, 1 << 20, backend, one_chip)
    mem = compiled.memory_analysis()
    state_bytes = sum(np.prod(v.shape) * jnp.dtype(v.dtype).itemsize
                      for v in tree.values())
    assert mem.argument_size_in_bytes >= state_bytes   # + tile padding
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")
