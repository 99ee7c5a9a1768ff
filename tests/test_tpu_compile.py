"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU
v5e that is described, not attached.

Each test lowers one kernel at the widths the chip runs and compiles it
with the TPU compiler installed beside JAX: what Mosaic refuses (tile
alignment, VMEM budget, operand layout) fails here, at no chip time. A
compile that passes is not a run: nothing here checks values or times.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the one that runs
this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.attention.flash_attention import flash_attention_bhld
from repro.kernels.decode_attention.flash_decode import flash_decode
from repro.kernels.meta_update.aggregate import weighted_aggregate_flat
from repro.kernels.meta_update.fused import inner_update_plane
from repro.models import init_lm
from repro.optim.fused_adam import adam_flat_pallas
from repro.utils.flat import plane_for


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smollm_plane():
    """smollm-360m's φ plane at published widths (shapes only)."""
    cfg = get_config("smollm-360m")
    shapes = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    return plane_for(shapes)


@pytest.fixture(scope="module")
def femnist_plane():
    """The benchmark's femnist CNN plane (2048 hidden units, 6,603,776
    padded f32 parameters; shapes only)."""
    from repro.models.paper import femnist_cnn
    model = femnist_cnn(num_classes=62, image_size=28, hidden=2048)
    return plane_for(jax.eval_shape(model.init, jax.random.PRNGKey(0)))


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("plane,clients,dtype", [
    ("smollm", 2, jnp.float32), ("smollm", 2, jnp.bfloat16),
    ("femnist", 32, jnp.float32)], ids=["f32", "bf16", "femnist32-f32"])
def test_inner_update_plane(request, one_chip, plane, clients, dtype):
    """The serving adapt batch (2 clients) on the smollm-360m plane, and
    the benchmark's 32-writer femnist round (N/1024 = 6,449, prime)."""
    n = request.getfixturevalue(f"{plane}_plane").n_padded
    _compile(lambda t, g: inner_update_plane(t, 0.05, g), one_chip,
             ((clients, n), dtype), ((clients, n), dtype))


def test_weighted_aggregate_flat(one_chip, femnist_plane):
    """A 1000-client round of the femnist plan's CNN, and the
    benchmark's 32-writer round of the 2048-unit CNN."""
    from repro.federated.experiment import DATASETS
    model = DATASETS["femnist"]["model"]()
    n = plane_for(jax.eval_shape(model.init, jax.random.PRNGKey(0))).n_padded
    for clients, width in [(1000, n), (32, femnist_plane.n_padded)]:
        _compile(weighted_aggregate_flat, one_chip,
                 ((clients, width), jnp.float32), ((clients,), jnp.float32))


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_adam_flat_pallas(one_chip, smollm_plane, state_dtype):
    """The fused outer Adam over smollm-360m's 362 M-parameter plane."""
    n = smollm_plane.n_padded

    def step(phi, g, m, v):
        return adam_flat_pallas(phi, g, m, v, jnp.ones((2,), jnp.float32),
                                lr=1e-4, b1=0.9, b2=0.999, eps=1e-8, wd=0.0)

    _compile(step, one_chip, ((n,), jnp.float32), ((n,), jnp.float32),
             ((n,), state_dtype), ((n,), state_dtype))


@pytest.mark.parametrize("vmapped", [False, True],
                         ids=["batch4", "vmap4xbatch1"])
def test_flash_decode(one_chip, vmapped):
    """smollm-360m decode heads over a 32k cache, four requests: one
    batched call, and the serving engine's per-request vmap."""
    cfg = get_config("smollm-360m")
    H, Kv, hd, C = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 32768
    if vmapped:
        fn = jax.vmap(flash_decode)
        shapes = (((4, 1, H, hd), jnp.bfloat16),
                  ((4, 1, C, Kv, hd), jnp.bfloat16),
                  ((4, 1, C, Kv, hd), jnp.bfloat16), ((4, 1), jnp.int32))
    else:
        fn = flash_decode
        shapes = (((4, H, hd), jnp.bfloat16), ((4, C, Kv, hd), jnp.bfloat16),
                  ((4, C, Kv, hd), jnp.bfloat16), ((4,), jnp.int32))
    _compile(fn, one_chip, *shapes)


def test_flash_attention_forward(one_chip):
    """smollm-360m prefill attention at 4096 tokens (forward only: the
    kernel has no backward, so training pins XLA attention)."""
    cfg = get_config("smollm-360m")
    H, Kv, hd, L = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 4096
    _compile(flash_attention_bhld, one_chip, ((1, H, L, hd), jnp.bfloat16),
             ((1, Kv, L, hd), jnp.bfloat16), ((1, Kv, L, hd), jnp.bfloat16))


def test_decode_step_keeps_one_kv_cache(topo):
    """The serve launcher's decode step for smollm-360m at one chip's
    share of decode_32k (8 × 32k tokens), its cache donated: the cache
    is updated in place, so the step's temp space is a small part of
    one cache and cache plus weights fit a 16 GB chip."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import INPUT_SHAPES
    from repro.kernels.decode_attention import ops as dec_ops
    from repro.launch.mesh import make_device_mesh
    from repro.launch.steps import input_specs, make_decode_step
    from repro.sharding.rules import param_pspecs

    mesh = make_device_mesh(topo.devices[:1])
    shape = dataclasses.replace(INPUT_SHAPES["decode_32k"], global_batch=8)
    spec = input_specs(get_config("smollm-360m"), shape, mesh)
    cfg = spec["serving_cfg"]
    params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))

    def placed(tree, pspecs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, pspecs)

    args = (placed(params, param_pspecs(params, mesh)),
            placed(spec["batch"]["cache"], spec["pspec"]["cache"]),
            placed(spec["batch"]["tokens"], spec["pspec"]["tokens"]))
    with dec_ops.use_impl("pallas"):
        compiled = jax.jit(make_decode_step(cfg), donate_argnums=(1,)) \
            .lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(spec["batch"]["cache"]))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < cache_bytes / 4
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
