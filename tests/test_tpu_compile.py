"""Ahead-of-time compiles for a described (not attached) TPU v5e chip.

Interpret mode runs the Pallas kernels as plain JAX, so it accepts what the
chip's compiler refuses: blocks that are not tile-aligned, primitives that
Mosaic does not lower, programs that do not fit the device. These tests
compile the main path's kernels at real widths, and one full-width decode
step, for one chip of a ``v5e:2x2`` topology. Nothing runs; a compile that
passes here is not a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.himeno import GRIDS
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.himeno.kernel import himeno_jacobi_pallas
from repro.kernels.rmsnorm.kernel import rms_norm_pallas
from repro.kernels.wkv.kernel import wkv_pallas
from repro.models import transformer as T

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _spec(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_himeno_kernel_compiles_at_paper_grid(one_chip):
    grid = GRIDS["L"]
    compiled = _compile(himeno_jacobi_pallas, _spec(grid), _spec((4,) + grid),
                        _spec((3,) + grid), _spec((3,) + grid), _spec(grid),
                        _spec(grid), sharding=one_chip)
    assert _has_kernel(compiled)
    assert compiled.memory_analysis().argument_size_in_bytes < V5E_HBM_BYTES


def test_flash_attention_kernel_compiles(one_chip):
    qkv = _spec((1, 8, 1024, 128), jnp.bfloat16)
    compiled = _compile(flash_attention_pallas, qkv, qkv, qkv,
                        sharding=one_chip)
    assert _has_kernel(compiled)


def test_rmsnorm_kernel_compiles(one_chip):
    compiled = _compile(rms_norm_pallas, _spec((4096, 3072), jnp.bfloat16),
                        _spec((3072,)), sharding=one_chip)
    assert _has_kernel(compiled)


def test_wkv_kernel_compiles(one_chip):
    x = _spec((1, 32, 512, 64))
    compiled = _compile(wkv_pallas, x, x, x, x, _spec((32, 64)),
                        sharding=one_chip)
    assert _has_kernel(compiled)


def test_full_width_decode_step_fits_one_chip(one_chip):
    cfg = get_config("llama3.2-3b")
    params = jax.eval_shape(functools.partial(T.init_params, cfg),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(functools.partial(T.init_decode_state, cfg, 4, 64))
    shard = functools.partial(
        jax.tree.map, lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                     sharding=one_chip))
    step = jax.jit(functools.partial(T.decode_step, cfg), donate_argnums=(1,))
    compiled = step.lower(shard(params), shard(state),
                          shard(_spec((4,), jnp.int32))).compile()
    assert compiled.memory_analysis().argument_size_in_bytes < V5E_HBM_BYTES
