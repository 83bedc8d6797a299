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
import dataclasses
import functools
import hashlib
import json
import math
import re
from pathlib import Path

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
from repro.runtime.serving import PREFILL_CHUNK

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


def _engine_args(cfg, slots: int, max_len: int, sharding):
    """Shapes of the parameters and the decode state, on ``sharding``, and
    the function that puts further shapes there."""
    params = jax.eval_shape(functools.partial(T.init_params, cfg),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(
        functools.partial(T.init_decode_state, cfg, slots, max_len))
    shard = functools.partial(
        jax.tree.map, lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                     sharding=sharding))
    return shard(params), shard(state), shard


def _compile_decode_step(cfg, slots: int, max_len: int, sharding):
    """The decode step as ``ServingEngine`` jits it, state donated."""
    params, state, shard = _engine_args(cfg, slots, max_len, sharding)
    step = jax.jit(functools.partial(T.decode_step, cfg), donate_argnums=(1,))
    return step.lower(params, state,
                      shard(_spec((slots,), jnp.int32))).compile()


def test_full_width_decode_step_fits_one_chip(one_chip):
    compiled = _compile_decode_step(get_config("llama3.2-3b"), 4, 64, one_chip)
    assert compiled.memory_analysis().argument_size_in_bytes < V5E_HBM_BYTES


def _instruction_sizes(hlo: str) -> list[tuple[str, int]]:
    """``(opcode, elements of its output)`` of every array-shaped
    instruction in the module's text."""
    return [(op, math.prod(int(d) for d in dims.split(",") if d))
            for dims, op in re.findall(
                r"= \w+\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(", hlo)]


def test_stablelm_decode_step_keeps_kv_cache_in_place(one_chip):
    """``stablelm-chat``'s decode step (stablelm-1.6b with its q/k/v bias, as
    the benchmark builds it; 24 slots x 1024) reads and writes the KV cache
    in place: no copy as large as one layer's K slice, and temporaries far
    below the 4.8 GB cache (the scatter write and the cache passed through
    the layer scan's xs/ys made 5.84 GB of them)."""
    cfg = dataclasses.replace(get_config("stablelm-1.6b"), qkv_bias=True)
    slots, max_len = 24, 1024
    compiled = _compile_decode_step(cfg, slots, max_len, one_chip)
    layer_slice = slots * max_len * cfg.num_kv_heads * cfg.resolved_head_dim
    sizes = _instruction_sizes(compiled.as_text())
    # the parse sees the cache itself, so it would see a copy of it
    assert max(n for _, n in sizes) >= layer_slice
    assert [n for op, n in sizes if op == "copy" and n >= layer_slice] == []
    assert compiled.memory_analysis().temp_size_in_bytes < 10**9


def test_stablelm_prefill_chunk_writes_kv_cache_in_place(one_chip):
    """``stablelm-chat``'s chunked-prefill program (24 slots x 1024, a chunk
    of 256 prompt tokens, state donated, as ``ServingEngine`` jits it)
    writes one slot's rows in place: no copy as large as one layer's K
    slice, temporaries far below the 4.8 GB cache, arguments and
    temporaries within the chip, and the state in the layouts the decode
    step leaves and takes, so nothing is relaid out between the two."""
    cfg = dataclasses.replace(get_config("stablelm-1.6b"), qkv_bias=True)
    slots, max_len = 24, 1024
    params, state, shard = _engine_args(cfg, slots, max_len, one_chip)
    scalar = shard(_spec((), jnp.int32))
    chunk = jax.jit(functools.partial(T.prefill_chunk, cfg),
                    donate_argnums=(1,))
    compiled = chunk.lower(
        params, state, scalar,
        shard(_spec((min(PREFILL_CHUNK, max_len),), jnp.int32)), scalar,
        scalar).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
    assert mem.temp_size_in_bytes < 10**9
    layer_slice = slots * max_len * cfg.num_kv_heads * cfg.resolved_head_dim
    sizes = _instruction_sizes(compiled.as_text())
    assert max(n for _, n in sizes) >= layer_slice
    assert [n for op, n in sizes if op == "copy" and n >= layer_slice] == []
    decode = _compile_decode_step(cfg, slots, max_len, one_chip)
    assert compiled.input_formats[0][1] == decode.output_formats[1]
    assert compiled.output_formats == decode.input_formats[0][1]


def test_granite_decode_step_fits_one_chip_with_state_in_place(one_chip):
    """``granite-h-small-chat``'s decode step (granite-4.0-h-small at its
    chip's share, 48 slots x 1024, as the benchmark builds it) fits one
    v5e beside its weights and decode state, and carries both kinds of
    state in place: no copy as large as one layer's Mamba SSM state or KV
    slice, and temporaries far below either stack."""
    path = Path(__file__).resolve().parents[1] / "bench" / "configs"
    conf = json.loads((path / "granite-4.0-h-small.json").read_text())
    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["overrides"])
    slots, max_len = 48, 1024
    compiled = _compile_decode_step(cfg, slots, max_len, one_chip)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
    assert mem.temp_size_in_bytes < 10**9
    kv_slice = slots * max_len * cfg.num_kv_heads * cfg.resolved_head_dim
    ssm_slice = slots * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
    sizes = _instruction_sizes(compiled.as_text())
    assert max(n for _, n in sizes) >= max(kv_slice, ssm_slice)
    assert [n for op, n in sizes
            if op == "copy" and n >= min(kv_slice, ssm_slice)] == []


# sha256 of the lowered decode step (no source locations) of each existing
# cell's model, as the parent commit of the moe_hybrid family lowers it for
# one described v5e: adding the family leaves both programs as they were.
# A change of JAX changes the lowering; recompute them then.
PARENT_DECODE_DIGESTS = {
    "stablelm-chat":
        "9107e1e55b897b8fba34335c86e948f94ea628c85768b7635e968e2e748a088c",
    "rwkv6-chat":
        "0b030b1f81370de6637d4296e7e603061fee28f9ff41d916bdb408e29a3429aa",
}


@pytest.mark.parametrize("cell,slots,max_len", [("stablelm-chat", 24, 1024),
                                                ("rwkv6-chat", 128, 2048)])
def test_existing_cells_decode_programs_are_unchanged(one_chip, cell, slots,
                                                      max_len):
    cfg = {"stablelm-chat": dataclasses.replace(get_config("stablelm-1.6b"),
                                                qkv_bias=True),
           "rwkv6-chat": get_config("rwkv6-1.6b")}[cell]
    params = jax.eval_shape(functools.partial(T.init_params, cfg),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(
        functools.partial(T.init_decode_state, cfg, slots, max_len))
    shard = functools.partial(
        jax.tree.map, lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                     sharding=one_chip))
    step = jax.jit(functools.partial(T.decode_step, cfg), donate_argnums=(1,))
    text = step.lower(shard(params), shard(state),
                      shard(_spec((slots,), jnp.int32))).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_DECODE_DIGESTS[cell]
