"""Compile-only checks for one TPU v5e, with no chip attached.

The TPU compiler is asked for the main path's kernels and decode step at
real widths on a described ``v5e:2x2`` topology. It refuses what interpret
mode accepts: blocks not aligned to the (8, 128) tiling, too much VMEM, a
program that does not fit HBM. Nothing runs, so nothing here is a time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.streaming_matmul import streaming_matmul
from repro.models import get_model

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(autouse=True)
def chip_precision():
    """Compile under the program's own matmul precision. ``conftest.py``
    asks for float32 dots (CPU allclose tests), which Mosaic refuses for
    bf16 operands; the chip never runs with that setting."""
    with jax.default_matmul_precision("default"):
        yield


@pytest.fixture(scope="module")
def shape_on(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    return shape


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_streaming_matmul_granite_width(shape_on):
    x, w = shape_on((256, 4096)), shape_on((4096, 14336))
    fwd = jax.jit(functools.partial(streaming_matmul, interpret=False))
    assert _has_kernel(fwd.lower(x, w).compile())

    def loss(x, w):
        y = streaming_matmul(x, w, interpret=False)
        return jnp.sum(y.astype(jnp.float32))

    grads = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w).compile()
    assert _has_kernel(grads)


@pytest.mark.parametrize("window", [None, 512])
def test_flash_attention_granite_heads(shape_on, window):
    q = shape_on((1, 32, 2048, 128))
    kv = shape_on((1, 8, 2048, 128))
    fn = jax.jit(functools.partial(flash_attention_tpu, causal=True,
                                   window=window, block_q=512, block_k=512,
                                   interpret=False))
    assert _has_kernel(fn.lower(q, kv, kv).compile())


def test_ssd_mamba2_130m(shape_on):
    cfg = get_config("mamba2-130m")
    B, L, G, N = 2, 2048, cfg.ssm_ngroups, cfg.ssm_state
    H, P = cfg.ssm_nheads, cfg.ssm_headdim
    f32 = jnp.float32
    fn = jax.jit(functools.partial(ops.ssd, chunk=cfg.ssm_chunk,
                                   interpret=False))
    compiled = fn.lower(shape_on((B, L, H, P), f32), shape_on((B, L, G, N), f32),
                        shape_on((B, L, G, N), f32), shape_on((B, L, H), f32),
                        shape_on((H,), f32)).compile()
    assert _has_kernel(compiled)


def test_granite_decode_step_fits_hbm(shape_on):
    """The served step at granite-8b widths, 2 layers, batch 8, 2048 context.
    It runs no Pallas kernel yet, so only its memory is checked."""
    cfg = dataclasses.replace(get_config("granite-8b"), n_layers=2)
    model = get_model(cfg)
    place = functools.partial(jax.tree.map,
                              lambda s: shape_on(s.shape, s.dtype))
    params = place(jax.eval_shape(
        functools.partial(model.init_params, cfg=cfg), jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        functools.partial(model.init_decode_cache, cfg, 8, 2048)))
    tok = shape_on((8, 1), jnp.int32)
    step = jax.jit(lambda p, c, t: model.decode_step(p, c, t, cfg,
                                                     moe_groups=1))
    mem = step.lower(params, cache, tok).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES


def test_granite_lane_step_reads_kv_stacks_in_place(shape_on, monkeypatch):
    """The donating lane step at granite-8b widths, 2 layers, 8 lanes, 2048
    context: attention is the decode-attention kernel, the caches alias the
    output, and no layer of K or V is copied out of the stacks."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    cfg = dataclasses.replace(get_config("granite-8b"), n_layers=2)
    model = get_model(cfg)
    place = functools.partial(jax.tree.map,
                              lambda s: shape_on(s.shape, s.dtype))
    params = place(jax.eval_shape(
        functools.partial(model.init_params, cfg=cfg), jax.random.PRNGKey(0)))
    cache = dict(jax.eval_shape(
        functools.partial(model.init_decode_cache, cfg, 8, 2048)))
    cache["pos"] = jax.ShapeDtypeStruct((8,), jnp.int32)
    cache = place(cache)
    step = jax.jit(lambda p, c, t: model.decode_step(p, c, t, cfg,
                                                     moe_groups=1),
                   donate_argnums=(1,))
    compiled = step.lower(params, cache, shape_on((8, 1), jnp.int32)).compile()
    assert _has_kernel(compiled)
    mem = compiled.memory_analysis()
    k_bytes = cache["k"].size * cache["k"].dtype.itemsize
    assert mem.alias_size_in_bytes >= 2 * k_bytes          # K and V
    assert mem.temp_size_in_bytes < k_bytes // cfg.n_layers  # one layer's K
