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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.moe_experts import held_experts
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


def _deepseek_cell_config():
    """deepseek-v3 as the chat cell serves it: 7 layers (1 dense), 8 of
    256 routed experts held, an eighth of the vocabulary, no MTP."""
    return dataclasses.replace(get_config("deepseek-v3-671b"), n_layers=7,
                               first_k_dense=1, vocab_size=16160,
                               n_experts_held=8, mtp_depth=0)


def _top_level_results(hlo: str) -> list[str]:
    """Result shapes of the ops outside fused computations: the arrays the
    program materializes."""
    fused, comps, cur = set(), {}, None
    for line in hlo.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            cur = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[cur.lstrip("%")] = []
        elif cur is not None and line.startswith(" "):
            comps[cur.lstrip("%")].append(line)
            if " fusion(" in line:
                fused.update(re.findall(r"calls=%([\w.\-]+)", line))
    return [m.group(1) for name, lines in comps.items() if name not in fused
            for line in lines
            if (m := re.search(r"= (\w+\[[\d,]*\])", line))]


def test_deepseek_lane_step_fits_and_reads_latent_in_place(shape_on,
                                                          monkeypatch):
    """The donating lane step at the cell's sizes (7 layers, 64 lanes, 4096
    positions) fits one v5e, runs the held-expert kernel, and materializes
    no whole layer of the latent stacks (every layer slice is fused into
    the score and context dots) and no layer's held experts."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    cfg = _deepseek_cell_config()
    model = get_model(cfg)
    place = functools.partial(jax.tree.map,
                              lambda s: shape_on(s.shape, s.dtype))
    params = place(jax.eval_shape(
        functools.partial(model.init_params, cfg=cfg), jax.random.PRNGKey(0)))
    lanes, max_len = 64, 4096
    cache = dict(jax.eval_shape(
        functools.partial(model.init_decode_cache, cfg, lanes, max_len)))
    cache["pos"] = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    cache = place(cache)
    step = jax.jit(lambda p, c, t: model.decode_step(p, c, t, cfg,
                                                     moe_groups=1),
                   donate_argnums=(1,))
    compiled = step.lower(params, cache,
                          shape_on((lanes, 1), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert _has_kernel(compiled)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES
    whole = {f"bf16[{one}{lanes},{max_len},{w}]" for one in ("", "1,")
             for w in (cfg.kv_lora_rank, cfg.qk_rope_head_dim)}
    # nor a layer's held experts, which the kernel reads from the stacks
    experts = {f"bf16[{E},{a},{b}]" for E in (cfg.n_experts_held,)
               for a, b in ((cfg.d_model, cfg.moe_d_ff),
                            (cfg.moe_d_ff, cfg.d_model))}
    assert not (whole | experts) & set(_top_level_results(hlo))


def test_held_expert_kernel_deepseek_share(shape_on):
    """The held-expert kernel at DeepSeek-V3's share: 8 experts, 64 tokens
    each at most, hidden 7168, expert width 2048, from 6 layers' stacks."""
    E, cap, d, ff = 8, 64, 7168, 2048
    L = 6
    fn = jax.jit(functools.partial(held_experts, interpret=False))
    compiled = fn.lower(shape_on((E, cap, d)), shape_on((L, E, d, ff)),
                        shape_on((L, E, d, ff)), shape_on((L, E, ff, d)),
                        shape_on((E,), jnp.int32),
                        shape_on((), jnp.int32)).compile()
    assert _has_kernel(compiled)
