"""Decode attention over one layer of a stacked KV cache, read in place.

The decode step keeps K and V as ``(L, B, S, KV, Dh)`` stacks and updates
them in place. Handing attention ``k_stack[layer]`` makes XLA copy that
whole layer out of the stack before its attention fusions read it (on a
v5e: 0.4 ms for each of K and V, a layer, a step at 16 lanes × 4096
positions). This kernel takes the stacks whole, with ``layer`` a scalar-
prefetch index that its BlockSpecs read, so each block is DMA'd from the
stack straight into VMEM and nothing else is copied.

Grid ``(B, S / block_s)`` with the position blocks minor: one lane's
``(m, l, acc)`` online-softmax statistics live in VMEM scratch across its
blocks. A K/V block is ``(block_s * KV, Dh)``: the ``(S, KV)`` dims of the
cache merged, a free reshape, so one lane's block holds every KV head. All
``H`` query heads are scored against every row of the block in one matmul,
and a row ``s * KV + kv`` counts for head ``h`` only where ``kv == h // G``
(and ``s`` is a valid position); the ``KV``-fold redundant MXU work is small
beside the block's HBM read. Values go through the MXU as ``p @ v`` over
the same rows, so no transpose is needed.

A lane's valid positions are ``[0, n_valid)``: for a full cache
``n_valid = pos + 1``, for a sliding-window ring ``min(pos + 1, S)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -0.7 * float(np.finfo(np.float32).max)
#: largest number of positions in one K or V block
MAX_BLOCK_S = 512


def _kernel(layer_ref, n_valid_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, kv_heads: int, group: int,
            block_s: int, scale: float):
    del layer_ref  # read by the BlockSpecs
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                   # (H, Dh)
    s = jax.lax.dot_general(
        q, k_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # (H, block_s * KV)
    shape = s.shape
    head = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    # row s*KV + kv of the block is position j*block_s + s of KV head kv
    rows_valid = (n_valid_ref[b] - j * block_s) * kv_heads
    valid = (jax.lax.rem(row, kv_heads) == jax.lax.div(head, group)) & (
        row < rows_valid)
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[...]                            # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0, 0], preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def block_positions(S: int) -> int:
    """Positions in one K/V block: the largest divisor of ``S`` up to
    ``MAX_BLOCK_S``."""
    return math.gcd(S, MAX_BLOCK_S)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q: jax.Array, k_stack: jax.Array, v_stack: jax.Array,
                     layer: jax.Array, n_valid: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """One query token per lane against layer ``layer`` of the stacks.

    q: ``(B, H, Dh)``; k_stack, v_stack: ``(L, B, S, KV, Dh)``; layer: int
    scalar; n_valid: ``(B,)`` int, lane b attends to positions
    ``[0, n_valid[b])``. Returns ``(B, H, Dh)`` in ``q.dtype``.
    """
    B, H, Dh = q.shape
    L, _, S, KV, _ = k_stack.shape
    block_s = block_positions(S)
    rows = block_s * KV
    # merging (S, KV) is a reshape of contiguous dims: no copy
    k = k_stack.reshape(L, B, S * KV, Dh)
    v = v_stack.reshape(L, B, S * KV, Dh)
    kv_spec = pl.BlockSpec((1, 1, rows, Dh),
                           lambda b, j, layer, n: (layer[0], b, j, 0))
    head_spec = pl.BlockSpec((1, H, Dh), lambda b, j, layer, n: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, kv_heads=KV, group=H // KV,
                          block_s=block_s, scale=1.0 / np.sqrt(Dh)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, S // block_s),
            in_specs=[head_spec, kv_spec, kv_spec],
            out_specs=head_spec,
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      n_valid.astype(jnp.int32), q, k, v)
