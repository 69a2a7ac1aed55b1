"""Absorbed MLA decode attention over one layer of the latent stacks, read
in place and only up to each lane's position.

MLA's decode caches one compressed latent a position: ``c`` of
``kv_lora_rank`` and the rope key ``kr`` of ``qk_rope_head_dim``, shared by
every head. With ``W_uk`` absorbed into the query, a head's score against a
position is ``q_c · c + q_rope · kr`` and its context is the probabilities
times ``c``: all ``H`` heads read the same latent rows. The jnp einsums read
each lane's whole latent twice, once for the scores and once for the
context, over every position of the cache, and write float32 scores of
``(B, H, S)`` to HBM between them. This kernel reads each latent row once,
keeps the scores in VMEM, and reads no block past a lane's last valid
position.

Grid ``(B, S / block_s)`` with the position blocks minor: one lane's
``(m, l, acc)`` online-softmax statistics live in VMEM scratch across its
blocks. A block's scores are one ``(H, block_s)`` float32 matmul pair on the
MXU, and its context ``p @ c`` reads the same ``c`` block already in VMEM.
The stacks are passed whole, with ``layer`` a scalar-prefetch index that the
BlockSpecs read, so each block is DMA'd from the stack and no layer is
copied out of it. The latent index maps clamp the block index to the lane's
last valid block: the blocks past it repeat that index, which issues no
DMA, and their compute is skipped. In every block computed, the rows past
``n_valid`` are masked out of both the scores and the context.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.decode_attention import NEG_INF, block_positions

_NT = (((1,), (1,)), ((), ()))   # contract the last dims: a @ b.T


def _kernel(layer_ref, n_valid_ref, qc_ref, qr_ref, c_ref, krt_ref, o_ref,
            m_scr, l_scr, acc_scr, *, block_s: int, scale: float):
    del layer_ref  # read by the BlockSpecs
    b, j = pl.program_id(0), pl.program_id(1)
    # positions of this block the lane attends to: none past its last block
    left = n_valid_ref[b] - j * block_s

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(left > 0)
    def _accumulate():
        c = c_ref[0, 0]                                      # (block_s, kr)
        s = (jax.lax.dot_general(qc_ref[0], c, _NT,
                                 preferred_element_type=jnp.float32)
             + jnp.dot(qr_ref[0], krt_ref[0, 0],
                       preferred_element_type=jnp.float32)
             ) * scale                                           # (H, block_s)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < left, s, NEG_INF)
        row = jax.lax.broadcasted_iota(jnp.int32, (c.shape[0], 1), 0)
        c = jnp.where(row < left, c, jnp.zeros_like(c))
        m_prev = m_scr[...]                                  # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)        # a masked score's exp is exactly 0
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret"))
def mla_decode_attention(q_c: jax.Array, q_rope: jax.Array,
                         c_stack: jax.Array, kr_stack: jax.Array,
                         layer: jax.Array, n_valid: jax.Array, *,
                         scale: float,
                         interpret: bool | None = None) -> jax.Array:
    """One query token per lane against layer ``layer`` of the latent
    stacks, in MLA's absorbed form.

    q_c: ``(B, H, kv_lora_rank)``, the query with ``W_uk`` absorbed;
    q_rope: ``(B, H, qk_rope_head_dim)``; c_stack: ``(L, B, S,
    kv_lora_rank)``; kr_stack: ``(L, B, S, qk_rope_head_dim)``; layer: int
    scalar; n_valid: ``(B,)`` int, at least 1: lane b attends to positions
    ``[0, n_valid[b])``. The blocks are GQA's,
    :func:`~repro.kernels.decode_attention.block_positions` of ``S``.
    Returns the context ``softmax(scale * (q_c c^T + q_rope kr^T)) @ c`` of
    ``(B, H, kv_lora_rank)`` in ``q_c.dtype``.
    """
    B, H, kr = q_c.shape
    rdim = q_rope.shape[-1]
    S = c_stack.shape[2]
    block_s = block_positions(S)

    def last_valid(b, j, n):
        return jnp.minimum(j, jax.lax.div(n[b] - 1, block_s))

    def latent_block(b, j, layer, n):
        return (layer[0], b, last_valid(b, j, n), 0)

    def rope_block(b, j, layer, n):
        return (layer[0], b, 0, last_valid(b, j, n))

    def head_block(b, j, layer, n):
        return (b, 0, 0)

    return pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, S // block_s),
            in_specs=[pl.BlockSpec((1, H, kr), head_block),
                      pl.BlockSpec((1, H, rdim), head_block),
                      pl.BlockSpec((1, 1, block_s, kr), latent_block),
                      pl.BlockSpec((1, 1, rdim, block_s), rope_block)],
            out_specs=pl.BlockSpec((1, H, kr), head_block),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, kr), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, kr), q_c.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="mla_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      n_valid.astype(jnp.int32), q_c, q_rope, c_stack,
      # XLA keeps the rope keys' stack on the TPU with positions minor (a
      # 64-wide minor dim would leave half of each (8, 128) tile empty):
      # read as (L, B, rdim, S) it is a bitcast there, where a row-major
      # operand costs a copy of the whole stack at every layer
      jnp.swapaxes(kr_stack, 2, 3))
