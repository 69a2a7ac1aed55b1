"""The held experts' SwiGLU FFN, reading only the experts that have tokens.

At decode a chip's share of a DeepSeek-V3 layer holds 8 experts of 88 MB
each and sees about two tokens for each: the step is the read of the
experts' weights. The jnp einsums read every held expert whatever its
tokens; this kernel reads an expert's three matrices only in a step in
which it has at least one token, and skips the rest.

Tokens come grouped by expert, ``(E, cap, d)`` with expert ``e``'s
``counts[e]`` tokens first in its rows (the dispatch's layout). The
weights are the stacks of every MoE layer, ``(L, E, d, ff)``, with the
layer a scalar-prefetch index: a layer loop that handed the kernel its
scanned slice would have XLA copy all of the layer's experts out of the
stacks first (on a v5e, 12 ms a step at DeepSeek-V3's share). The counts
become the scalar-prefetched ``ids``: the experts with tokens, in order,
then the last of them repeated. Grid ``(E, ff / block_f)``: step
``(j, f)`` computes expert ``ids[j]``'s ``silu(x W_gate) * (x W_up)`` for
block ``f`` of the hidden and accumulates its ``W_down`` product in VMEM.
Steps ``j >= n_active`` map every block to the one the step before read,
so nothing is fetched, computed or written back for them. Rows of experts
without tokens are left unwritten: the combine reads no row of them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

#: largest block of the experts' hidden dimension
MAX_BLOCK_F = 512
#: scoped VMEM the kernel may use: double-buffered 7168 x 512 blocks of
#: three matrices are 44 MB, over the 16 MiB default
VMEM_LIMIT_BYTES = 96 * 1024 * 1024


def _kernel(layer_ref, ids_ref, n_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
            acc_ref):
    del layer_ref  # read by the BlockSpecs
    j, f = pl.program_id(0), pl.program_id(1)

    @pl.when(j < n_ref[0])
    def _compute():
        @pl.when(f == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[0]
        g = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        acc_ref[...] += jnp.dot(h, wd_ref[0, 0],
                                preferred_element_type=jnp.float32)

        @pl.when(f == pl.num_programs(1) - 1)
        def _done():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def block_hidden(ff: int) -> int:
    """Columns of the hidden in one block: the largest divisor of ``ff`` up
    to ``MAX_BLOCK_F`` that is a multiple of 128, else ``ff`` whole."""
    best = math.gcd(ff, MAX_BLOCK_F)
    return best if best % 128 == 0 else ff


@functools.partial(jax.jit, static_argnames=("interpret",))
def held_experts(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                 w_down: jax.Array, counts: jax.Array, layer: jax.Array, *,
                 interpret: bool | None = None) -> jax.Array:
    """``(silu(x_e W_gate[e]) * (x_e W_up[e])) W_down[e]`` for every expert
    ``e`` with ``counts[e] > 0``, its weights read from layer ``layer`` of
    the stacks in place.

    x: ``(E, cap, d)``; w_gate, w_up: ``(L, E, d, ff)``; w_down:
    ``(L, E, ff, d)``; counts: ``(E,)`` int; layer: int scalar. Returns
    ``(E, cap, d)`` in ``x.dtype``; the rows of an expert with no token hold
    no defined value.
    """
    E, cap, d = x.shape
    ff = w_gate.shape[3]
    bf = block_hidden(ff)
    nf = ff // bf
    active = counts > 0
    n = jnp.sum(active).astype(jnp.int32)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    ids = jnp.where(jnp.arange(E) < n, order, order[jnp.maximum(n - 1, 0)])

    def hidden(j, f, n_ref):
        # past the active experts, stay on the block the last step read
        return jnp.where(j < n_ref[0], f, nf - 1)

    x_spec = pl.BlockSpec((1, cap, d),
                          lambda j, f, layer, ids, n: (ids[j], 0, 0))
    in_spec = pl.BlockSpec(
        (1, 1, d, bf),
        lambda j, f, layer, ids, n: (layer[0], ids[j], 0, hidden(j, f, n)))
    down_spec = pl.BlockSpec(
        (1, 1, bf, d),
        lambda j, f, layer, ids, n: (layer[0], ids[j], hidden(j, f, n), 0))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(E, nf),
            in_specs=[x_spec, in_spec, in_spec, down_spec],
            out_specs=x_spec,
            scratch_shapes=[pltpu.VMEM((cap, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, cap, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
        name="moe_held_experts",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), ids, jnp.reshape(n, (1,)),
      x, w_gate, w_up, w_down)
