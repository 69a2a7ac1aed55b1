"""Mamba2 SSD chunk kernel (state-space duality) for TPU.

Grid (batch, head, chunk) with the chunk dim minor/sequential: the running
SSM state (P, N) lives in VMEM scratch and is carried across chunk steps —
the recurrence the pure-jnp implementation expresses as a lax.scan. Per
chunk, the intra-chunk quadratic term, the chunk-state construction, and the
inter-chunk broadcast are all (Q x Q)/(Q x N)/(Q x P) MXU matmuls.

Inputs are the precomputed per-chunk tensors (the cheap cumsum/broadcast prep
lives in ops.py); everything hot is in the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(x_ref, b_ref, c_ref, dtr_ref, dtc_ref, cumr_ref, cumc_ref,
            y_ref, state):
    # dt and cum arrive twice, as a (1, Q) row and a (Q, 1) column: Mosaic
    # needs the last two block dims tile-aligned or whole, and the decay
    # matrix is an outer difference of the two orientations.
    cb = pl.program_id(2)

    @pl.when(cb == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    Bm = b_ref[0, 0, 0].astype(jnp.float32)       # (Q, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)       # (Q, N)
    dt_row = dtr_ref[0, 0, 0].astype(jnp.float32)     # (1, Q)
    dt_col = dtc_ref[0, 0, 0].astype(jnp.float32)     # (Q, 1)
    cum_row = cumr_ref[0, 0, 0].astype(jnp.float32)   # (1, Q)
    cum_col = cumc_ref[0, 0, 0].astype(jnp.float32)   # (Q, 1)
    Q = x.shape[0]
    # cum[Q-1] as a masked lane sum: a (1, 1) slice at lane Q-1 keeps that
    # lane offset, which Mosaic cannot broadcast down a column
    last = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    total = jnp.sum(jnp.where(last, cum_row, 0.0), axis=1, keepdims=True)

    # intra-chunk: scores (Q,Q) = C_i . B_j, decay L[i,j] = exp(cum_i - cum_j)
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    li = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lmat = jnp.exp(cum_col - cum_row) * dt_row
    lmat = jnp.where(li >= lj, lmat, 0.0)
    y_intra = jax.lax.dot_general(
        scores * lmat, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # inter-chunk: contribution of the carried state
    y_inter = jax.lax.dot_general(
        Cm, state[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * jnp.exp(cum_col)                          # (Q, P)

    # chunk-local state and carry update
    decay_out = jnp.exp(total - cum_col) * dt_col * Bm          # (Q, N)
    s_local = jax.lax.dot_general(
        x, decay_out, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # (P, N)
    state[...] = jnp.exp(total) * state[...] + s_local

    y_ref[0, 0, 0] = (y_intra + y_inter).astype(y_ref.dtype)


def ssd_chunk_scan_tpu(
    xc: jax.Array,    # (B, H, nc, Q, P)
    bc: jax.Array,    # (B, H, nc, Q, N)  (per-head broadcast B)
    cc: jax.Array,    # (B, H, nc, Q, N)
    dtc: jax.Array,   # (B, H, nc, Q)     fp32 (softplus'd dt)
    cum: jax.Array,   # (B, H, nc, Q)     fp32 inclusive cumsum of dt*A
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """SSD chunk scan; ``interpret=None`` resolves per platform."""
    return _ssd_call(xc, bc, cc, dtc, cum,
                     interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_call(xc, bc, cc, dtc, cum, *, interpret: bool) -> jax.Array:
    B, H, nc, Q, P = xc.shape
    N = bc.shape[-1]
    grid = (B, H, nc)

    def chunk_spec(*tail):
        return pl.BlockSpec((1, 1, 1, *tail),
                            lambda b, h, c: (b, h, c) + (0,) * len(tail))

    row, col = chunk_spec(1, Q), chunk_spec(Q, 1)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[chunk_spec(Q, P), chunk_spec(Q, N), chunk_spec(Q, N),
                  row, col, row, col],
        out_specs=chunk_spec(Q, P),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, Q, P), xc.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xc, bc, cc, dtc[..., None, :], dtc[..., None], cum[..., None, :],
      cum[..., None])
