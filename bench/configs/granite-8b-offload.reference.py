"""Plain reference for the granite-8b-offload chain: every stage's matmul in
float32 at the highest precision, the activation kept in float32 from stage
to stage. It imports nothing of the program; each stage's weight comes from
the benchmark's own generator, one stage at a time.

``quant`` turns it into the control: every weight rounded to int8 (or fp8
e4m3) with one scale per output column, the rest as above.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights

HI = jax.lax.Precision.HIGHEST


def quantize(w: jax.Array, kind: str | None) -> jax.Array:
    """A (K, N) weight in float32, rounded per output column to ``kind``."""
    w = w.astype(jnp.float32)
    if kind is None:
        return w
    amax = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30)
    if kind == "int8":
        return jnp.clip(jnp.round(w / (amax / 127.0)), -127, 127) * (amax / 127.0)
    if kind == "fp8":
        s = amax / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown precision {kind!r}")


@functools.partial(jax.jit, static_argnums=(2,))
def _stage(x, w, quant):
    return jnp.matmul(x, quantize(w, quant), precision=HI)


def chain_outputs(seed: int, c: dict, inputs, quant: str | None = None):
    """The chain over each (M, K) input; returns a float32 (n, M, N) array."""
    x = jnp.asarray(np.stack(inputs)).astype(jnp.float32)
    n, m, k = x.shape
    x = x.reshape(n * m, k)
    for idx, (_name, shape) in enumerate(weights.chain_shapes(c)):
        x = _stage(x, weights.stage_weight(seed, idx, shape), quant)
    return np.asarray(x).reshape(n, m, -1)


def worst_row_error(got: np.ndarray, ref: np.ndarray) -> float:
    """The largest relative L2 error of one row (one token's activation)."""
    got = got.astype(np.float32).reshape(-1, ref.shape[-1])
    ref = ref.reshape(-1, ref.shape[-1])
    num = np.linalg.norm(got - ref, axis=1)
    return float(np.max(num / np.linalg.norm(ref, axis=1)))
