"""Seeded random weights and inputs, made on the device by the benchmark.

The program under test and the plain references both take their weights
from here, so the reference never reads anything the program has made: it
calls the same generator with the same seed, one layer or one stage at a
time. Layer ``i``'s weights come from ``fold_in(key, i)``, so generating the
stacked model in one call and one layer alone give the same bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int, stream: int) -> jax.Array:
    """A threefry key from a seed of up to 64 bits and a stream id."""
    seed = int(seed) % (1 << 64)
    data = jnp.array([seed >> 32, seed & 0xFFFFFFFF], dtype=jnp.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(data), stream)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def granite_layer(key, c: dict, dtype=jnp.bfloat16) -> dict:
    """One decoder layer in the program's parameter layout: matmul weights
    N(0, 1/fan_in), norm scales 1."""
    d, h, kv, dh = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    ff = c["intermediate_size"]
    ks = jax.random.split(key, 7)
    return {
        "ln1": {"scale": jnp.ones((d,), dtype)},
        "ln2": {"scale": jnp.ones((d,), dtype)},
        "attn": {
            "wq": _normal(ks[0], (d, h * dh), d ** -0.5, dtype),
            "wk": _normal(ks[1], (d, kv * dh), d ** -0.5, dtype),
            "wv": _normal(ks[2], (d, kv * dh), d ** -0.5, dtype),
            "wo": _normal(ks[3], (h * dh, d), (h * dh) ** -0.5, dtype),
        },
        "mlp": {
            "w_gate": _normal(ks[4], (d, ff), d ** -0.5, dtype),
            "w_up": _normal(ks[5], (d, ff), d ** -0.5, dtype),
            "w_down": _normal(ks[6], (ff, d), ff ** -0.5, dtype),
        },
    }


def granite_embedding(key, c: dict, dtype=jnp.bfloat16) -> jax.Array:
    """The (vocab, hidden) matrix that embeds tokens and, tied, scores them:
    N(0, 1/hidden), rows of about unit norm. At N(0, 1) the tied head scores
    the input token itself some 15 standard deviations above every other, so
    every step would echo its input whatever the context; at this scale the
    logits spread by about 1 and the next token depends on the context."""
    d = c["hidden_size"]
    return _normal(key, (c["vocab_size"], d), d ** -0.5, dtype)


def granite_keys(seed: int) -> tuple[jax.Array, jax.Array]:
    """(embedding key, layer root key) of a seed."""
    return root_key(seed, 1), root_key(seed, 2)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _granite_params(keys, layers: int, cfg_items: tuple) -> dict:
    c = dict(cfg_items)
    k_embed, k_layers = keys
    stacked = jax.vmap(lambda i: granite_layer(
        jax.random.fold_in(k_layers, i), c))(jnp.arange(layers))
    d = c["hidden_size"]
    return {"embed": {"embedding": granite_embedding(k_embed, c)},
            "ln_f": {"scale": jnp.ones((d,), jnp.bfloat16)},
            "layers": stacked}


def _sizes(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size")
    return tuple((k, int(c[k])) for k in keys)


def granite_params(seed: int, c: dict) -> dict:
    """The whole model, stacked over layers, in one jitted call on the
    default device, in bf16."""
    return _granite_params(granite_keys(seed), int(c["num_hidden_layers"]),
                           _sizes(c))


@functools.partial(jax.jit, static_argnums=(1,))
def _one_layer(k_layers, cfg_items: tuple, i):
    return granite_layer(jax.random.fold_in(k_layers, i), dict(cfg_items))


def granite_layer_at(seed: int, c: dict, i: int) -> dict:
    """Layer ``i`` alone, bit-identical to its slice of ``granite_params``."""
    return _one_layer(granite_keys(seed)[1], _sizes(c), jnp.int32(i))


@functools.partial(jax.jit, static_argnums=(1,))
def _embedding(k_embed, cfg_items: tuple):
    return granite_embedding(k_embed, dict(cfg_items))


def granite_embedding_at(seed: int, c: dict) -> jax.Array:
    """The embedding alone, bit-identical to ``granite_params``'s."""
    return _embedding(granite_keys(seed)[0], _sizes(c))


# -- the streamed chain --------------------------------------------------
def chain_shapes(c: dict) -> list[tuple[str, tuple[int, int]]]:
    """(name, (K, N)) of every stage, layer by layer: each layer's chain of
    ``c["chain"]`` projections."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    dims = {"wq": (d, c["num_attention_heads"] * c["head_dim"]),
            "wo": (c["num_attention_heads"] * c["head_dim"], d),
            "w_up": (d, ff), "w_down": (ff, d)}
    return [(f"l{i}.{p}", dims[p]) for i in range(c["num_hidden_layers"])
            for p in c["chain"]]


@functools.partial(jax.jit, static_argnums=(1,))
def _stage_weight(key, shape):
    return _normal(key, shape, shape[0] ** -0.5, jnp.bfloat16)


def stage_weight(seed: int, index: int, shape: tuple[int, int]) -> jax.Array:
    """Stage ``index``'s (K, N) bf16 weight, N(0, 1/K), on the device."""
    return _stage_weight(jax.random.fold_in(root_key(seed, 3), index),
                         tuple(shape))


@functools.partial(jax.jit, static_argnums=(1,))
def _activation(key, shape):
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)


def chain_input(seed: int, index: int, shape: tuple[int, int]) -> np.ndarray:
    """Pass input ``index``: an (M, K) bf16 N(0, 1) activation, on the host."""
    return np.asarray(_activation(
        jax.random.fold_in(root_key(seed, 4), index), tuple(shape)))
