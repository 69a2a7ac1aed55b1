"""Seeded random weights of a DeepSeek-V3 configuration, made on the device,
layer by layer, in the program's parameter layout.

The program and the plain reference both take their weights from here.
Layer ``i`` comes from ``fold_in(layer key, i)``; routed expert ``e`` of a
layer from ``fold_in(its experts key, e)`` for its global id ``e``, so a
shard's held experts are those of the uncut model. Whole stacks are filled
one layer at a time into buffers the fill donates: a stacked float32 draw
of six MoE layers would need 8.5 GB before its cast.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from weights import _normal, root_key

#: spread of the seeded e_score_correction_bias (the config file says why)
BIAS_STD = 0.005


def _dtype(c: dict):
    return jnp.dtype(c.get("dtype", "bfloat16"))


def held_ids(c: dict) -> jax.Array:
    """Global ids of the routed experts this chip holds."""
    n = c["n_routed_experts"]
    return c["deployment"]["expert_shard"] * n + jnp.arange(n)


def padded_rows(c: dict, multiple: int = 2048) -> int:
    """The program's embedding and head rows: the vocabulary padded up."""
    return -(-c["vocab_size"] // multiple) * multiple


def _mla(key, c: dict, dt) -> dict:
    d, H = c["hidden_size"], c["num_attention_heads"]
    qr, kr = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rdim, vh = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    ks = jax.random.split(key, 5)
    return {
        "wq_a": _normal(ks[0], (d, qr), d ** -0.5, dt),
        "q_ln": {"scale": jnp.ones((qr,), dt)},
        "wq_b": _normal(ks[1], (qr, H * (nope + rdim)), qr ** -0.5, dt),
        "wkv_a": _normal(ks[2], (d, kr + rdim), d ** -0.5, dt),
        "kv_ln": {"scale": jnp.ones((kr,), dt)},
        "wkv_b": _normal(ks[3], (kr, H * (nope + vh)), kr ** -0.5, dt),
        "wo": _normal(ks[4], (H * vh, d), (H * vh) ** -0.5, dt),
    }


def _swiglu(key, d: int, ff: int, dt) -> dict:
    ks = jax.random.split(key, 3)
    return {"w_gate": _normal(ks[0], (d, ff), d ** -0.5, dt),
            "w_up": _normal(ks[1], (d, ff), d ** -0.5, dt),
            "w_down": _normal(ks[2], (ff, d), ff ** -0.5, dt)}


def layer(key, c: dict, moe: bool) -> dict:
    """One decoder layer: MLA, then the dense SwiGLU or the MoE block (the
    router over every expert, its bias, the held experts, the shared one)."""
    d, dt = c["hidden_size"], _dtype(c)
    ks = jax.random.split(key, 6)
    p = {"ln1": {"scale": jnp.ones((d,), dt)},
         "ln2": {"scale": jnp.ones((d,), dt)},
         "attn": _mla(ks[0], c, dt)}
    if not moe:
        p["mlp"] = _swiglu(ks[1], d, c["intermediate_size"], dt)
        return p
    E, ff = c["deployment"]["routed_experts"], c["moe_intermediate_size"]
    experts = jax.vmap(lambda e: _swiglu(jax.random.fold_in(ks[2], e),
                                         d, ff, dt))(held_ids(c))
    p["moe"] = {
        "router": _normal(ks[3], (d, E), d ** -0.5, jnp.float32),
        "router_bias": _normal(ks[4], (E,), BIAS_STD, jnp.float32),
        **experts,
        "shared": _swiglu(ks[5], d, c["n_shared_experts"] * ff, dt),
    }
    return p


def _sizes(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "n_routed_experts", "n_shared_experts", "vocab_size", "dtype")
    dep = c["deployment"]
    return tuple((k, c.get(k, "bfloat16")) for k in keys) + (
        ("deployment", (("routed_experts", dep["routed_experts"]),
                        ("expert_shard", dep["expert_shard"]))),)


def _cfg(items: tuple) -> dict:
    c = dict(items)
    c["deployment"] = dict(c["deployment"])
    return c


@functools.partial(jax.jit, static_argnums=(1, 2))
def _layer(k_layers, items: tuple, moe: bool, i):
    return layer(jax.random.fold_in(k_layers, i), _cfg(items), moe)


def layer_at(seed: int, c: dict, i: int) -> dict:
    """Layer ``i`` (dense below ``first_k_dense_replace``), bit-identical to
    its slice of :func:`params`."""
    moe = i >= c["first_k_dense_replace"]
    return _layer(root_key(seed, 2), _sizes(c), moe, jnp.int32(i))


@functools.partial(jax.jit, static_argnums=(1,))
def _vocab(keys, items: tuple):
    c = _cfg(items)
    d, dt = c["hidden_size"], _dtype(c)
    shape = (c["vocab_size"], d)
    return (_normal(keys[0], shape, 1.0, dt), _normal(keys[1], shape, d ** -0.5, dt))


def vocab_at(seed: int, c: dict) -> tuple[jax.Array, jax.Array]:
    """The (vocab, hidden) embedding and head: this chip's rows of each."""
    return _vocab((root_key(seed, 1), root_key(seed, 5)), _sizes(c))


@functools.partial(jax.jit, donate_argnums=(0,))
def _put(stack, one, i):
    return jax.tree.map(
        lambda s, x: jax.lax.dynamic_update_index_in_dim(s, x, i, 0), stack, one)


def _stack(seed: int, c: dict, first: int, n: int) -> dict:
    one = layer_at(seed, c, first)
    stack = jax.tree.map(lambda x: jnp.zeros((n,) + x.shape, x.dtype), one)
    for i in range(n):
        if i:
            one = layer_at(seed, c, first + i)
        stack = _put(stack, one, i)
        del one
    return stack


def params(seed: int, c: dict) -> dict:
    """The whole model in the program's layout: ``dense_layers`` and
    ``layers`` stacked, embedding and head padded to :func:`padded_rows`
    with zero rows (the program masks them from the logits)."""
    nd, n = c["first_k_dense_replace"], c["num_hidden_layers"]
    emb, head = vocab_at(seed, c)
    pad = ((0, padded_rows(c) - c["vocab_size"]), (0, 0))
    p = {"embed": {"embedding": jnp.pad(emb, pad), "head": jnp.pad(head, pad)},
         "ln_f": {"scale": jnp.ones((c["hidden_size"],), _dtype(c))}}
    del emb, head
    if nd:
        p["dense_layers"] = _stack(seed, c, 0, nd)
    p["layers"] = _stack(seed, c, nd, n - nd)
    return p
