"""Plain reference for granite-8b: the decoder's full forward pass over a
whole sequence, in float32 at the highest matmul precision, with no cache,
kernels or batching tricks. It imports nothing of the program; its weights
come from the benchmark's own generator, one layer at a time.

Equations (Llama-style decoder, as the configuration file states them):
x = E[tokens]; per layer x += Wo attn(rope(RMSNorm(x) Wq), rope(RMSNorm(x)
Wk), RMSNorm(x) Wv) under a causal mask with grouped K/V heads, then
x += (silu(h Wgate) * (h Wup)) Wdown with h = RMSNorm(x); logits =
RMSNorm(x) E^T. The head reuses the embedding matrix E, as the program does.

``quant`` turns the reference into the control: every weight rounded to
int8 (or fp8 e4m3) with one scale per output channel, the rest as above.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 256


def quantize(w: jax.Array, axis: int, kind: str | None) -> jax.Array:
    """``w`` in float32, rounded to ``kind`` per output channel (``axis`` is
    the input axis the scale spans), or as it is for ``kind=None``."""
    w = w.astype(jnp.float32)
    if kind is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if kind == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if kind == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown precision {kind!r}")


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (B, S, heads, D); rotate the two halves by position."""
    S, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, p, cfg_items: tuple, quant):
    c = dict(cfg_items)
    B, S, d = x.shape
    H, KV, Dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    G = H // KV
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    w = {k: quantize(v, 0, quant) for k, v in {**p["attn"], **p["mlp"]}.items()}
    h = _rmsnorm(x, eps) * p["ln1"]["scale"].astype(jnp.float32)
    q = _rope(jnp.matmul(h, w["wq"], precision=HI).reshape(B, S, H, Dh), theta)
    k = _rope(jnp.matmul(h, w["wk"], precision=HI).reshape(B, S, KV, Dh), theta)
    v = jnp.matmul(h, w["wv"], precision=HI).reshape(B, S, KV, Dh)
    q = q.reshape(B, S // Q_CHUNK, Q_CHUNK, KV, G, Dh)

    def chunk(j):
        qj = q[:, j]                                   # (B, Q, KV, G, Dh)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qj, k, precision=HI) / np.sqrt(Dh)
        qpos = j * Q_CHUNK + jnp.arange(Q_CHUNK)
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", pr, v, precision=HI)

    o = jax.lax.map(chunk, jnp.arange(S // Q_CHUNK))  # (nq, B, Q, KV, G, Dh)
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * Dh)
    x = x + jnp.matmul(o, w["wo"], precision=HI)
    h = _rmsnorm(x, eps) * p["ln2"]["scale"].astype(jnp.float32)
    g = jax.nn.silu(jnp.matmul(h, w["w_gate"], precision=HI))
    u = jnp.matmul(h, w["w_up"], precision=HI)
    return x + jnp.matmul(g * u, w["w_down"], precision=HI)


@functools.partial(jax.jit, static_argnums=(4,))
def _head(x, rows, cols, emb, eps):
    xs = _rmsnorm(x[rows, cols], eps)
    return jnp.matmul(xs, emb.T, precision=HI)


def _items(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size", "rms_norm_eps",
            "rope_theta")
    return tuple((k, c[k]) for k in keys)


def served_logits(seed: int, c: dict, samples, seq_len: int, batch: int,
                  quant: str | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Logits at every served position of every sample.

    ``samples`` holds up to ``batch`` (prompt ids, served ids) pairs; they are
    packed as rows of one (batch, seq_len) batch, padded at the end (the causal mask
    keeps padding out of every scored position). Returns the (N, vocab)
    float32 logits that predict each served token, in sample order, and the
    N served tokens.
    """
    if seq_len % Q_CHUNK:
        raise ValueError(f"seq_len {seq_len} is not a multiple of {Q_CHUNK}")
    toks = np.zeros((batch, seq_len), np.int32)
    rows, cols, served = [], [], []
    for b, (prompt, out) in enumerate(samples):
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        if len(seq) > seq_len:
            raise ValueError(f"sample of {len(seq)} tokens > {seq_len}")
        toks[b, :len(seq)] = seq
        n = len(out)
        rows += [b] * n
        cols += list(range(len(prompt) - 1, len(prompt) - 1 + n))
        served.append(out)
    n = len(rows)
    pad = -n % 512   # a fixed set of head shapes, so the compile cache hits
    rows, cols = rows + [0] * pad, cols + [0] * pad
    emb = quantize(weights.granite_embedding_at(seed, c), 1, quant)
    x = emb[jnp.asarray(toks)]
    items = _items(c)
    for i in range(c["num_hidden_layers"]):
        x = _layer(x, weights.granite_layer_at(seed, c, i), items, quant)
    logits = _head(x, jnp.asarray(rows), jnp.asarray(cols), emb,
                   c["rms_norm_eps"])
    return np.asarray(logits[:n]), np.concatenate(served).astype(np.int64)


def widest_gap(ref: np.ndarray, tokens: np.ndarray) -> float:
    """The widest gap by which a chosen token's reference logit lies below
    the reference's best logit at its position."""
    picked = ref[np.arange(len(tokens)), tokens]
    return float(np.max(ref.max(axis=1) - picked))
