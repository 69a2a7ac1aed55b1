"""Plain reference for deepseek-v3: the decoder's full forward pass over a
whole sequence, in float32 at the highest matmul precision, with no cache,
absorption, kernels or batching tricks. It imports nothing of the program;
its weights come from the benchmark's own generator, one layer at a time.

Equations (arXiv:2412.19437 and the released config, as the configuration
file states them; rope rotates halves, a listed departure):
x = E[tokens]. Per layer, with h = RMSNorm(x):
  c_q = RMSNorm(h Wq_a); [q_nope, q_rope] = c_q Wq_b per head;
  [c_kv, k_rope] = h Wkv_a; c_kv = RMSNorm(c_kv); [k_nope, v] = c_kv Wkv_b;
  q_rope, k_rope rotated by YaRN's frequencies (factor 40 over 4096);
  x += Wo softmax(([q_nope, q_rope] . [k_nope, k_rope]) * s, causal) v with
  s = mscale(40, 1)^2 / sqrt(192);
then with h = RMSNorm(x) the dense layer adds SwiGLU(h), an MoE layer adds
the shared expert and, for each held expert e chosen for the token,
w_e * SwiGLU_e(h). The gate: s = sigmoid(h W_r) over all routed experts;
choice = s + bias, kept only in the topk_group groups whose two best
choices sum highest; the top-k of it; w = s / sum of chosen s * 2.5.
logits = RMSNorm(x) H^T over this chip's vocabulary rows.

``quant`` turns the reference into the control: every weight matrix (the
router's too) rounded to int8 (or fp8 e4m3) with one scale per output
channel, the rest as above.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import deepseek_weights as dw

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 256
#: samples are padded to a multiple of this, so few shapes compile
PAD = 512


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


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def yarn_inv_freq(c: dict) -> np.ndarray:
    """YaRN's rotary frequencies for the rope dims."""
    dim, theta = c["qk_rope_head_dim"], float(c["rope_theta"])
    rs = c["rope_scaling"]
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    inter = extra / rs["factor"]

    def dim_at(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi)) / (2 * math.log(theta)))

    low = max(math.floor(dim_at(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_at(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def softmax_scale(c: dict) -> float:
    rs = c["rope_scaling"]
    m = _mscale(rs["factor"], rs["mscale_all_dim"])
    return m * m / math.sqrt(c["qk_nope_head_dim"] + c["qk_rope_head_dim"])


def _rope(x, inv_freq, cos_scale):
    """x: (S, heads, D); rotate the two halves by position."""
    S, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang) * cos_scale, jnp.sin(ang) * cos_scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, w):
    return _mm(jax.nn.silu(_mm(h, w["w_gate"])) * _mm(h, w["w_up"]),
               w["w_down"])


def gate(h, router, bias, c: dict):
    """(S, E) combine weights of every routed expert: the DeepSeek-V3 gate,
    zero for experts not chosen."""
    k, n_group, topk_group = (c["num_experts_per_tok"], c["n_group"],
                              c["topk_group"])
    s = jax.nn.sigmoid(_mm(h, router))                     # (S, E)
    choice = s + bias
    S, E = s.shape
    groups = choice.reshape(S, n_group, E // n_group)
    g_score = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)  # two best of each
    g_rank = jnp.argsort(jnp.argsort(-g_score, axis=-1), axis=-1)
    g_keep = g_rank < topk_group                           # (S, n_group)
    choice = jnp.where(jnp.repeat(g_keep, E // n_group, axis=-1), choice,
                       -jnp.inf)
    rank = jnp.argsort(jnp.argsort(-choice, axis=-1), axis=-1)
    chosen = rank < k
    w = jnp.where(chosen, s, 0.0)
    return w / w.sum(-1, keepdims=True) * c["routed_scaling_factor"]


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, p, items: tuple, moe: bool, quant):
    c = _cfg(items)
    S, d = x.shape
    H = c["num_attention_heads"]
    nope, rdim, vh = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    kr, eps = c["kv_lora_rank"], c["rms_norm_eps"]
    rs = c["rope_scaling"]
    cos_scale = _mscale(rs["factor"], rs["mscale"]) / _mscale(
        rs["factor"], rs["mscale_all_dim"])
    inv = jnp.asarray(yarn_inv_freq(c))
    a = {k: quantize(v, 0, quant) for k, v in p["attn"].items()
         if k.startswith("w")}
    f32 = jnp.float32
    h = _rmsnorm(x, eps) * p["ln1"]["scale"].astype(f32)
    cq = _rmsnorm(_mm(h, a["wq_a"]), eps) * p["attn"]["q_ln"]["scale"].astype(f32)
    q = _mm(cq, a["wq_b"]).reshape(S, H, nope + rdim)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, cos_scale)], -1)
    ckv = _mm(h, a["wkv_a"])
    lat = _rmsnorm(ckv[:, :kr], eps) * p["attn"]["kv_ln"]["scale"].astype(f32)
    k_rope = _rope(ckv[:, None, kr:], inv, cos_scale)        # (S, 1, rdim)
    kv = _mm(lat, a["wkv_b"]).reshape(S, H, nope + vh)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (S, H, rdim))], -1)
    v = kv[..., nope:]
    qc = q.reshape(S // Q_CHUNK, Q_CHUNK, H, nope + rdim)
    scale = softmax_scale(c)

    def chunk(j):
        s = jnp.einsum("qhd,shd->hqs", qc[j], k, precision=HI) * scale
        qpos = j * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(jnp.arange(S)[None, :] <= qpos[:, None], s, -jnp.inf)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    o = jax.lax.map(chunk, jnp.arange(S // Q_CHUNK)).reshape(S, H * vh)
    x = x + _mm(o, a["wo"])
    h = _rmsnorm(x, eps) * p["ln2"]["scale"].astype(f32)
    if not moe:
        w = {k: quantize(v, 0, quant) for k, v in p["mlp"].items()}
        return x + _swiglu(h, w)
    m = p["moe"]
    out = _swiglu(h, {k: quantize(v, 0, quant) for k, v in m["shared"].items()})
    weights = gate(h, quantize(m["router"], 0, quant),
                   m["router_bias"].astype(f32), c)
    ids = c["deployment"]["expert_shard"] * c["n_routed_experts"] + jnp.arange(
        c["n_routed_experts"])

    def expert(acc, e):
        i, gid = e
        w = {k: quantize(m[k][i], 0, quant)
             for k in ("w_gate", "w_up", "w_down")}
        return acc + weights[:, gid, None] * _swiglu(h, w), None

    out, _ = jax.lax.scan(expert, out,
                          (jnp.arange(c["n_routed_experts"]), ids))
    return x + out


@functools.partial(jax.jit, static_argnums=(3,))
def _head(x, cols, head, eps):
    return _mm(_rmsnorm(x[cols], eps), head.T)


def _items(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rms_norm_eps", "rope_theta", "num_experts_per_tok",
            "n_group", "topk_group", "routed_scaling_factor",
            "n_routed_experts")
    rs, dep = c["rope_scaling"], c["deployment"]
    return tuple((k, c[k]) for k in keys) + (
        ("rope_scaling", tuple(sorted((k, v) for k, v in rs.items()
                                      if k != "type"))),
        ("expert_shard", dep["expert_shard"]))


def _cfg(items: tuple) -> dict:
    c = dict(items)
    c["rope_scaling"] = dict(c["rope_scaling"])
    c["deployment"] = {"expert_shard": c["expert_shard"]}
    return c


def served_logits(seed: int, c: dict, samples, seq_len: int, batch: int,
                  quant: str | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Logits at every served position of every sample.

    ``samples`` holds up to ``batch`` (prompt ids, served ids) pairs. Each
    is run alone, as one sequence padded at the end to the longest one
    rounded up to a multiple of 512 (the causal mask keeps padding out of
    every scored position), layer by layer over all samples. Returns the
    (N, vocab) float32 logits that predict each served token, in sample
    order, and the N served tokens.
    """
    del batch
    seqs, cols, served = [], [], []
    for prompt, out in samples:
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        if len(seq) > seq_len:
            raise ValueError(f"sample of {len(seq)} tokens > {seq_len}")
        seqs.append(seq)
        cols.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(out)))
        served.append(out)
    if not seqs:
        return np.zeros((0, c["vocab_size"]), np.float32), np.zeros(0, np.int64)
    S = -(-max(len(s) for s in seqs) // PAD) * PAD
    emb, head = dw.vocab_at(seed, c)
    emb = quantize(emb, 1, quant)
    xs = [emb[jnp.asarray(np.pad(s, (0, S - len(s))))] for s in seqs]
    del emb
    items, nd = _items(c), c["first_k_dense_replace"]
    for i in range(c["num_hidden_layers"]):
        p = dw.layer_at(seed, c, i)
        xs = [_layer(x, p, items, i >= nd, quant) for x in xs]
        del p
    head = quantize(head, 1, quant)
    logits = []
    for x, col in zip(xs, cols):
        padded = np.pad(col, (0, -len(col) % Q_CHUNK))  # few head shapes
        out = _head(x, jnp.asarray(padded), head, c["rms_norm_eps"])
        logits.append(np.asarray(out[:len(col)]))
    return np.concatenate(logits), np.concatenate(served).astype(np.int64)


def widest_gap(ref: np.ndarray, tokens: np.ndarray) -> float:
    """The widest gap by which a chosen token's reference logit lies below
    the reference's best logit at its position."""
    if len(tokens) == 0:
        return 0.0
    picked = ref[np.arange(len(tokens)), tokens]
    return float(np.max(ref.max(axis=1) - picked))


def mean_gap(ref: np.ndarray, tokens: np.ndarray) -> float:
    """The mean, over the chosen tokens, of the gap by which each one's
    reference logit lies below the reference's best logit at its position."""
    if len(tokens) == 0:
        return 0.0
    picked = ref[np.arange(len(tokens)), tokens]
    return float(np.mean(ref.max(axis=1) - picked))
