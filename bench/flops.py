"""Operations and bytes each measured piece of work needs, from its shapes.

Only useful work counts: positions beyond a lane's decode position are
masked work and are not counted, nor is recomputation. A share computed from
these numbers can therefore only rise when a change removes waste.
"""
from __future__ import annotations

BF16 = 2


def granite_layer_params(c: dict) -> int:
    """Matmul parameters of one decoder layer (attention + SwiGLU MLP)."""
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    dh, ff = c["head_dim"], c["intermediate_size"]
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * ff


def granite_weight_bytes(c: dict, layers: int) -> int:
    """Bytes of every weight a decode step reads: the layers' matmuls and
    norms, the final norm and one embedding matrix (the program ties the
    input embedding and the output head)."""
    d = c["hidden_size"]
    per_layer = granite_layer_params(c) + 2 * d
    return BF16 * (layers * per_layer + d + c["vocab_size"] * d)


def kv_bytes_per_token(c: dict, layers: int) -> int:
    """K and V bytes one cached position holds across ``layers`` layers."""
    return 2 * layers * c["num_key_value_heads"] * c["head_dim"] * BF16


def decode_step_bytes(c: dict, layers: int, positions) -> int:
    """HBM bytes a batched decode step needs: every weight once, the K/V of
    positions ``0..pos`` of each active lane read, and the new K/V written.
    ``positions`` holds each active lane's position before the step."""
    kv = kv_bytes_per_token(c, layers)
    return granite_weight_bytes(c, layers) + sum(
        kv * (int(p) + 1) + kv for p in positions)


def token_flops(c: dict, layers: int, pos: int) -> int:
    """FLOPs of one token at position ``pos`` through ``layers`` layers and
    the head: 2 per matmul parameter, plus scores and weighted values over
    the ``pos + 1`` visible positions."""
    h, dh = c["num_attention_heads"], c["head_dim"]
    matmul = layers * granite_layer_params(c) + c["hidden_size"] * c["vocab_size"]
    attn = layers * 2 * 2 * h * dh * (int(pos) + 1)
    return 2 * matmul + attn


def matmul_flops(m: int, k: int, n: int) -> int:
    """FLOPs of an (m, k) @ (k, n) product."""
    return 2 * m * k * n


def matmul_bytes(m: int, k: int, n: int, itemsize: int = BF16) -> int:
    """Least HBM traffic of an (m, k) @ (k, n) product: both operands read
    once and the result written once."""
    return itemsize * (m * k + k * n + m * n)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of FLOPs over peak
    FLOP/s and bytes over peak HBM bandwidth."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
