"""Operations and bytes of a DeepSeek-V3 decode step (latent attention and
the chip's share of the routed experts), from the configuration's shapes.

As in ``flops.py``, only useful work counts: the latent cache up to each
lane's position, and the held experts' work for the tokens routed to them
(read from the program's counters), never a masked position or an empty
capacity slot. The decode step is the absorbed form: the query is scored
against the 512-wide latent and its 64 rope dims, and the context is taken
over the latent before ``W_uv``.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def mla_params(c: dict) -> int:
    """Matmul parameters of one MLA block."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    qr, kr = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rdim, vh = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    return (d * qr + qr * H * (nope + rdim) + d * (kr + rdim)
            + kr * H * (nope + vh) + H * vh * d)


def expert_params(c: dict) -> int:
    """Parameters of one routed expert (SwiGLU of the expert width)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: dict) -> int:
    return BF16 * expert_params(c)


def _norms(c: dict) -> int:
    return 2 * c["hidden_size"] + c["q_lora_rank"] + c["kv_lora_rank"]


def dense_layer_bytes(c: dict) -> int:
    """MLA, its norms and the dense SwiGLU, bf16."""
    return BF16 * (mla_params(c) + _norms(c)
                   + 3 * c["hidden_size"] * c["intermediate_size"])


def router_bytes(c: dict) -> int:
    """The float32 router over every routed expert, and its bias."""
    E = c["deployment"]["routed_experts"]
    return F32 * (c["hidden_size"] * E + E)


def moe_layer_bytes(c: dict, experts: int | None = None) -> int:
    """MLA, norms, router, the shared expert and ``experts`` held experts
    (all of them by default)."""
    n = c["n_routed_experts"] if experts is None else experts
    return (BF16 * (mla_params(c) + _norms(c))
            + (n + c["n_shared_experts"]) * expert_bytes(c) + router_bytes(c))


def vocab_bytes(c: dict) -> int:
    """The embedding and the head: this chip's rows of each, bf16."""
    return 2 * BF16 * c["vocab_size"] * c["hidden_size"]


def weight_bytes(c: dict) -> int:
    """Every weight the chip holds (the final norm included)."""
    nd, n = c["first_k_dense_replace"], c["num_hidden_layers"]
    return (nd * dense_layer_bytes(c) + (n - nd) * moe_layer_bytes(c)
            + vocab_bytes(c) + BF16 * c["hidden_size"])


def latent_bytes_per_token(c: dict) -> int:
    """Latent and rope-key bytes one cached position holds over all layers."""
    return (BF16 * c["num_hidden_layers"]
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]))


def step_bytes(c: dict, positions, active_experts: float) -> float:
    """Least HBM bytes of one batched decode step: every weight but the
    routed experts and the embedding once, the embedding rows of the lanes,
    ``active_experts`` held experts once (those with a token this step),
    the latent of positions ``0..pos`` of each lane read and its new latent
    written. ``positions`` holds each lane's position before the step."""
    nd, n = c["first_k_dense_replace"], c["num_hidden_layers"]
    d = c["hidden_size"]
    fixed = (nd * dense_layer_bytes(c) + (n - nd) * moe_layer_bytes(c, 0)
             + BF16 * c["vocab_size"] * d + BF16 * d)
    lat = latent_bytes_per_token(c)
    return (fixed + BF16 * d * len(positions)
            + active_experts * expert_bytes(c)
            + sum(lat * (int(p) + 1) + lat for p in positions))


def token_flops(c: dict, pos: int) -> int:
    """FLOPs of one token at position ``pos``, all but its routed experts:
    MLA's projections (with ``W_uk`` absorbed into the query and ``W_uv``
    applied to the latent context), scores over the ``pos + 1`` visible
    latents and rope keys, the context over the latents, the dense layers'
    SwiGLU, each MoE layer's router and shared expert, and the head."""
    nd, n = c["first_k_dense_replace"], c["num_hidden_layers"]
    d, H = c["hidden_size"], c["num_attention_heads"]
    qr, kr = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rdim, vh = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    E = c["deployment"]["routed_experts"]
    proj = (d * qr + qr * H * (nope + rdim) + d * (kr + rdim)
            + H * nope * kr + H * kr * vh + H * vh * d)
    attn = H * (kr + rdim) * (int(pos) + 1) + H * kr * (int(pos) + 1)
    ffn = (nd * 3 * d * c["intermediate_size"]
           + (n - nd) * (d * E + c["n_shared_experts"] * expert_params(c)))
    return 2 * (n * (proj + attn) + ffn + d * c["vocab_size"])


def routed_flops(c: dict, routed: int) -> int:
    """FLOPs of ``routed`` token slots through a held expert."""
    return 2 * expert_params(c) * int(routed)


def routed_bytes(c: dict, routed: int, active: int) -> int:
    """Least bytes of the held experts' work: each active (layer, expert)
    pair's weights once, and each routed slot's input read and output
    written."""
    return active * expert_bytes(c) + 2 * BF16 * c["hidden_size"] * int(routed)
