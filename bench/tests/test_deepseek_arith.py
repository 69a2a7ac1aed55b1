"""DeepSeek-V3's bytes and FLOPs against counts made by hand at published
sizes, and its chat driver end to end on the CPU at a tiny size."""
import json

import jax
import pytest

import deepseek_flops as flops
import run as harness
from common import load_module
from conftest import BENCH, FAKE_PEAKS, _load, cell, tiny_chat

DS = _load("configs", "deepseek-v3")
MB = 1e6
CELL = "deepseek-v3.chat-local-64"


def test_mla_params_by_hand():
    # wq_a 7168*1536 + wq_b 1536*128*192 + wkv_a 7168*576
    # + wkv_b 512*128*256 + wo 16384*7168
    hand = (11_010_048 + 37_748_736 + 4_128_768 + 16_777_216 + 117_440_512)
    assert flops.mla_params(DS) == hand == 187_105_280


def test_the_issue_table_of_bytes():
    """The table of ISSUE 15 section 1, bf16 (the router float32)."""
    assert flops.expert_bytes(DS) / MB == pytest.approx(88.08, abs=0.01)
    assert 2 * flops.mla_params(DS) / MB == pytest.approx(374.2, abs=0.05)
    assert flops.dense_layer_bytes(DS) / MB == pytest.approx(1166.9, abs=0.1)
    assert flops.moe_layer_bytes(DS) / MB == pytest.approx(1174.3, abs=0.1)
    assert flops.vocab_bytes(DS) / MB == pytest.approx(463.3, abs=0.05)
    assert flops.weight_bytes(DS) / MB == pytest.approx(8676, abs=1)
    lat = 64 * 4096 * flops.latent_bytes_per_token(DS)
    assert lat == 64 * 4096 * 576 * 7 * 2 and lat / MB == pytest.approx(2114, abs=1)


def test_step_bytes_and_flops_by_hand():
    d, E = 7168, 256
    fixed = (flops.dense_layer_bytes(DS) + 6 * flops.moe_layer_bytes(DS, 0)
             + 2 * 16160 * d + 2 * d)
    lat = 576 * 7 * 2
    # two lanes at positions 0 and 9, 3.5 active experts on average
    got = flops.step_bytes(DS, [0, 9], 3.5)
    want = fixed + 2 * 2 * d + 3.5 * 88_080_384 + lat * (1 + 10 + 2)
    assert got == pytest.approx(want)
    # a token at position 99: projections (absorbed), scores over 100 latents
    # and rope keys, the context, the dense SwiGLU, 6 routers and shared
    # experts, the head
    proj = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 128 * 128 * 512
            + 128 * 512 * 128 + 16384 * 7168)
    attn = 128 * 576 * 100 + 128 * 512 * 100
    ffn = 3 * 7168 * 18432 + 6 * (7168 * E + 3 * 7168 * 2048)
    assert flops.token_flops(DS, 99) == 2 * (7 * (proj + attn) + ffn
                                             + 7168 * 16160)
    assert flops.routed_flops(DS, 10) == 10 * 2 * 3 * 7168 * 2048
    assert flops.routed_bytes(DS, 10, 4) == 4 * 88_080_384 + 10 * 2 * 2 * 7168


def test_moe_experts_roofline_reader():
    reader = load_module(BENCH / "metrics" / "moe_experts_roofline.py")
    rec = {"kind": "chat", "peaks": FAKE_PEAKS, "moe_flops": 1e9,
           "moe_bytes": 2e10,
           "trace": {"ops": {"%moe_held_experts.3 bf16[8,64,7168]": 0.4,
                             "%fusion.1 f32[8]": 9.0}}}
    # bytes bind: 2e10 / 1e11 = 0.2 s of 0.4 s
    assert reader.read(rec) == pytest.approx(50.0)
    rec["trace"]["ops"] = {"%fusion.1 f32[8]": 9.0}
    assert reader.read(rec) is None
    assert reader.read({"kind": "chat"}) is None


def tiny_deepseek() -> dict:
    c = json.loads(json.dumps(DS))
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
             n_routed_experts=4, num_experts_per_tok=4, n_group=4,
             topk_group=2, num_hidden_layers=3, vocab_size=256)
    c["deployment"] = dict(c["deployment"], routed_experts=16, expert_shard=1)
    return c


@pytest.mark.parametrize("trace", [0, 1])
def test_driver_short_window(spec, trace):
    """setup, window, record or end_to_end, and check through
    ``run.measure``, as the chip runs them."""
    res = harness.measure(spec, cell(spec, CELL), tiny_deepseek(),
                          tiny_chat("chat-local-64"), 2**33 + 99, 1.0, trace,
                          jax.devices(), FAKE_PEAKS)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    names = {m["name"] for m in spec["per_layer" if trace else "end_to_end"]
             if CELL in m.get("workloads", [CELL])}
    if trace:
        # no device plane on the CPU: the trace-read metrics stay silent
        names -= {"decode_step_roofline", "moe_experts_roofline"}
    assert set(res["metrics"]) == names


def test_driver_record_counts_the_held_experts(spec):
    """The record carries the window's expert counts and the chat keys."""
    driver = load_module(BENCH / "drivers" / "chat_deepseek.py")
    run = driver.Run(tiny_deepseek(), tiny_chat("chat-local-64"), 2**33 + 1,
                     FAKE_PEAKS)
    run.setup()
    run.window(0.5)
    rec = run.record()
    steps = len(rec["decode_s"])
    assert 0 < rec["moe_active"] <= steps * 2 * 4
    assert rec["moe_active"] <= rec["moe_routed"] <= steps * 2 * 4 * 4
    assert rec["moe_bytes"] == flops.routed_bytes(run.c, rec["moe_routed"],
                                                  rec["moe_active"])
    assert len(rec["step_bytes"]) == steps and rec["token_flops"] > 0


def test_program_config_from_the_file():
    """The program's config keeps every width and routing rule of the file,
    the router over all 256 experts and 8 of them held."""
    driver = load_module(BENCH / "drivers" / "chat_deepseek.py")
    cfg = driver.program_config(DS)
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.moe_d_ff, cfg.d_ff) == (7168, 128, 1536, 512, 2048, 18432)
    assert (cfg.n_experts, cfg.n_experts_local, cfg.expert_shard,
            cfg.top_k) == (256, 8, 0, 8)
    assert (cfg.scoring_func, cfg.n_group, cfg.topk_group,
            cfg.routed_scaling_factor) == ("sigmoid", 8, 4, 2.5)
    assert (cfg.rope_factor, cfg.rope_mscale_all_dim) == (40.0, 1.0)
    assert not cfg.tie_embeddings and cfg.vocab_size == 16160


def _alter_tokens(monkeypatch):
    from repro.serving.engine import ServingEngine
    orig = ServingEngine.decode_lanes

    def altered(self, tokens):
        nxt, us = orig(self, tokens)
        return (nxt + 1) % self.cfg.vocab_size, us
    monkeypatch.setattr(ServingEngine, "decode_lanes", altered)


def _skip_held_experts(monkeypatch):
    """The held experts' part of every MoE layer dropped, the rest kept."""
    from repro.models import moe
    orig = moe._einsum_ffn

    def nothing(*weights):
        ffn = orig(*weights)
        return lambda xg, counts: 0 * ffn(xg, counts)
    monkeypatch.setattr(moe, "_einsum_ffn", nothing)


@pytest.mark.parametrize("fault", [_alter_tokens, _skip_held_experts])
def test_broken_timed_path_is_not_correct(spec, monkeypatch, fault):
    fault(monkeypatch)
    res = harness.measure(spec, cell(spec, CELL), tiny_deepseek(),
                          tiny_chat("chat-local-64"), 2**33 + 99, 1.0, 0,
                          jax.devices(), FAKE_PEAKS)
    assert res["correct"] is False
