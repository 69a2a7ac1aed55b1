"""Chat serving of a DeepSeek-V3 configuration (latent attention, the chip's
share of the routed experts) through the program's continuous-batching
scheduler.

The timed path, the window, the spans and the end-to-end numbers are
``chat.py``'s: ``ContinuousScheduler.step`` (lane mode, one tenant) ->
``ServingEngine.decode_lanes`` -> ``transformer.decode_step``. This driver
builds the program's config and weights from the DeepSeek-V3 file, and
counts the step's operations and bytes for latent attention and the held
experts, whose work it reads from ``ServingEngine.moe_counts`` once before
and once after the window.

The check compares two numbers with the float32 reference: the widest gap
of a served token's logit below the reference's best, as ``chat.py`` does,
and the mean gap over the served tokens. In bfloat16 a token's router
scores differ from float32 by about 1e-3, so now and then a held expert
enters or leaves its top-8 near a tie, and that one token's hidden state
moves by a whole expert's share: the widest gap reads such rare flips,
the mean reads how far the logits stray everywhere.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

import deepseek_flops as flops
import deepseek_weights
import generator
from common import BENCH, load_module

chat = load_module(BENCH / "drivers" / "chat.py")


def program_config(c: dict):
    """The program's model config, built from the benchmark's file. Raises
    at once where the program lacks a field the configuration needs."""
    from repro.configs.base import ModelConfig
    dep, rs = c["deployment"], c["rope_scaling"]
    return ModelConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], attention="mla",
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original_max_position=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        n_experts=dep["routed_experts"], n_experts_held=c["n_routed_experts"],
        expert_shard=dep["expert_shard"],
        n_shared_experts=c["n_shared_experts"],
        top_k=c["num_experts_per_tok"], moe_d_ff=c["moe_intermediate_size"],
        first_k_dense=c["first_k_dense_replace"],
        scoring_func=c["scoring_func"], n_group=c["n_group"],
        topk_group=c["topk_group"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        tie_embeddings=c["tie_word_embeddings"],
        dtype=jnp.dtype(c.get("dtype", "bfloat16")))


class Run(chat.Run):
    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        from repro.serving import EngineConfig, ServingEngine
        from repro.serving.scheduler import ContinuousScheduler, SchedulerConfig
        dep = self.dep
        if dep["tier_kv"] or dep["pool_nodes"]:
            raise ValueError("this driver serves a deployment with no KV tier")
        cfg = program_config(self.c)
        t0 = time.perf_counter()
        params = jax.block_until_ready(deepseek_weights.params(self.seed, self.c))
        self.info["weights_s"] = time.perf_counter() - t0
        self.engine = ServingEngine(cfg, params, EngineConfig(
            max_batch=dep["lanes"], max_len=dep["max_len"]))
        del params
        remote = sorted(self.engine.placement.remote_names())
        if remote:
            raise RuntimeError(f"placement demotes {remote} with no budget")
        self.sched = ContinuousScheduler(self.engine, SchedulerConfig(
            readvise_every=0, node_capacity_bytes=dep["node_capacity_bytes"],
            min_nodes=0))
        self.clients = generator.ChatRequests(self.mix, self.seed,
                                              self.c["vocab_size"])
        self._wrap()
        t0 = time.perf_counter()
        starts = generator.dephase_starts(dep["lanes"], self.mix["dephase_steps"])
        for step in range(self.mix["dephase_steps"]):
            self._top_up(sum(1 for s in starts if s <= step))
            self.sched.step()
        self._top_up(dep["lanes"])
        self.info["dephase_s"] = time.perf_counter() - t0
        self.info["dephase_steps"] = self.mix["dephase_steps"]
        self.counts0 = self.engine.moe_counts()

    # -- correctness ------------------------------------------------------
    def readings(self, controls=()) -> dict:
        """The widest and the mean logit gap of the served tokens, and of
        each control's first choice at the same positions."""
        ref_mod = self._reference()
        if not self.samples:
            return {"served_tokens": 0.0}
        args = (self.seed, self.c, self.samples, self.dep["max_len"],
                chat.SAMPLES)
        ref, served = ref_mod.served_logits(*args)
        out = {"max_logit_gap": ref_mod.widest_gap(ref, served),
               "mean_logit_gap": ref_mod.mean_gap(ref, served),
               "served_tokens": float(len(served))}
        for q in controls:
            picks = ref_mod.served_logits(*args, quant=q)[0].argmax(axis=1)
            out[f"control_{q}"] = ref_mod.widest_gap(ref, picks)
            out[f"control_{q}_mean"] = ref_mod.mean_gap(ref, picks)
        return out

    def check(self) -> dict:
        """{name: (value, limit, ok)} of every number compared."""
        r = self.readings()
        out = {}
        for name in ("max_logit_gap", "mean_logit_gap"):
            limit = self.c["correct"][name]
            value = r.get(name, float("inf"))
            out[name] = (value, limit, value <= limit)
        n = r["served_tokens"]
        out["served_tokens"] = (n, 1, n >= 1)
        return out

    # -- what the window's steps did ----------------------------------------
    def record(self) -> dict:
        """``chat.py``'s record, its FLOPs and bytes counted for latent
        attention and the held experts, with the window's expert counts."""
        counts = self.engine.moe_counts()
        routed = counts["moe_routed"] - self.counts0["moe_routed"]
        active = counts["moe_active"] - self.counts0["moe_active"]
        dec, first = self._decodes()
        win = dec[first:]
        steps = self.spans.within("step", self.t0, self.t1)
        c = self.c
        per_step = active / max(len(win), 1)
        return {
            "kind": "chat", "window_s": self.t1 - self.t0, "steps": len(steps),
            "decode_s": [b - a for a, b, _ in win],
            "step_s": [b - a for a, b, _ in steps],
            "token_flops": (sum(flops.token_flops(c, p)
                                for _a, _b, (pos, _g, _f) in win for p in pos)
                            + flops.routed_flops(c, routed)),
            "step_bytes": [flops.step_bytes(c, pos, per_step)
                           for _a, _b, (pos, _g, _f) in win],
            "moe_routed": routed, "moe_active": active,
            "moe_flops": flops.routed_flops(c, routed),
            "moe_bytes": flops.routed_bytes(c, routed, active),
            "peaks": self.peaks,
        }
