"""Chat serving: closed-loop clients through the program's continuous-
batching scheduler.

The timed path is ``ContinuousScheduler.step`` (lane mode, one tenant) ->
``ServingEngine.decode_lanes`` -> ``transformer.decode_step``, with the
scheduler's admission pass and ``offload_tenant_kv`` where the deployment
tiers the KV cache. Each client keeps one request outstanding and sends the
next as soon as the last completes (zero think time).
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import flops
import generator
import weights
from common import BENCH, Spans, load_module, percentile

TENANT = "chat"
#: requests the reference re-runs after the window (the longest among them)
SAMPLES = 4


def program_config(c: dict):
    """The program's model config, built from the benchmark's file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        rope_theta=c["rope_theta"], dtype=jnp.bfloat16)


class Run:
    def __init__(self, cfg: dict, traffic: dict, seed: int, peaks: dict):
        self.c, self.dep, self.mix = cfg, traffic["deployment"], traffic["mix"]
        self.seed, self.peaks = seed, peaks
        self.layers = cfg["num_hidden_layers"]
        self.spans = Spans()
        self.prompts: dict[str, np.ndarray] = {}
        self.info: dict = {}

    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        from repro.serving import EngineConfig, ServingEngine
        from repro.serving.scheduler import ContinuousScheduler, SchedulerConfig
        dep = self.dep
        t0 = time.perf_counter()
        params = jax.block_until_ready(weights.granite_params(self.seed, self.c))
        self.info["weights_s"] = time.perf_counter() - t0
        budget = None
        if dep["tier_kv"]:
            # the catalog total less both KV leaves: params + the int32 pos
            budget = sum(a.nbytes for a in jax.tree.leaves(params)) + 4
        ecfg = EngineConfig(max_batch=dep["lanes"], max_len=dep["max_len"],
                            hbm_budget_bytes=budget,
                            pool_nodes=dep["pool_nodes"])
        self.engine = ServingEngine(program_config(self.c), params, ecfg)
        del params
        remote = sorted(self.engine.placement.remote_names())
        want = ["cache['k']", "cache['v']"] if dep["tier_kv"] else []
        if remote != want:
            raise RuntimeError(f"placement demotes {remote}, expected {want}")
        self.sched = ContinuousScheduler(self.engine, SchedulerConfig(
            readvise_every=0, node_capacity_bytes=dep["node_capacity_bytes"],
            min_nodes=dep["pool_nodes"]))
        self.clients = generator.ChatRequests(self.mix, self.seed,
                                              self.c["vocab_size"])
        self._wrap()
        # de-phase: client i arrives at set-up step starts[i], with the
        # admission pass off, so the window opens with lanes out of phase
        t0 = time.perf_counter()
        starts = generator.dephase_starts(dep["lanes"], self.mix["dephase_steps"])
        for step in range(self.mix["dephase_steps"]):
            self._top_up(sum(1 for s in starts if s <= step))
            self.sched.step()
        self._top_up(dep["lanes"])
        self.sched.scfg.readvise_every = dep["readvise_every"]
        if dep["readvise_every"]:
            self.sched.readvise()   # the pool holds the tenant's KV from now
        self.info["dephase_s"] = time.perf_counter() - t0
        self.info["dephase_steps"] = self.mix["dephase_steps"]

    def _wrap(self) -> None:
        sched, eng = self.sched, self.engine

        def lanes_now(*_a, **_k):
            pos, n_gap, n_first = [], 0, 0
            for st in sched._lanes.values():
                n_prompt = len(st.prompt)
                if st.prompt_idx < n_prompt:
                    pos.append(st.prompt_idx)
                    n_first += st.prompt_idx == n_prompt - 1
                else:
                    pos.append(n_prompt + len(st.tokens) - 1)
                    n_gap += 1
            return pos, n_gap, n_first

        self.spans.wrap(eng, "decode_lanes", before=lanes_now)
        self.spans.wrap(eng, "reset_lanes")
        self.spans.wrap(eng, "offload_tenant_kv")
        self.spans.wrap(sched, "step")

    def _top_up(self, clients: int) -> None:
        """Keep one request outstanding for each of ``clients`` clients."""
        from repro.serving.scheduler import Request
        ts = self.sched.tenants.get(TENANT)
        done = len(ts.completed) if ts else 0
        for _ in range(clients - (self.clients.issued - done)):
            prompt, max_new = self.clients.next()
            rid = self.sched.submit(Request(TENANT, prompt, max_new=max_new))
            self.prompts[rid] = prompt

    # -- the window -------------------------------------------------------
    def window(self, seconds: float) -> None:
        self.done0 = len(self.sched.tenants[TENANT].completed)
        lanes = self.dep["lanes"]
        self.t0 = t0 = time.perf_counter()
        while True:
            self.sched.step()
            self._top_up(lanes)
            if time.perf_counter() - t0 >= seconds:
                break
        self.t1 = time.perf_counter()
        ts = self.sched.tenants[TENANT]
        self.n_attempted = (len(ts.completed) - self.done0
                            + len(self.sched._lanes))

    def attempted(self) -> int:
        """Requests served in the window: completed in it or in flight."""
        return self.n_attempted

    def failed(self) -> int:
        return 0

    def _decodes(self):
        """(all decode spans, index of the first one in the window)."""
        dec = self.spans.spans["decode_lanes"]
        first = next(i for i, s in enumerate(dec) if s[0] >= self.t0)
        return dec, first

    def end_to_end(self) -> dict:
        dec, first = self._decodes()
        tokens, gaps = 0, []
        for i in range(first, len(dec)):
            _t0, t1, (_pos, n_gap, n_first) = dec[i]
            tokens += n_gap + n_first
            gaps += [t1 - dec[i - 1][1]] * n_gap
        if not gaps:
            raise RuntimeError("no two consecutive tokens of one request in "
                               "the window: lengthen --seconds")
        return {"tok_s": tokens / (self.t1 - self.t0),
                "itl_p95_ms": percentile(gaps, 95) * 1e3}

    def record(self) -> dict:
        dec, first = self._decodes()
        win = dec[first:]
        steps = self.spans.within("step", self.t0, self.t1)
        step_s = [b - a for a, b, _ in steps]
        dec_s = [b - a for a, b, _ in win]
        c, L = self.c, self.layers
        return {
            "kind": "chat", "window_s": self.t1 - self.t0, "steps": len(steps),
            "decode_s": dec_s, "step_s": step_s,
            "token_flops": sum(flops.token_flops(c, L, p)
                               for _a, _b, (pos, _g, _f) in win for p in pos),
            "step_bytes": [flops.decode_step_bytes(c, L, pos)
                           for _a, _b, (pos, _g, _f) in win],
            "peaks": self.peaks,
        }

    # -- correctness ------------------------------------------------------
    def _samples(self) -> list[tuple[np.ndarray, np.ndarray]]:
        """Requests that were served in the window: those that completed in
        it and those still in flight with tokens, the one with the most
        served tokens first, then others drawn from the seed."""
        ts = self.sched.tenants[TENANT]
        cand = [(r["request_id"], np.asarray(r["tokens"]))
                for r in ts.completed[self.done0:]]
        cand += [(st.request.request_id, np.asarray(st.tokens, np.int32))
                 for st in self.sched._lanes.values() if st.tokens]
        cand.sort(key=lambda rt: (-len(rt[1]), rt[0]))
        if not cand:
            return []
        rest = cand[1:]
        order = generator.rng_for(self.seed, 20).permutation(len(rest))
        picked = [cand[0]] + [rest[i] for i in order[:SAMPLES - 1]]
        return [(self.prompts[rid], toks) for rid, toks in picked]

    def release(self) -> None:
        """Keep what the check needs, free every device buffer of the run."""
        self.samples = self._samples()
        self.sched = self.engine = None
        gc.collect()

    def _reference(self):
        return load_module(BENCH / "configs" / self.c["reference"])

    def readings(self, controls=()) -> dict:
        """The widest logit gap of the served tokens, and of each control's
        first choice at the same positions."""
        ref_mod = self._reference()
        if not self.samples:
            return {"served_tokens": 0.0}
        args = (self.seed, self.c, self.samples, self.dep["max_len"], SAMPLES)
        ref, served = ref_mod.served_logits(*args)
        out = {"max_logit_gap": ref_mod.widest_gap(ref, served),
               "served_tokens": float(len(served))}
        for q in controls:
            ctl, _ = ref_mod.served_logits(*args, quant=q)
            out[f"control_{q}"] = ref_mod.widest_gap(ref, ctl.argmax(axis=1))
        return out

    def check(self) -> dict:
        """{name: (value, limit, ok)} of every number compared."""
        r = self.readings()
        limit = self.c["correct"]["max_logit_gap"]
        gap = r.get("max_logit_gap", float("inf"))
        return {"max_logit_gap": (gap, limit, gap <= limit),
                "served_tokens": (r["served_tokens"], 1, r["served_tokens"] >= 1)}
