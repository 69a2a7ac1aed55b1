"""Offloaded prefill: passes of one activation batch through a chain of
streamed matmul stages, via the program's ``StreamingExecutor``.

The timed path is ``StreamingExecutor.run``: resident stages compute from
HBM, streamed stages come from host memory through ``HostFetchEngine``'s
real host-to-HBM copies, one stage ahead, and every stage runs the compiled
``streaming_matmul`` kernel.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import flops
import generator
import weights
from common import BENCH, Spans, load_module

#: outputs kept for the check: two of the first eight passes, drawn from
#: the seed, and the last pass
KEEP = 3
FIRST = 8


class Run:
    def __init__(self, cfg: dict, traffic: dict, seed: int, peaks: dict):
        self.c, self.dep, self.mix = cfg, traffic["deployment"], traffic["mix"]
        self.seed, self.peaks = seed, peaks
        self.spans = Spans()
        self.info: dict = {}

    def setup(self) -> None:
        from repro.core.exec import StreamingExecutor, StreamStage
        dep, mix = self.dep, self.mix
        blocks = {k: dep[k] for k in ("block_m", "block_n", "block_k")}
        t0 = time.perf_counter()
        stages = []
        # each stage is made on the device and kept on the host: the
        # executor seats resident stages in HBM itself and reads streamed
        # ones from host memory, so HBM never holds more than the window
        for i, (name, shape) in enumerate(weights.chain_shapes(self.c)):
            w = np.asarray(weights.stage_weight(self.seed, i, shape))
            stages.append(StreamStage(name=name, op="matmul", params={"w": w},
                                      kwargs=blocks))
        self.info["weights_s"] = time.perf_counter() - t0
        self.shapes = [st.params["w"].shape for st in stages]
        k0 = self.shapes[0][0]
        self.inputs = [weights.chain_input(self.seed, i, (mix["m"], k0))
                       for i in range(mix["inputs"])]
        t0 = time.perf_counter()
        self.ex = StreamingExecutor(stages, prefetch=dep["prefetch"],
                                    throttle=dep["throttle"])
        plan = self.ex.plan_tiers(dep["local_fraction"])
        self.info["seat_s"] = time.perf_counter() - t0
        self.info["streamed_stages"] = len(plan.remote_names())
        self.info["streamed_bytes"] = int(plan.remote_bytes)
        self.info["resident_bytes"] = int(plan.local_bytes)
        del stages
        t0 = time.perf_counter()
        self.ex.warmup(self.inputs[0])   # compiles every stage shape
        self.ex.run(self.inputs[0])      # and warms the timed path once
        self.info["warmup_s"] = time.perf_counter() - t0
        self.spans.wrap(self.ex, "run")

    def window(self, seconds: float) -> None:
        picks = set(generator.rng_for(self.seed, 30).choice(
            FIRST, KEEP - 1, replace=False).tolist())
        self.kept: list[tuple[int, object]] = []
        self.results = []
        n_in = len(self.inputs)
        self.t0 = t0 = time.perf_counter()
        p = 0
        while True:
            res = self.ex.run(self.inputs[p % n_in])
            self.results.append((res.elapsed_us, sum(res.stage_wait_us.values())))
            if p in picks:
                self.kept.append((p % n_in, res.output))
            p += 1
            if time.perf_counter() - t0 >= seconds:
                break
        self.t1 = time.perf_counter()
        self.kept.append(((p - 1) % n_in, res.output))
        self.passes = p

    def attempted(self) -> int:
        return self.passes

    def failed(self) -> int:
        return 0

    def end_to_end(self) -> dict:
        return {"iter_ms": (self.t1 - self.t0) / self.passes * 1e3}

    def record(self) -> dict:
        m = self.mix["m"]
        pass_flops = sum(flops.matmul_flops(m, k, n) for k, n in self.shapes)
        pass_roof = sum(flops.roofline_seconds(
            flops.matmul_flops(m, k, n), flops.matmul_bytes(m, k, n),
            self.peaks) for k, n in self.shapes)
        reads = [x for x in self.ex.engine.measurements if x[0] == "read"]
        return {
            "kind": "offload", "window_s": self.t1 - self.t0,
            "passes": self.passes, "pass_flops": pass_flops,
            "pass_roofline_s": pass_roof,
            "elapsed_us": [e for e, _w in self.results],
            "wait_us": [w for _e, w in self.results],
            "read_bytes": sum(x[1] for x in reads),
            "read_s": sum(x[2] for x in reads) * 1e-6,
            "peaks": self.peaks,
        }

    def release(self) -> None:
        """Keep the sampled outputs on the host, free the executor."""
        self.kept = [(i, np.asarray(out)) for i, out in self.kept]
        self.ex.engine.close()
        self.ex = None
        gc.collect()

    def _reference(self):
        return load_module(BENCH / "configs" / self.c["reference"])

    def readings(self, controls=()) -> dict:
        ref_mod = self._reference()
        ins = [self.inputs[i] for i, _ in self.kept]
        got = np.stack([out for _, out in self.kept])
        ref = ref_mod.chain_outputs(self.seed, self.c, ins)
        out = {"worst_row_err": ref_mod.worst_row_error(got, ref)}
        for q in controls:
            ctl = ref_mod.chain_outputs(self.seed, self.c, ins, quant=q)
            out[f"control_{q}"] = ref_mod.worst_row_error(ctl, ref)
        return out

    def check(self) -> dict:
        r = self.readings()
        limit = self.c["correct"]["worst_row_err"]
        v = r["worst_row_err"]
        return {"worst_row_err": (v, limit, bool(v <= limit))}
