"""The harness end to end on the CPU at reduced sizes: each driver runs one
short window through ``run.measure`` (everything but the look for a chip),
the timed path broken underneath turns ``correct`` false, and ``run.py``
itself refuses to measure off a TPU."""
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

import run as harness
from conftest import (BENCH, FAKE_PEAKS, ROOT, cell, tiny_chain, tiny_chat,
                      tiny_granite, tiny_passes)

SEED = 2**33 + 99
CHAT = ["granite-8b.chat-kv-tiered", "granite-8b.chat-local"]
OFFLOAD = ["granite-8b-offload.prefill-half-remote",
           "granite-8b-offload.prefill-all-local"]


def _measure(spec, name, trace=0, seconds=1.0):
    traffic = name.split(".", 1)[1]
    if name in CHAT:
        cfg, mix = tiny_granite(), tiny_chat(traffic)
    else:
        cfg, mix = tiny_chain(), tiny_passes(traffic)
    return harness.measure(spec, cell(spec, name), cfg, mix, SEED, seconds,
                           trace, jax.devices(), FAKE_PEAKS)


def _names(spec, name, key):
    return {m["name"] for m in spec[key] if name in m.get("workloads", [name])}


@pytest.mark.parametrize("name", CHAT + OFFLOAD)
def test_driver_short_window_end_to_end(spec, name):
    res = _measure(spec, name)
    assert res["correct"] is True
    assert set(res["metrics"]) == _names(spec, name, "end_to_end")
    assert all(m["value"] >= 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", [CHAT[0], OFFLOAD[0]])
def test_driver_short_window_traced(spec, name):
    res = _measure(spec, name, trace=1)
    assert res["correct"] is True
    # no device plane on the CPU: the trace-read metrics stay silent
    want = _names(spec, name, "per_layer") - {
        "decode_step_roofline", "streaming_matmul_roofline"}
    assert set(res["metrics"]) == want
    assert res["device"]["window_s"] > 0 and "breakdown" in res


# -- faults planted under the timed path ---------------------------------
def _alter_tokens(monkeypatch):
    from repro.serving.engine import ServingEngine
    orig = ServingEngine.decode_lanes

    def altered(self, tokens):
        nxt, us = orig(self, tokens)
        return (nxt + 1) % self.cfg.vocab_size, us
    monkeypatch.setattr(ServingEngine, "decode_lanes", altered)


def _state_unchanged(monkeypatch):
    from repro.serving.engine import ServingEngine
    orig = ServingEngine.decode_lanes

    def frozen(self, tokens):
        before = self.cache
        out = orig(self, tokens)
        self.cache = before          # the step's cache writes are lost
        return out
    monkeypatch.setattr(ServingEngine, "decode_lanes", frozen)


def _alter_stage_output(monkeypatch):
    from repro.core.exec import StreamingExecutor
    orig = StreamingExecutor._compute_stage

    def altered(self, st, params, x):
        y = orig(self, st, params, x)
        return y.at[0].add(1.0) if st.name == "l1.wo" else y
    monkeypatch.setattr(StreamingExecutor, "_compute_stage", altered)


def _skip_stage(monkeypatch):
    from repro.core.exec import StreamingExecutor
    orig = StreamingExecutor._compute_stage

    def skipped(self, st, params, x):
        return x if st.name == "l0.wo" else orig(self, st, params, x)
    monkeypatch.setattr(StreamingExecutor, "_compute_stage", skipped)


def _half_batch(monkeypatch):
    from repro.core.exec import StreamingExecutor
    orig = StreamingExecutor.run

    def half(self, x):
        x = np.array(x)
        x[x.shape[0] // 2:] = 0
        return orig(self, x)
    monkeypatch.setattr(StreamingExecutor, "run", half)


@pytest.mark.parametrize("name,fault", [
    (CHAT[0], _alter_tokens), (CHAT[0], _state_unchanged),
    (CHAT[1], _alter_tokens), (CHAT[1], _state_unchanged),
    (OFFLOAD[0], _alter_stage_output), (OFFLOAD[0], _skip_stage),
    (OFFLOAD[0], _half_batch), (OFFLOAD[1], _alter_stage_output),
])
def test_broken_timed_path_is_not_correct(spec, monkeypatch, name, fault):
    fault(monkeypatch)
    assert _measure(spec, name)["correct"] is False


# -- the look for a chip ---------------------------------------------------
def test_run_py_exits_nonzero_off_tpu_naming_the_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CHAT[1],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr


def _fake_jax(kind, n=1):
    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [dev] * n)


def test_unknown_device_kind_exits_nonzero():
    with pytest.raises(SystemExit) as e:
        harness.check_device(_fake_jax("TPU v99 imaginary"), 1)
    assert e.value.code != 0


def test_too_few_chips_exits_nonzero():
    with pytest.raises(SystemExit) as e:
        harness.check_device(_fake_jax("TPU v5 lite", 1), 4)
    assert e.value.code != 0
    dev, devices, peaks = harness.check_device(_fake_jax("TPU v5 lite", 4), 4)
    assert len(devices) == 4 and peaks["bf16_flops"] == 197e12
