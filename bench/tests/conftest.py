"""Tiny configurations and a CPU stand-in for the chip, so the harness's
code paths run under pytest on the CPU: ``python -m pytest bench/tests``."""
from __future__ import annotations

import copy
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: peaks of a made-up device; CPU runs only exercise the arithmetic
FAKE_PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11,
              "hbm_bytes": 1e9, "source": "test"}


def _load(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def tiny_granite() -> dict:
    c = _load("configs", "granite-8b")
    c.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
             num_key_value_heads=2, head_dim=64, num_hidden_layers=2,
             vocab_size=2048)
    return c


def tiny_chat(traffic: str) -> dict:
    t = copy.deepcopy(_load("traffic", traffic))
    t["mix"].update(prompt={"median": 12, "sigma": 0.5, "min": 4, "max": 40},
                    output={"median": 6, "sigma": 0.5, "min": 2, "max": 16},
                    max_total=256, block=4, dephase_steps=8)
    t["deployment"].update(lanes=4, max_len=1024,
                           node_capacity_bytes=1 << 26)
    return t


def tiny_chain() -> dict:
    c = _load("configs", "granite-8b-offload")
    c.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
             head_dim=64, num_hidden_layers=2)
    return c


def tiny_passes(traffic: str) -> dict:
    t = copy.deepcopy(_load("traffic", traffic))
    t["mix"].update(m=128, inputs=2)
    t["deployment"].update(block_m=128, block_n=128, block_k=128)
    return t


@pytest.fixture(scope="session")
def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(spec: dict, name: str) -> dict:
    """The cell's entry; a deployment kept as a traffic file but not yet a
    cell (``chat-kv-tiered``) gets an entry of its own."""
    config, traffic = name.split(".", 1)
    return next((w for w in spec["workloads"] if w["name"] == name),
                {"name": name, "config": config, "traffic": traffic, "chips": 1})
