"""The traffic generator is deterministic and keeps to its clips."""
import numpy as np

import generator
from conftest import _load

CHAT = _load("traffic", "chat-kv-tiered")["mix"]
SEED = 2**33 + 12345   # wider than 32 bits, as the driver's seeds are


def _draw(seed, n=64):
    g = generator.ChatRequests(CHAT, seed, 49152)
    return [g.next() for _ in range(n)]


def test_same_seed_same_requests():
    a, b = _draw(SEED), _draw(SEED)
    assert all(np.array_equal(p, q) and m == n for (p, m), (q, n) in zip(a, b))


def test_different_seeds_different_requests_same_sizes():
    a, b = _draw(SEED), _draw(SEED + 1)
    assert [(len(p), m) for p, m in a] == [(len(q), n) for q, n in b]
    assert all(not np.array_equal(p, q) for (p, _), (q, _) in zip(a, b))


def test_lengths_within_clips_and_context():
    for prompt, max_new in _draw(SEED, 256):
        assert CHAT["prompt"]["min"] <= len(prompt) <= CHAT["prompt"]["max"]
        assert CHAT["output"]["min"] <= max_new <= CHAT["output"]["max"]
        assert len(prompt) + max_new <= CHAT["max_total"] <= 4096
        assert prompt.dtype == np.int32
        assert 0 <= prompt.min() and prompt.max() < 49152


def test_each_block_holds_the_grid_of_sizes():
    block = CHAT["block"]
    for seed in (1, 2, SEED):
        g = generator.ChatRequests(CHAT, seed, 49152)
        sizes = sorted(g.lengths()[0] for _ in range(block))
        assert sizes == sorted(generator.lognormal_grid(CHAT["prompt"], block))


def test_lognormal_grid_median_and_clip():
    grid = generator.lognormal_grid(CHAT["prompt"], 16)
    assert grid.min() >= 64 and grid.max() <= 3072
    assert 400 < np.median(grid) < 650


def test_dephase_spreads_lanes_over_the_steps():
    starts = generator.dephase_starts(16, 512)
    assert starts[0] == 0 and len(set(starts)) == 16 and max(starts) < 512
