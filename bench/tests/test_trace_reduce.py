"""Trace reduction: pure interval arithmetic by hand, and a small trace
recorded on a TPU v5e (``data/small.xplane.pb``) against values read off
its event listing by hand."""
import pathlib

import pytest

import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_once():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_gaps_are_the_complement_in_the_window():
    assert tr._gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]


def test_gaps_charged_to_the_innermost_span():
    spans = [("bench:step", 0, 10), ("bench:offload", 4, 7)]
    got = tr.charge_gaps([(1, 2), (3, 6), (9, 12)], spans)
    # (1,2) step; (3,4) step, (4,6) offload; (9,10) step, (10,12) no span
    assert got == {"bench:step": 3, "bench:offload": 2, tr.NO_SPAN: 2}


def test_recorded_trace_by_hand():
    """Three jitted (4096, 4096) reductions, each under ``bench:work`` and
    followed by a 2 ms ``bench:sleep``, inside ``bench:window``. Read off
    the event listing (ns): window 46630881 + 13460271. The first op ended
    (46372570) before the window opened; ops 2 and 3 fall inside it:
    copy-start 14 + 13, copy-done 3 + 3, fusion 704513 + 704516. The
    device's timestamps lead the host's by about 1 ms here, so both ops sit
    inside the ``bench:sleep`` span before the call that launched them."""
    got = tr.reduce(str(DATA / "small.xplane.pb"))
    assert got["n_devices"] == 1
    assert got["window_s"] == pytest.approx(13_460_271e-9)
    assert got["busy_s"] == pytest.approx((14 + 3 + 704_513 + 13 + 3 + 704_516) * 1e-9)
    assert got["ops"]["%convolution_reduce_fusion bf16[]"] == pytest.approx(
        1_409_029e-9)
    # sleeps: 2922030 + 2724080 + 2847880 less the two ops inside them;
    # gaps between spans: 7650 + 4340 + 8591 + 10340 + 5389 + 3751 + 3200
    assert got["idle_by_span"]["bench:sleep"] == pytest.approx(
        (2_922_030 - 704_530 + 2_724_080 - 704_532 + 2_847_880) * 1e-9)
    assert got["idle_by_span"][tr.NO_SPAN] == pytest.approx(43_261e-9)
    assert got["idle_by_span"]["bench:work"] == pytest.approx(
        (1_757_220 + 1_622_980 + 1_542_820) * 1e-9)
    idle = sum(got["idle_by_span"].values())
    assert idle == pytest.approx(got["window_s"] - got["busy_s"])
    assert got["device_ops"][0][0] == "%convolution_reduce_fusion bf16[]"
