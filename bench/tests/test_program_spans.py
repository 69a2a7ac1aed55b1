"""The program's ``dolma:`` spans against device idle: interval arithmetic
on made-up spans, and a small trace recorded on the CPU
(``data/program_spans.xplane.pb``: one ``StreamingExecutor.run`` of three
128 x 128 matmul stages, the first two streamed, inside ``bench:window``),
against values read off its event listing (``trace_reduce.dump``) by hand.
"""
import pathlib
import shutil

import pytest

import program_spans as ps
from common import BENCH, load_module
from trace_reduce import NO_SPAN

DATA = pathlib.Path(__file__).resolve().parent / "data"
RECORDED = DATA / "program_spans.xplane.pb"


def _trace(driving=(), other=(), busy=((),), lo=0, hi=100, modules=()):
    return ps.Trace(lo, hi, [("dolma:" + n, s, e) for n, s, e in driving],
                    [("dolma:" + n, s, e, {}) for n, s, e in other],
                    [list(b) for b in busy], list(modules))


def test_idle_charged_to_the_innermost_span():
    # the stage and its dispatch start on the same ns: dispatch is inner
    t = _trace(driving=[("exec.pass", 0, 100), ("exec.stage", 10, 60),
                        ("exec.dispatch", 10, 40), ("exec.sync", 40, 60)],
               busy=[[(20, 30), (45, 50)]])
    assert ps.idle_by_span(t) == {"dolma:exec.pass": 50,
                                  "dolma:exec.dispatch": 20,
                                  "dolma:exec.sync": 15}
    assert ps.idle_share(t, "exec.dispatch") == pytest.approx(20.0)
    assert ps.idle_share(t, "exec.stage") == 0.0
    assert ps.below_top_share(ps.idle_by_span(t)) == pytest.approx(35 / 85 * 100)


def test_fetch_thread_span_takes_no_driving_thread_idle():
    t = _trace(driving=[("exec.barrier", 0, 50)],
               other=[("fabric.read", 0, 80)])
    idle = ps.idle_by_span(t)
    assert idle == {"dolma:exec.barrier": 50, NO_SPAN: 50}
    assert ps.idle_share(t, "fabric.read") is None


def test_overlap_of_a_read_half_covered_by_device_work():
    t = _trace(other=[("fabric.read", 20, 60)], busy=[[(0, 10), (40, 90)]])
    assert ps.overlap_share(t, "fabric.read") == pytest.approx(50.0)
    # two devices, one idle throughout: the mean over planes
    t.busy.append([])
    assert ps.overlap_share(t, "fabric.read") == pytest.approx(25.0)


def test_idle_outside_any_span_stays_unattributed():
    t = _trace(driving=[("exec.pass", 20, 80)], busy=[[(30, 40)]])
    assert ps.idle_by_span(t) == {NO_SPAN: 40, "dolma:exec.pass": 50}
    assert ps.below_top_share(ps.idle_by_span(t)) == 0.0


def test_a_program_without_spans_reads_none(tmp_path):
    t = _trace(busy=[[(0, 10)]])
    assert ps.idle_share(t, "exec.barrier") is None
    assert ps.overlap_share(t, "fabric.read") is None
    assert ps.idle_share(None, "exec.barrier") is None
    assert ps.load(tmp_path) is None


def test_clock_lead_bounds_from_launch_and_wait():
    t = _trace(driving=[("decode.dispatch", 100, 120),
                        ("decode.readback", 150, 300),
                        ("decode.dispatch", 1000, 1020),
                        ("decode.readback", 1050, 1210),
                        # its run falls after the window: skipped
                        ("decode.dispatch", 1900, 1920),
                        ("decode.readback", 1930, 1990)],
               modules=[("jit__lambda", 90, 280), ("jit_argmax", 285, 295),
                        ("jit__lambda", 1012, 1190), ("jit_argmax", 1195, 1200)],
               hi=2000)
    # at least: 100 - 90, 1000 - 1012; at most: 300 - 280, 1210 - 1190
    assert ps.clock_lead(t, "decode.dispatch", "decode.readback",
                         "jit__lambda") == ([10, -12], [20, 20])
    assert ps.clock_lead(t, "exec.dispatch", "exec.sync") is None


def test_idle_split_with_the_device_times_moved():
    t = _trace(driving=[("exec.dispatch", 0, 50), ("exec.sync", 50, 100)],
               busy=[[(40, 90)]])
    assert ps.idle_by_span(t) == {"dolma:exec.dispatch": 40,
                                  "dolma:exec.sync": 10}
    assert ps.idle_by_span(t, shift=10) == {"dolma:exec.dispatch": 50}


# the recorded trace, read off its listing (ns): window 25199 + 5276334;
# on the driving line exec.pass 31269 + 5261956, exec.input 457454, two
# barriers 1061794 + 11542, three stages 1173221 + 1496521 + 817714 each
# holding a dispatch (1143447, 640549, 495849) and a sync (19525, 845816,
# 314436); on the fetch worker's line two fabric.read of 65536 bytes. No
# device plane on the CPU: the whole window is idle.
WINDOW = 5_276_334
BARRIER = 1_061_794 + 11_542
DISPATCH = 1_143_447 + 640_549 + 495_849
SYNC = 19_525 + 845_816 + 314_436
STAGES = 1_173_221 + 1_496_521 + 817_714
PASS_SELF = 5_261_956 - 457_454 - BARRIER - STAGES


def test_recorded_trace_by_hand():
    t = ps.parse(str(RECORDED))
    assert (t.lo, t.hi - t.lo) == (25_199, WINDOW)
    assert t.busy == [[]]
    assert [s[0] for s in t.driving].count("dolma:exec.dispatch") == 3
    assert [(n, st) for n, _s, _e, st in t.other] == [
        ("dolma:fabric.read", {"stage": "w0", "nbytes": 65536}),
        ("dolma:fabric.read", {"stage": "w1", "nbytes": 65536})]
    idle = ps.idle_by_span(t)
    assert idle == {
        NO_SPAN: 6_070 + 8_308, "dolma:exec.pass": PASS_SELF,
        "dolma:exec.input": 457_454, "dolma:exec.barrier": BARRIER,
        "dolma:exec.dispatch": DISPATCH, "dolma:exec.sync": SYNC,
        "dolma:exec.stage": STAGES - DISPATCH - SYNC}
    assert sum(idle.values()) == WINDOW
    assert ps.overlap_share(t, "fabric.read") == 0.0
    assert ps.clock_lead(t, "exec.dispatch", "exec.sync") is None


@pytest.mark.parametrize("metric,kind,want", [
    ("exec.idle_at_barrier", "offload", BARRIER / WINDOW * 100),
    ("exec.idle_at_dispatch", "offload", DISPATCH / WINDOW * 100),
    ("exec.idle_at_sync", "offload", SYNC / WINDOW * 100),
    ("fabric.read_overlap", "offload", 0.0),
    ("decode.idle_at_dispatch", "chat", None),
    ("decode.idle_at_readback", "chat", None),
    ("decode.idle_at_readback", "offload", None),
    ("exec.idle_at_sync", "chat", None),
])
def test_metric_files_read_the_trace_run_py_wrote(tmp_path, monkeypatch,
                                                  metric, kind, want):
    shutil.copy(RECORDED, tmp_path / RECORDED.name)
    monkeypatch.setattr(ps, "TRACE_DIR", tmp_path)
    got = load_module(BENCH / "metrics" / f"{metric}.py").read({"kind": kind})
    assert got == (want if want is None else pytest.approx(want))
