"""The program's own spans in a traced run, against the device's idle time.

The program annotates its host work as ``dolma:<name>`` spans on the
profiler's clock (``repro.core.telemetry.Telemetry.wall_span``). This module
reads them from the ``.xplane.pb`` that ``run.py`` writes under
``<checkout>/.bench_trace``, parsing each trace once, and reduces them with
``trace_reduce``'s interval arithmetic:

- The **driving thread** is the host line that holds ``bench:window``.
  Device idle inside the window is charged to the innermost ``dolma:`` span
  open on that line, or to ``(no span)``. Spans of other threads never take
  any of it.
- Spans of other threads (the fetch worker's ``dolma:fabric.*``) are read
  for their overlap with device work only.

A trace without the program's spans (a program that does not make them)
gives ``None`` for every reading, never an error. A trace without a device
plane (a CPU run in the tests) counts as one device that ran nothing.

    python3 bench/program_spans.py [trace_dir]

prints the reduction of a trace as one JSON line: idle seconds by span, the
share of idle charged below ``exec.pass`` / ``sched.step``, the spans
counted by name with their mean host duration, the overlap of each
fetch-thread span with device work, and bounds on how far the device's
clock leads the host's (``clock_lead``), with the split of idle between
launching and waiting read as if the device's times were moved by each.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import json
import pathlib
import statistics
import sys

from trace_reduce import (
    DEVICE_PREFIX,
    MODULE_LINE,
    OP_LINE,
    WINDOW,
    _gaps,
    _union,
    charge_gaps,
    find_xplane,
)

PREFIX = "dolma:"
TRACE_DIR = pathlib.Path(__file__).resolve().parent.parent / ".bench_trace"
#: the outermost spans of a pass and a step: idle in their self time is
#: not put down to any piece of work
TOP = ("dolma:exec.pass", "dolma:sched.step")
#: (the span that launches device work, the span that waits for it, the
#: name of the module the launch starts) on each path, for ``clock_lead``
PATHS = (("decode.dispatch", "decode.readback", "jit__lambda"),
         ("exec.dispatch", "exec.sync", ""))


@dataclasses.dataclass
class Trace:
    """The pieces of one trace the readings need; times in ns."""

    lo: int
    hi: int
    driving: list[tuple[str, int, int]]       # dolma: spans, driving line
    other: list[tuple[str, int, int, dict]]   # dolma: spans, other lines
    busy: list[list[tuple[int, int]]]         # busy union per device plane
    modules: list[tuple[str, int, int]]       # device modules, all planes


def _clip(events, lo, hi):
    return [ev for ev in events if ev.start_ns + ev.duration_ns > lo
            and ev.start_ns < hi]


@functools.lru_cache(maxsize=4)
def parse(path: str) -> Trace:
    """Read the window, the ``dolma:`` spans by thread, and device busy
    time from one ``.xplane.pb`` (once per path)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host_lines, devices = [], []
    for plane in data.planes:
        (devices if plane.name.startswith(DEVICE_PREFIX)
         else host_lines).append(plane)
    host_lines = [line for plane in host_lines for line in plane.lines]
    window, driving_line = None, None
    for line in host_lines:
        for ev in line.events:
            if ev.name == WINDOW:
                window, driving_line = ev, line
                break
        if window is not None:
            break
    if window is None:
        raise ValueError(f"no {WINDOW} span in {path}")
    lo, hi = window.start_ns, window.start_ns + window.duration_ns
    driving, other = [], []
    for line in host_lines:
        for ev in _clip(line.events, lo, hi):
            if not ev.name.startswith(PREFIX):
                continue
            span = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            if line is driving_line:
                driving.append(span)
            else:
                other.append(span + (dict(ev.stats),))
    busy, modules = [], []
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name == OP_LINE:
                intervals += [(max(ev.start_ns, lo),
                               min(ev.start_ns + ev.duration_ns, hi))
                              for ev in _clip(line.events, lo, hi)]
            elif line.name == MODULE_LINE:
                modules += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in _clip(line.events, lo, hi)]
        busy.append(_union(intervals))
    # no device plane (a CPU run): one device that ran nothing, as
    # trace_reduce's busy_s has it
    return Trace(lo, hi, driving, other, busy or [[]], modules)


def load(trace_dir: pathlib.Path | None = None) -> Trace | None:
    """The newest trace under ``trace_dir`` (default ``TRACE_DIR``), parsed
    once; None if there is none."""
    try:
        return parse(find_xplane(str(trace_dir or TRACE_DIR)))
    except FileNotFoundError:
        return None


def idle_by_span(t: Trace, shift: int = 0) -> dict[str, float]:
    """Device idle ns in the window charged to each driving-thread span
    (innermost open), mean over device planes; with ``shift``, as if the
    device's timestamps were that many ns later."""
    # charge_gaps takes the latest-starting open span as the innermost: an
    # inner span that starts on the same ns as its parent must come later
    spans = sorted(t.driving, key=lambda s: (s[1], -s[2]))
    out = collections.Counter()
    for busy in t.busy:
        if shift:
            busy = [(max(s + shift, t.lo), min(e + shift, t.hi))
                    for s, e in busy if e + shift > t.lo and s + shift < t.hi]
        out.update(charge_gaps(_gaps(busy, t.lo, t.hi), spans))
    return {k: v / len(t.busy) for k, v in out.items()}


def idle_share(t: Trace | None, span: str) -> float | None:
    """Device idle charged to ``dolma:<span>`` over the window, in percent;
    None where the trace holds no such span on the driving thread."""
    name = PREFIX + span
    if t is None or not any(s[0] == name for s in t.driving):
        return None
    return idle_by_span(t).get(name, 0.0) / (t.hi - t.lo) * 100.0


def _covered(busy, s, e):
    """ns of [s, e] that a disjoint sorted union covers."""
    i = max(bisect.bisect_right(busy, (s,)) - 1, 0)
    got = 0
    while i < len(busy) and busy[i][0] < e:
        got += max(0, min(e, busy[i][1]) - max(s, busy[i][0]))
        i += 1
    return got


def overlap_share(t: Trace | None, span: str) -> float | None:
    """Device-busy time inside the other threads' ``dolma:<span>`` spans
    (clipped to the window) over those spans' total time, in percent, mean
    over device planes; None where there is no such span."""
    name = PREFIX + span
    spans = [(max(s, t.lo), min(e, t.hi)) for n, s, e, _st in t.other
             if n == name] if t is not None else []
    total = sum(e - s for s, e in spans)
    if total <= 0:
        return None
    busy = sum(_covered(b, s, e) for b in t.busy for s, e in spans)
    return busy / len(t.busy) / total * 100.0


def below_top_share(idle: dict[str, float]) -> float | None:
    """Share of all idle charged to a ``dolma:`` span other than the
    outermost pass and step spans, in percent."""
    total = sum(idle.values())
    if total <= 0:
        return None
    below = sum(v for k, v in idle.items()
                if k.startswith(PREFIX) and k not in TOP)
    return below / total * 100.0


def clock_lead(t: Trace, launch: str, wait: str,
               module: str = "") -> tuple[list[int], list[int]] | None:
    """Samples of bounds, in ns, on how far the device's clock leads the
    host's in this trace, one pair per ``dolma:<launch>`` span: it is
    paired with the run of a module named ``module*`` that starts nearest
    to it and with the first ``dolma:<wait>`` span after it. The run
    starts on the device after the launch begins and has ended before the
    wait ends, so the lead is at least the launch's start less the run's
    start and at most the wait's end less the run's end. A launch whose
    paired run does not overlap it and its wait (its own run lies outside
    the window) is skipped. Returns (at least, at most), or None where the
    trace has no such spans or runs."""
    runs = sorted((s, e) for n, s, e in t.modules if n.startswith(module))
    waits = sorted((s, e) for n, s, e in t.driving if n == PREFIX + wait)
    run_starts = [s for s, _e in runs]
    wait_starts = [s for s, _e in waits]
    low, high = [], []
    for n, ls, le in t.driving:
        j = bisect.bisect_left(wait_starts, le)
        if n != PREFIX + launch or not runs or j == len(waits):
            continue
        i = bisect.bisect_left(run_starts, ls)
        i = min((k for k in (i - 1, i) if 0 <= k < len(runs)),
                key=lambda k: abs(run_starts[k] - ls))
        (run_s, run_e), wait_e = runs[i], waits[j][1]
        if run_e > ls and run_s < wait_e:   # else its own run is cut off
            low.append(ls - run_s)
            high.append(wait_e - run_e)
    return (low, high) if low else None


def _durations(spans) -> dict[str, list[int]]:
    out = collections.defaultdict(list)
    for n, s, e in spans:
        out[n].append(e - s)
    return out


def summary(t: Trace) -> dict:
    """The reduction printed by ``python3 bench/program_spans.py``."""
    idle = idle_by_span(t)
    ns = 1e-9
    out = {
        "window_s": (t.hi - t.lo) * ns,
        "idle_s": sum(idle.values()) * ns,
        "idle_by_span_s": {k: v * ns for k, v in
                           sorted(idle.items(), key=lambda kv: -kv[1])},
        "below_top_share": below_top_share(idle),
        "spans_n_mean_ms": {
            n: [len(d), statistics.fmean(d) * 1e-6] for n, d in sorted(
                _durations(t.driving + [x[:3] for x in t.other]).items())},
        "overlap_share": {n: overlap_share(t, n[len(PREFIX):])
                          for n in sorted({s[0] for s in t.other})},
    }
    for launch, wait, module in PATHS:
        lead = clock_lead(t, launch, wait, module)
        if lead is None:
            continue
        low, high = max(lead[0]), min(lead[1])
        window = t.hi - t.lo
        out[f"clock_lead.{launch}"] = {
            "at_least_ms": low * 1e-6, "at_most_ms": high * 1e-6,
            "median_at_least_ms": statistics.median(lead[0]) * 1e-6,
            "median_at_most_ms": statistics.median(lead[1]) * 1e-6,
            "n": len(lead[0]),
            # the split of idle between the two spans, in % of the window,
            # with the device's times moved by each bound
            "split_if_shifted": {
                f"{shift * 1e-6:.3f}ms": {
                    n: idle_by_span(t, shift).get(PREFIX + n, 0.0)
                    / window * 100.0 for n in (launch, wait)}
                for shift in (0, low, high)}}
    return out


if __name__ == "__main__":
    where = pathlib.Path(sys.argv[1]) if len(sys.argv) > 1 else None
    trace = load(where)
    if trace is None:
        sys.exit(f"no .xplane.pb under {where or TRACE_DIR}")
    print(json.dumps(summary(trace)))
