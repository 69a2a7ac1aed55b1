"""From the profiler's ``.xplane.pb`` to device busy and idle time, device
time per operation and per kernel, and idle gaps put down to host spans.

Device events are those on the ``/device:TPU:<n>`` planes, op line
``XLA Ops``. Host spans are the benchmark's own ``bench:<name>``
annotations on the host plane. The window is the ``bench:window`` span:
everything is clipped to it. Busy time is the union of the device's op
intervals (overlapping ops count once); idle is the rest of the window.
Each stretch of idle time is charged to the innermost host span open at
that moment (the latest to start), or to ``(no span)``.
"""
from __future__ import annotations

import collections
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW = "bench:window"
NO_SPAN = "(no span)"


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint union."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _gaps(busy, lo, hi):
    """The complement of a disjoint sorted union within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def charge_gaps(gaps, spans):
    """Seconds of each gap charged to the innermost open span, by name.

    ``gaps``: disjoint (start, end) in ns; ``spans``: (name, start, end) in
    ns, possibly nested. Returns {name: ns}.
    """
    edges = []
    for i, (_n, s, e) in enumerate(spans):
        edges.append((s, 1, i))
        edges.append((e, -1, i))
    for s, e in gaps:
        edges.append((s, 2, -1))
        edges.append((e, -2, -1))
    edges.sort(key=lambda x: (x[0], x[1] < 0))
    out = collections.Counter()
    active: dict[int, int] = {}
    in_gap, t_prev = 0, None
    for t, kind, i in edges:
        if in_gap and t_prev is not None and t > t_prev:
            if active:
                j = max(active, key=lambda k: (active[k], k))
                out[spans[j][0]] += t - t_prev
            else:
                out[NO_SPAN] += t - t_prev
        if kind == 1:
            active[i] = spans[i][1]
        elif kind == -1:
            active.pop(i, None)
        elif kind == 2:
            in_gap += 1
        else:
            in_gap -= 1
        t_prev = t
    return dict(out)


def op_name(event_name: str) -> str:
    """``%name = type[shape]{layout} op(...)`` -> ``%name type[shape]``: the
    op and its result shape, without the operands and attributes."""
    head, _, rest = event_name.partition(" = ")
    shape = rest.split(" ", 1)[0].split("{", 1)[0]
    return f"{head} {shape}".strip()


def _events(line, lo, hi):
    for ev in line.events:
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        if e > lo and s < hi:
            yield ev, max(s, lo), min(e, hi)


def reduce(path: str, top: int = 10) -> dict:
    """Reduce one trace. Returns seconds: ``window_s``, ``busy_s`` (mean over
    the device planes), ``ops`` ({``op_name``: device seconds}) and
    ``modules`` ({module: device seconds}), summed over planes, ``idle_by_span`` ({span: idle seconds}, mean over
    planes), ``n_devices``, and ``device_ops`` / ``idle_gaps``: the ``top``
    largest of ``ops`` and ``idle_by_span`` as [name, seconds] lists."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    windows = [s for s in spans if s[0] == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in {path}")
    _n, lo, hi = windows[0]
    inner = [s for s in spans if s[0] != WINDOW and s[2] > lo and s[1] < hi]
    ops, modules, idle = (collections.Counter() for _ in range(3))
    busy_total = 0
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name == OP_LINE:
                for ev, s, e in _events(line, lo, hi):
                    ops[op_name(ev.name)] += e - s
                    intervals.append((s, e))
            elif line.name == MODULE_LINE:
                for ev, s, e in _events(line, lo, hi):
                    modules[ev.name] += e - s
        busy = _union(intervals)
        busy_total += sum(e - s for s, e in busy)
        idle.update(charge_gaps(_gaps(busy, lo, hi), inner))
    n = max(len(devices), 1)
    ns = 1e-9
    out = {
        "window_s": (hi - lo) * ns,
        "busy_s": busy_total / n * ns,
        "n_devices": len(devices),
        "ops": {k: v * ns for k, v in ops.items()},
        "modules": {k: v * ns for k, v in modules.items()},
        "idle_by_span": {k: v / n * ns for k, v in idle.items()},
    }
    out["device_ops"] = [[k, v] for k, v in
                         sorted(out["ops"].items(), key=lambda kv: -kv[1])[:top]]
    out["idle_gaps"] = [[k, v] for k, v in sorted(
        out["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]]
    return out


def dump(path: str, per_line: int = 5) -> str:
    """A readable listing of a trace's planes, lines and first events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            rows.append(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                stats = {k: v for k, v in ev.stats}
                rows.append(f"    {ev.name!r} start={ev.start_ns} "
                            f"dur={ev.duration_ns} {stats}")
    return "\n".join(rows)
