"""From a `jax.profiler` trace to device metrics (the reduction is pure
and tested on a recorded trace; only `load_xplane` touches JAX).

A loaded trace is a plain dict:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

Device planes are those named `/device:...` (not the `... CUSTOM` ones);
on each, the line `XLA Ops` holds one event per executed operation and
`XLA Modules` one per executed program (`jit_<function>(<id>)`). The
benchmark's `TraceAnnotation`s (`bench:<layer>`) are in the host planes for
whoever opens the trace; the reduction names idle gaps by the same spans
as the harness clocked them (`host`), because an annotation that began
before the slice is not in it.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CUT_NS = 1000  # a program cut short begins or ends with the trace, to a microsecond


def load_xplane(trace_dir: str) -> dict:
    """Newest `.xplane.pb` under `trace_dir` -> the dict above: the device
    planes' op and module lines."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        if not is_device_plane(plane.name):
            continue
        lines = [{"name": line.name,
                  "events": [[op_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                             for ev in line.events]}
                 for line in plane.lines if line.name in (OPS_LINE, MODULES_LINE)]
        # an idle device is a device all the same
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load(path: str) -> dict:
    """A loaded trace kept as gzipped JSON (the tests' recorded one)."""
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


def op_name(event: str) -> str:
    """The tracer names an operation by its whole HLO text
    (`%while.2 = (s32[]...) while(...)`): keep the result's name."""
    return event.split(" = ", 1)[0][:80]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def program_of(module_event: str) -> str:
    """`jit_miller_loop(123456)` -> `miller_loop`."""
    name = re.sub(r"\(\d+\)$", "", module_event)
    return name[4:] if name.startswith("jit_") else name


def reduce(trace: dict, window_ns: tuple = None, host: list = ()) -> dict:
    """-> busy_s and window_s (averaged over the device planes), per
    program [dispatches, seconds], the operations with most time and the
    longest idle gaps named by the host span that covers them.

    `window_ns` (start, end) bounds the slice on the trace's clock; default:
    up to the last device event. The window opens at the first device event
    whatever `start` says: device tracing comes up some tens of
    milliseconds after the trace starts, and what ran before that is not
    in the trace at all, so that stretch is unknown, not idle. A program
    that was already running when the device tracer came up (its event then
    begins with the trace, short of its head), or that the trace's stop cut
    short, counts as busy time but not as a dispatch of its program. The
    first program event of a trace is taken for the former, whole or not.
    One cut by the stop is the last event of all and ends with the trace,
    which is stopped no earlier than the slice's `end`: the operation it
    was in is never written, so its event runs past the last operation's.
    A last event that ended before `end` ran to its end. (With no
    `window_ns` the trace is taken to stop with its last operation.)
    `host`: the benchmark's spans, (name, start_ns, end_ns) on the same
    clock."""
    devices = [p for p in trace["planes"] if is_device_plane(p["name"])]
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy_s, window_s = [], []
    programs, ops, gaps = {}, {}, []
    for plane in devices:
        op_events = _line(plane, OPS_LINE)
        mod_events = _line(plane, MODULES_LINE)
        # an operation ran on the device: the op line where the tracer
        # wrote one, else whole program executions
        base = op_events or mod_events
        if not base:
            busy_s.append(0.0)
            window_s.append(0.0)
            continue
        lo = min(ev[1] for ev in base)
        last = max(ev[1] + ev[2] for ev in base)
        hi = last if window_ns is None else max(window_ns[1], lo)
        stopped = max([last] + [ev[1] + ev[2] for ev in mod_events])
        merged = union([[max(ev[1], lo), min(ev[1] + ev[2], hi)] for ev in base
                        if ev[1] < hi and ev[1] + ev[2] > lo])
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        window_s.append((hi - lo) / 1e9)
        for name, start, dur in mod_events:
            end = start + dur
            by_stop = (abs(end - last) <= CUT_NS if window_ns is None
                       else stopped - end <= CUT_NS and end >= hi)
            if start - lo <= CUT_NS or by_stop:
                continue  # running when the trace came up, or when it stopped
            p = programs.setdefault(program_of(name), [0, 0.0])
            p[0] += 1
            p[1] += dur / 1e9
        for name, _start, dur in op_events:
            ops[name] = ops.get(name, 0.0) + dur / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    named = {}
    for s, e in gaps:
        mid = (s + e) // 2
        # the innermost (shortest) benchmark span over the gap's middle
        cover = [h for h in host if h[1] <= mid < h[2]]
        name = (min(cover, key=lambda h: h[2] - h[1])[0]
                if cover else "outside the benchmark's spans")
        named[name] = named.get(name, 0.0) + (e - s) / 1e9
    n = len(devices)
    return {
        "busy_s": sum(busy_s) / n,
        "window_s": sum(window_s) / n,
        "programs": {k: {"dispatches": v[0], "seconds": v[1]}
                     for k, v in programs.items()},
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(([k, v] for k, v in named.items()),
                            key=lambda kv: -kv[1])[:10],
    }
