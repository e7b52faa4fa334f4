"""Reduction of a profiler trace to device busy time, kernel time and gaps.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps, as plain dicts, the device planes' op and program events and the
harness's ``bench.window`` annotation from the host plane.  ``reduce``
works on that list alone, so a small recorded trace, committed as JSON,
checks it.

Times in a trace are nanoseconds from the trace's start.  The harness
opens a ``bench.window`` annotation at the moment it records on its own
clock, so host spans taken with ``time.perf_counter`` map onto the trace
by one offset (``host_spans_to_events``).
"""
from __future__ import annotations

import bisect
import glob
import os

DEVICE_PREFIX = "/device:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"  # one event per program run: jit_<function>(<id>)
DEVICE_LINES = (OP_LINE, MODULE_LINE)
WINDOW = "bench.window"


def load_events(trace_dir: str) -> list[dict]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    out: list = []
    window = None
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name in DEVICE_LINES:
                out.extend(_event(plane, line, ev) for ev in line.events)
            elif not device and window is None:
                # host lines hold one event per JAX dispatch: read them only
                # as far as the harness's window annotation
                window = next((ev for ev in line.events if ev.name == WINDOW), None)
                if window is not None:
                    out.append(_event(plane, line, window))
    return out


NAME_CHARS = 100  # an op's name is its HLO text; its head names it well enough


def _event(plane, line, ev) -> dict:
    return {"plane": plane.name, "line": line.name, "name": ev.name[:NAME_CHARS],
            "start_ns": float(ev.start_ns), "dur_ns": float(ev.duration_ns)}


def host_spans_to_events(spans, window_perf_s: float, events: list[dict]) -> list[dict]:
    """Host spans ``(name, t0, t1)`` on ``time.perf_counter`` as trace
    events, aligned by the ``bench.window`` annotation, which opened at
    ``window_perf_s``."""
    w = [e for e in events if e["name"] == WINDOW]
    if not w:
        return []
    off_ns = w[0]["start_ns"] - window_perf_s * 1e9
    return [
        {"plane": "/host:spans", "line": "spans", "name": name,
         "start_ns": t0 * 1e9 + off_ns, "dur_ns": (t1 - t0) * 1e9}
        for name, t0, t1 in spans
    ]


def _union(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _device_ops(events: list[dict]) -> dict:
    """Device plane -> its op events (the ``XLA Ops`` line where the plane
    has one, else every line of the plane)."""
    planes: dict = {}
    for e in events:
        if e["plane"].startswith(DEVICE_PREFIX):
            planes.setdefault(e["plane"], []).append(e)
    return {
        p: [e for e in evs if e["line"] == OP_LINE] or evs
        for p, evs in planes.items()
    }


def reduce(events: list[dict], top: int = 10) -> dict | None:
    """Busy and window seconds, op totals and labelled idle gaps.

    Busy is the union of the intervals in which an op ran on a device,
    inside the ``bench.window`` annotation, averaged over the device
    planes.  Each idle gap is labelled with the innermost host span
    (harness annotation or program stage span) open at its midpoint.
    Returns ``None`` where the trace has no window or no device op.
    """
    w = [e for e in events if e["name"] == WINDOW]
    dev = {p: ops for p, ops in _device_ops(events).items() if ops}
    if not w or not dev:
        return None
    w0, w1 = w[0]["start_ns"], w[0]["start_ns"] + w[0]["dur_ns"]
    host = [
        e for e in events
        if not e["plane"].startswith(DEVICE_PREFIX) and e["name"] != WINDOW
    ]
    label = _Labeller(host)
    busy_ns, op_ns, gaps = [], {}, []
    for p, ops in sorted(dev.items()):
        clipped = []
        for e in ops:
            a, b = max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)
            if b > a:
                clipped.append((a, b))
                op_ns[e["name"]] = op_ns.get(e["name"], 0.0) + (b - a)
        merged = _union(clipped)
        busy_ns.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, label((a + b) / 2, b - a)))
    module_ns: dict = {}
    for e in events:
        if e["plane"].startswith(DEVICE_PREFIX) and e["line"] == MODULE_LINE:
            a, b = max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)
            if b > a:
                module_ns[e["name"]] = module_ns.get(e["name"], 0.0) + (b - a)
    by_label: dict = {}
    for d, lab in gaps:
        by_label[lab] = by_label.get(lab, 0.0) + d
    n_dev = len(dev)
    return {
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [
            [name, ns / n_dev / 1e9]
            for name, ns in sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [lab, d / 1e9] for d, lab in sorted(gaps, key=lambda g: -g[0])[:top]
        ],
        "idle_by_label": {lab: d / n_dev / 1e9 for lab, d in by_label.items()},
        "op_ns": op_ns,
        "module_ns": module_ns,
        "n_devices": n_dev,
    }


SHORT_GAP_NS = 1e5  # gaps under 0.1 ms are lumped, not labelled one by one


class _Labeller:
    """The innermost (shortest) host span open at a time.  A span that
    contains ``t`` starts before it; the search looks back over the
    latest-starting spans first, and over at most ``depth`` of them."""

    def __init__(self, host: list[dict], depth: int = 256):
        self.spans = sorted(host, key=lambda e: e["start_ns"])
        self.starts = [e["start_ns"] for e in self.spans]
        self.longest = max((e["dur_ns"] for e in host), default=0.0)
        self.depth = depth

    def __call__(self, t: float, width: float) -> str:
        if width < SHORT_GAP_NS:
            return "gaps under 0.1 ms"
        i = bisect.bisect_right(self.starts, t)
        best = None
        for e in self.spans[max(i - self.depth, 0):i][::-1]:
            if t - e["start_ns"] > self.longest:
                break
            if t <= e["start_ns"] + e["dur_ns"] and (best is None or e["dur_ns"] < best["dur_ns"]):
                best = e
        if best is None:  # an enclosing long span may start further back
            for e in self.spans[: max(i - self.depth, 0)]:
                if t <= e["start_ns"] + e["dur_ns"] and (
                    best is None or e["dur_ns"] < best["dur_ns"]
                ):
                    best = e
        return best["name"] if best is not None else "no host span open"


def device_time_in(events: list[dict], spans: list[tuple], name_filter=None) -> float:
    """Seconds of device op time (averaged over device planes) that lies
    inside the given trace-clock intervals ``[(start_ns, end_ns), ...]``;
    ``name_filter(op_name)`` narrows the ops."""
    iv = _union([(a, b) for a, b in spans])
    dev = _device_ops(events)
    if not dev or not iv:
        return 0.0
    ends = [b for _, b in iv]
    total = 0.0
    for ops in dev.values():
        for e in ops:
            if name_filter is not None and not name_filter(e["name"]):
                continue
            a0, b0 = e["start_ns"], e["start_ns"] + e["dur_ns"]
            for a, b in iv[bisect.bisect_right(ends, a0):]:
                if a >= b0:
                    break
                total += min(b, b0) - max(a, a0)
    return total / len(dev) / 1e9


def op_seconds(reduced: dict, match, key: str = "op_ns") -> float:
    """Summed device seconds (per device) of the ops (``key="module_ns"``:
    the programs) whose name ``match`` accepts."""
    n = reduced["n_devices"]
    return sum(ns for name, ns in reduced[key].items() if match(name)) / n / 1e9
