"""Reduce a profiler trace (``.xplane.pb``) to what the metric readers use.

A TPU trace holds one plane per chip, ``/device:TPU:<i>``, whose line
``XLA Ops`` has one event per HLO instruction that ran, named by the
instruction's text (``%fusion.12 = f32[...] fusion(...)``). Ops nest: a
``while`` op's event spans the events of its body. The host plane,
``/host:CPU``, holds one line per thread; the harness's own
``TraceAnnotation`` spans (names starting ``chipbench.``) are on the line
of the thread that ran the window. Times are nanoseconds from the start
of the profile, on one clock for the host and the chips.

Only ``jax.profiler.ProfileData`` is needed to read a trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
MAX_GAPS = 2000


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``collective-permute-done.3`` ->
    ``collective-permute-done``: the instruction without its number."""
    return re.sub(r"\.\d+$", "", name)


def merged(intervals) -> list[tuple[float, float]]:
    """The union of (start, end) intervals as disjoint sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


@dataclasses.dataclass
class Device:
    """One chip's ops in the traced window."""

    index: int
    ops: list[tuple[float, float, str]]  # (start, end, op name), leaves and parents
    self_ns: dict[str, float]  # op name -> time not covered by its child ops
    busy: list[tuple[float, float]]  # disjoint intervals in which some op ran

    def busy_ns(self, t0: float, t1: float) -> float:
        return sum(e - s for s, e in clip(self.busy, t0, t1))


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]  # the harness's chipbench.window span
    devices: list[Device]
    spans: list[tuple[float, float, str]]  # chipbench.* host spans
    host: list[tuple[float, float, str]]  # every other host event

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the chips."""
        t0, t1 = self.window
        return sum(d.busy_ns(t0, t1) for d in self.devices) * 1e-9 / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def units(self) -> list[tuple[float, float]]:
        return [(s, e) for s, e, n in self.spans if n == SPAN_PREFIX + "unit"]

    def op_seconds(self, match) -> float:
        """Self seconds of the ops whose name ``match`` accepts, summed
        over the chips and divided by their number."""
        total = sum(ns for d in self.devices for name, ns in d.self_ns.items() if match(name))
        return total * 1e-9 / len(self.devices)

    def any_busy(self) -> list[tuple[float, float]]:
        """Intervals in which at least one chip ran an op."""
        return merged(iv for d in self.devices for iv in d.busy)

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` op kinds with the most self time, seconds per chip."""
        by_kind: dict[str, float] = {}
        for d in self.devices:
            for name, ns in d.self_ns.items():
                k = op_kind(name)
                by_kind[k] = by_kind.get(k, 0.0) + ns
        top = sorted(by_kind.items(), key=lambda kv: -kv[1])[:n]
        return [[k, ns * 1e-9 / len(self.devices)] for k, ns in top]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle time of chip 0 in the window, by what the host was doing:
        each of the ``MAX_GAPS`` longest gaps goes to the shortest host
        event that covers its middle; the ``n`` largest totals, in
        seconds."""
        t0, t1 = self.window
        busy = clip(self.devices[0].busy, t0, t1)
        gaps, cur = [], t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < t1:
            gaps.append((cur, t1))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:MAX_GAPS]
        events = self.host + self.spans
        starts = np.array([s for s, _, _ in events], np.float64)
        ends = np.array([e for _, e, _ in events], np.float64)
        by_what: dict[str, float] = {}
        for gs, ge in gaps:
            mid = 0.5 * (gs + ge)
            cover = np.flatnonzero((starts <= mid) & (ends >= mid))
            if cover.size:
                what = events[cover[np.argmin(ends[cover] - starts[cover])]][2]
            else:
                what = "(no host event)"
            by_what[what] = by_what.get(what, 0.0) + (ge - gs)
        top = sorted(by_what.items(), key=lambda kv: -kv[1])[:n]
        return [[w, ns * 1e-9] for w, ns in top]


def _self_times(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Self time per op name from properly nested (start, end, name) events."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, name, duration, children's time]

    def close(item):
        _, name, dur, child = item
        out[name] = out.get(name, 0.0) + dur - child

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0.0])
    while stack:
        close(stack.pop())
    return out


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {files}")
    return files[0]


def read(path: str, devices: int | None = None) -> Trace:
    """Reduce the trace at ``path`` (a file, or a profiler log directory).
    ``devices`` keeps the planes of chips 0..devices-1 only."""
    import jax

    if os.path.isdir(path):
        path = find_xplane(path)
    data = jax.profiler.ProfileData.from_file(path)
    chips, spans, host = [], [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            index = int(m.group(1))
            if devices is not None and index >= devices:
                continue
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, op_name(ev.name))
                           for ev in line.events]
            chips.append(Device(index, ops, _self_times(ops),
                                merged((s, e) for s, e, _ in ops)))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    item = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    (spans if ev.name.startswith(SPAN_PREFIX) else host).append(item)
    if not chips:
        raise ValueError(f"no TPU device plane in {path}")
    chips.sort(key=lambda d: d.index)
    windows = [(s, e) for s, e, n in spans if n == SPAN_PREFIX + "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one {SPAN_PREFIX}window span in {path}, found {len(windows)}")
    return Trace(windows[0], chips, sorted(spans), sorted(host))
