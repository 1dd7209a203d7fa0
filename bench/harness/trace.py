"""Profiler capture and the reduction from an XPlane trace to device intervals.

`capture(log_dir)` records a JAX profiler trace with the Python tracer off
(a 30-second window of Python calls would be far larger than the device
trace). `load(log_dir)` reads the `.xplane.pb` it wrote and returns a
`Trace`: for each device plane, the events of its "XLA Ops" line (the
operations as the device ran them), and for the host, the annotation
events the benchmark wrapped around its own phases.

`Trace` is what the per-layer metric readers work on. Times are in
nanoseconds on the profiler's common clock; every device interval is clipped
to the window the benchmark marked with `WINDOW_SPAN`.
"""
from __future__ import annotations

import contextlib
import glob
import os
from dataclasses import dataclass, field
from typing import Callable, Iterable

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"


@dataclass
class Event:
    name: str
    start: float  # ns
    dur: float  # ns
    stats: dict = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


@contextlib.contextmanager
def capture(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _events(line) -> list:
    out = []
    for e in line.events:
        stats = {}
        for k, v in e.stats:
            stats[str(k)] = v
        out.append(Event(str(e.name), float(e.start_ns), float(e.duration_ns),
                         stats))
    return out


def load(log_dir: str) -> "Trace":
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, host = {}, []
    for plane in data.planes:
        name = str(plane.name)
        if name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[name] = _events(line)
        elif name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return Trace.build(ops, host)


def union_length(intervals: Iterable[tuple]) -> float:
    """Total length covered by a set of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _merged(intervals: Iterable[tuple]) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class Trace:
    ops: dict  # device plane name -> [Event], clipped to the window
    host: list  # [Event] on the host plane
    window: tuple  # (start, end) ns

    @classmethod
    def build(cls, ops: dict, host: list) -> "Trace":
        spans = [e for e in host if e.name == WINDOW_SPAN]
        if spans:
            w0 = min(e.start for e in spans)
            w1 = max(e.end for e in spans)
        else:
            every = [e for evs in ops.values() for e in evs]
            if not every:
                raise ValueError("trace holds no device operation")
            w0 = min(e.start for e in every)
            w1 = max(e.end for e in every)
        clip = {k: [c for c in (_clip(e, w0, w1) for e in v) if c is not None]
                for k, v in ops.items()}
        # device planes that ran nothing in the window (idle cores of a
        # multi-core plane listing) are not chips this run used
        clip = {k: v for k, v in clip.items() if v}
        return cls(clip, host, (w0, w1))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> list:
        return sorted(self.ops)

    def busy_s(self, device: str) -> float:
        return union_length((e.start, e.end) for e in self.ops[device]) * 1e-9

    def mean_busy_s(self) -> float:
        devs = self.devices
        return sum(self.busy_s(d) for d in devs) / len(devs) if devs else 0.0

    def op_s(self, device: str, pred: Callable[[Event], bool]) -> float:
        """Device time of the operations `pred` selects, overlaps merged."""
        return union_length((e.start, e.end) for e in self.ops[device]
                            if pred(e)) * 1e-9

    def mean_op_s(self, pred: Callable[[Event], bool]) -> float:
        devs = self.devices
        return sum(self.op_s(d, pred) for d in devs) / len(devs) if devs else 0.0

    def top_ops(self, k: int = 10) -> list:
        """[instruction, seconds] of the k operations with the most device
        time, summed over devices and divided by their number."""
        devs = self.devices
        tot: dict = {}
        for d in devs:
            for e in self.ops[d]:
                name = instruction(e.name)
                tot[name] = tot.get(name, 0.0) + e.dur
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9 / len(devs)] for name, ns in ranked]

    def idle_gaps(self, k: int = 10) -> list:
        """[label, seconds] of the k longest idle stretches of the first
        device, each labelled by the host span that overlaps it most."""
        if not self.devices:
            return []
        busy = _merged((e.start, e.end) for e in self.ops[self.devices[0]])
        w0, w1 = self.window
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._host_label(s, e), (e - s) * 1e-9] for s, e in gaps[:k]]

    def _host_label(self, s: float, e: float) -> str:
        best, best_key = "no host span", None
        for h in self.host:
            if h.name == WINDOW_SPAN:
                continue
            ov = min(e, h.end) - max(s, h.start)
            if ov <= 0:
                continue
            key = (ov, -h.dur)
            if best_key is None or key > best_key:
                best, best_key = h.name, key
        return best


def instruction(name: str) -> str:
    """The HLO instruction of a TPU op event: "%fusion.3 = f32[...] ..." ->
    "fusion.3"; other names are kept."""
    head = name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _clip(e: Event, w0: float, w1: float):
    s, t = max(e.start, w0), min(e.end, w1)
    if t <= s:
        return None
    return Event(e.name, s, t - s, e.stats)
