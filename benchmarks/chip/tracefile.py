"""Reduction of a JAX profiler trace to the intervals the metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three things: for each TPU, the device operations (name, start, end) of its
``XLA Ops`` line; the benchmark's own host spans (``HOST_SPANS``); and the
traced window, which is the ``bench_window`` host span. Times are integer
nanoseconds on the profiler's clock, which host and device events share.

Everything else here works on that plain ``Trace`` so that it can be tested
on small hand-made traces.
"""

from __future__ import annotations

import dataclasses
import glob
import os

#: host spans the benchmark records around its own calls into the program
HOST_SPANS = ("segment_dispatch", "telemetry_drain", "results_fetch")
WINDOW_SPAN = "bench_window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    devices: list          # per chip: [(name, start_ns, end_ns), ...]
    host: list             # [(name, start_ns, end_ns), ...]
    window: tuple          # (start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, "
                           f"found {paths}")
    data = ProfileData.from_file(paths[0])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            host += [(e.name, int(e.start_ns),
                      int(e.start_ns + e.duration_ns))
                     for line in plane.lines for e in line.events
                     if e.name in HOST_SPANS or e.name == WINDOW_SPAN]
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if len(windows) != 1 or not devices:
        raise RuntimeError(f"trace has {len(windows)} window spans and "
                           f"{len(devices)} TPU planes")
    return Trace(devices=devices,
                 host=[h for h in host if h[0] != WINDOW_SPAN],
                 window=windows[0])


def union(intervals, lo: int, hi: int) -> list:
    """Disjoint sorted [start, end) intervals covering ``intervals`` clipped
    to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for _, s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(trace: Trace, chip: int) -> float:
    """Seconds of the window in which some operation ran on ``chip``."""
    return sum(e - s for s, e in union(trace.devices[chip],
                                       *trace.window)) * 1e-9


def idle_gaps(trace: Trace, chip: int) -> list:
    """[(start_ns, end_ns)] of the window in which nothing ran on ``chip``."""
    lo, hi = trace.window
    gaps, t = [], lo
    for s, e in union(trace.devices[chip], lo, hi):
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_label(trace: Trace, start: int, end: int) -> str:
    """The host span that overlaps [start, end) the most, or ``other``."""
    best, name = 0, "other"
    for n, s, e in trace.host:
        ov = min(e, end) - max(s, start)
        if ov > best:
            best, name = ov, n
    return name


def op_seconds(trace: Trace, chip: int, match) -> tuple:
    """(seconds, count) of the window's operations on ``chip`` whose name
    satisfies ``match``."""
    lo, hi = trace.window
    hits = [(s, e) for n, s, e in trace.devices[chip]
            if match(n) and s >= lo and e <= hi]
    return sum(e - s for s, e in hits) * 1e-9, len(hits)


def op_name(text: str) -> str:
    """``%fusion.12`` of an op's HLO text ``%fusion.12 = f32[...] ...``."""
    return text.split(" = ", 1)[0]


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took the most time (summed by name over
    the window, chip 0; ``while`` loops, which contain other operations,
    left out) and the longest idle gaps of chip 0, each gap labelled by the
    host span it fell in."""
    lo, hi = trace.window
    by_name: dict = {}
    for text, s, e in trace.devices[0]:
        n = op_name(text)
        if s >= lo and e <= hi and not n.startswith("%while"):
            by_name[n] = by_name.get(n, 0) + (e - s)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace, 0), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t * 1e-9] for n, t in ops],
            "idle_gaps": [[host_label(trace, s, e), (e - s) * 1e-9]
                          for s, e in gaps]}
