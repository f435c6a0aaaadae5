"""Reduction of a profiler trace (``.xplane.pb``) to device time.

From the planes of the traced run this keeps, per TPU device, the
operations of its ``XLA Ops`` line, and from the host planes the
benchmark's own ``TraceAnnotation`` spans (``perfbench.sweep`` around
each ``run_lattice`` call, ``perfbench.between`` around the harness's loop
between calls). The profiler puts both on one clock.

Per device it gives: the union of the op intervals inside the window
(busy time), the time per op name, the idle gaps labelled by the host
span their midpoint fell in, and the idle time between the last op of one
sweep and the first op of the next. Intervals are half-open, in ns.

``python -m perfbench.trace <file.xplane.pb>`` prints the planes, lines
and the longest op names with their stats, for reading a trace by hand.
"""
from __future__ import annotations

import dataclasses
import sys
from collections import defaultdict

SPANS = ("perfbench.sweep", "perfbench.between")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Device:
    name: str
    busy_ns: float                      # union of op intervals in the window
    op_ns: dict                         # op name -> summed duration in the window
    op_count: dict                      # op name -> events in the window
    gaps: list                          # (label, start, end) idle intervals
    sweep_gaps_ns: list                 # idle time between consecutive sweeps


@dataclasses.dataclass
class Reduction:
    window: tuple                       # (start, end) of the traced window
    devices: list                       # one Device per TPU device

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def mean(self, f) -> float:
        return sum(f(d) for d in self.devices) / len(self.devices)


def union(intervals) -> list:
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(i) for i in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def label_of(t, spans) -> str:
    for name, s, e in spans:
        if s <= t < e:
            return name
    return "outside"


def reduce_events(device_ops: dict, host_spans: list, window=None) -> Reduction:
    """``device_ops``: device name -> [(op name, start, end)];
    ``host_spans``: [(span name, start, end)]. The window defaults to the
    first span's start to the last span's end."""
    if window is None:
        window = (min(s for _, s, _ in host_spans), max(e for _, _, e in host_spans))
    lo, hi = window
    sweeps = sorted((s, e) for n, s, e in host_spans if n == "perfbench.sweep")
    devices = []
    for dev in sorted(device_ops):
        ops = device_ops[dev]
        busy = clip(union((s, e) for _, s, e in ops), lo, hi)
        op_ns, op_count = defaultdict(float), defaultdict(int)
        for name, s, e in ops:
            c = clip([(s, e)], lo, hi)
            if c:
                op_ns[name] += c[0][1] - c[0][0]
                op_count[name] += 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [
            (label_of((s + e) / 2, host_spans), s, e)
            for s, e in zip(edges[::2], edges[1::2]) if e > s
        ]
        # device work of sweep k lies inside its host span (dispatch to
        # records on the host); the gap is last op of k to first op of k+1
        per_sweep = []
        for s, e in sweeps:
            inside = [iv for iv in busy if s <= iv[0] < e]
            if inside:
                per_sweep.append((inside[0][0], inside[-1][1]))
        sweep_gaps = [b[0] - a[1] for a, b in zip(per_sweep, per_sweep[1:])]
        devices.append(Device(
            name=dev, busy_ns=sum(e - s for s, e in busy), op_ns=dict(op_ns),
            op_count=dict(op_count), gaps=gaps, sweep_gaps_ns=sweep_gaps,
        ))
    return Reduction(window=window, devices=devices)


def op_name(event_name: str) -> str:
    """``fusion.3`` from the HLO text an op event carries
    (``%fusion.3 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


CONTROL_FLOW = ("while", "conditional", "call")


def leaves(ops) -> list:
    """The ops that do work: a ``while`` loop's or a ``conditional``'s event
    spans its whole body, whose ops are events of their own on the same
    line, so control-flow ops are left out."""
    return [o for o in ops if o[0].split(".", 1)[0] not in CONTROL_FLOW]


def events_of(pdata) -> tuple[dict, list]:
    """(device_ops, host_spans) from a ``jax.profiler.ProfileData``; device
    ops are the leaf ops of each TPU's ``XLA Ops`` line."""
    device_ops, spans = {}, []
    for plane in pdata.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = leaves(
                        (op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return device_ops, sorted(spans, key=lambda x: x[1])


def reduce_file(path: str) -> Reduction | None:
    """The reduction of one trace file, or None when it holds no TPU ops
    or no benchmark spans."""
    from jax.profiler import ProfileData

    device_ops, spans = events_of(ProfileData.from_file(path))
    if not device_ops or not any(n == "perfbench.sweep" for n, _, _ in spans):
        return None
    return reduce_events(device_ops, spans)


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The device ops that took most time (seconds, mean over devices) and
    the longest idle gaps, named by the host span they fell in."""
    ops = defaultdict(float)
    for d in red.devices:
        for name, ns in d.op_ns.items():
            ops[name] += ns / 1e9 / len(red.devices)
    gaps = sorted(
        ((label, (e - s) / 1e9) for d in red.devices for label, s, e in d.gaps),
        key=lambda x: -x[1],
    )
    return {
        "device_ops": [[n, s] for n, s in sorted(ops.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
    }


def describe(path: str, top: int = 25) -> None:
    from jax.profiler import ProfileData

    pdata = ProfileData.from_file(path)
    for plane in pdata.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: " + ", ".join(
            f"{ln.name!r} ({sum(1 for _ in ln.events)})" for ln in lines))
        if not plane.name.startswith("/device:"):
            continue
        for ln in lines:
            tot, stats = defaultdict(float), {}
            for ev in ln.events:
                tot[ev.name] += ev.duration_ns
                stats.setdefault(ev.name, list(ev.stats))
            for name, ns in sorted(tot.items(), key=lambda x: -x[1])[:top]:
                print(f"  {ln.name} | {name} | {ns / 1e6:.3f} ms | {stats[name]}")


if __name__ == "__main__":
    describe(sys.argv[1])
