"""The program's own instrumentation, read from the traced run's profile.

The program names each stage of its round step on the device (a
``jax.named_scope`` per stage) and registers the op -> stage map of every
lattice module it compiles with ``repro.obs.stages``; its host spans
(``lattice.sweep`` and the child spans ``lattice.place``,
``lattice.dispatch``, ``lattice.wait``, ``lattice.stream_out``,
``lattice.assemble``) are profiler annotations, on the clock of the device
ops. This module reads both from the trace of a ``--trace 1`` run, once,
for the per-layer metrics that need them.

The trace is the newest ``.xplane.pb`` under
``chiprun_out/perfbench/*/trace/`` written after this process started. Its
device ops are named ``<module>/<op>``, the module being the one whose
execution (the device's ``XLA Modules`` line) holds the op, because two
modules can both hold a ``fusion.3``. They are reduced by
``perfbench.trace.reduce_events`` over the window of the harness's own
spans, as the accepted metrics are.

Idle time is split at the boundaries of the harness's and the program's
spans, and each piece goes to the innermost span open over it: a
``lattice.*`` span wherever the program had one open. A program that
lacks the stage map or the spans (an older checkout) reads as None: the
metrics are then left out of the result line.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import sys
from collections import defaultdict

from perfbench import trace as btrace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROGRAM_SPAN = "lattice."
SWEEP = "lattice.sweep"
MODULES_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class ProgramTrace:
    red: btrace.Reduction               # device ops named "<module>/<op>"
    spans: list                         # (name, start, end), by start
    n_sweeps: int                       # lattice.sweep spans in the window


def module_of(event_name: str) -> str:
    """``jit__fused_lattice_cell`` from ``jit__fused_lattice_cell(1213...)``."""
    return _FINGERPRINT.sub("", event_name)


def qualify(device_ops: dict, modules: dict) -> dict:
    """Name each op ``<module>/<op>`` by the module execution that holds
    its start (``modules``: device -> [(module, start, end)]); an op outside
    every execution keeps its bare name."""
    out = {}
    for dev, ops in device_ops.items():
        runs = sorted(modules.get(dev, []), key=lambda r: r[1])
        named, i = [], 0
        for name, s, e in sorted(ops, key=lambda o: o[1]):
            while i < len(runs) and runs[i][2] <= s:
                i += 1
            inside = i < len(runs) and runs[i][1] <= s
            named.append((f"{runs[i][0]}/{name}" if inside else name, s, e))
        out[dev] = named
    return out


def from_events(device_ops: dict, modules: dict, host_spans: list) -> ProgramTrace | None:
    """The reading of one run: ``device_ops`` device -> [(op, start, end)],
    ``modules`` device -> [(module, start, end)], ``host_spans`` [(name,
    start, end)] holding the harness's ``perfbench.*`` spans and the
    program's ``lattice.*`` spans. None without a ``perfbench.sweep``."""
    harness = [sp for sp in host_spans if sp[0] in btrace.SPANS]
    if not device_ops or not any(n == "perfbench.sweep" for n, _, _ in harness):
        return None
    red = btrace.reduce_events(qualify(device_ops, modules), harness)
    lo, hi = red.window
    spans = sorted(host_spans, key=lambda sp: (sp[1], -sp[2]))
    n_sweeps = sum(1 for n, s, e in spans if n == SWEEP and lo <= s and e <= hi)
    return ProgramTrace(red=red, spans=spans, n_sweeps=n_sweeps)


def stage_ns(pt: ProgramTrace) -> list[dict] | None:
    """Per device, round stage -> device time in the window, from the op ->
    stage maps the program registered; None when the program registered
    none for the modules that ran (an older program)."""
    try:
        from repro.obs.stages import UNSCOPED, stage_map
    except ImportError:
        return None
    found, out = False, []
    for d in pt.red.devices:
        per = defaultdict(float)
        for name, ns in d.op_ns.items():
            module, _, op = name.rpartition("/")
            table = stage_map(module) if module else None
            found = found or table is not None
            per[(table or {}).get(op, UNSCOPED)] += ns
        out.append(dict(per))
    return out if found else None


def idle_ns(pt: ProgramTrace) -> list[dict]:
    """Per device, innermost span -> device-idle time in the window (None
    for idle time under no span). Each idle interval is cut at every span
    boundary inside it."""
    bounds = sorted({t for _, s, e in pt.spans for t in (s, e)})
    out = []
    for d in pt.red.devices:
        per = defaultdict(float)
        for _, s, e in d.gaps:
            inner = bounds[bisect.bisect_right(bounds, s):bisect.bisect_left(bounds, e)]
            cuts = [s] + inner + [e]
            for a, b in zip(cuts, cuts[1:]):
                per[innermost(pt.spans, a, b)] += b - a
        out.append(dict(per))
    return out


def innermost(spans: list, a: float, b: float) -> str | None:
    """The latest-starting span that covers [a, b) (spans nest on one
    thread, so that is the innermost one)."""
    best = None
    for name, s, e in spans:
        if s > a:
            break
        if e >= b:
            best = name
    return best


def per_sweep_ms(pt: ProgramTrace, ns_per_device: list):
    """Mean over devices of time per sweep, in ms."""
    if not pt.n_sweeps:
        return None
    return sum(ns / pt.n_sweeps / 1e6 for ns in ns_per_device) / len(ns_per_device)


def _process_start() -> float:
    """This process's start, in seconds since the epoch (0 when unknown)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(ln.split()[1]) for ln in f if ln.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return 0.0


def find_trace() -> str | None:
    """The newest trace of this process under ``chiprun_out/perfbench``."""
    start = _process_start() - 1.0
    files = [
        f for f in glob.glob(
            os.path.join(ROOT, "chiprun_out", "perfbench", "*", "trace", "**",
                         "*.xplane.pb"),
            recursive=True,
        )
        if os.path.getmtime(f) >= start
    ]
    return max(files, key=os.path.getmtime) if files else None


def events_of(pdata) -> tuple[dict, dict, list]:
    """(device ops, module executions, host spans) of a
    ``jax.profiler.ProfileData``."""
    device_ops, _ = btrace.events_of(pdata)
    modules, spans = defaultdict(list), []
    for plane in pdata.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (module_of(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in btrace.SPANS or ev.name.startswith(PROGRAM_SPAN):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return device_ops, dict(modules), spans


_CACHE: dict = {}


def current() -> ProgramTrace | None:
    """The reading of this process's trace, parsed once; None when there is
    no trace or it holds nothing to read."""
    path = find_trace()
    if path is None:
        return None
    if path not in _CACHE:
        from jax.profiler import ProfileData

        _CACHE[path] = pt = from_events(*events_of(ProfileData.from_file(path)))
        if pt is not None:
            _log_summary(pt)
    return _CACHE[path]


def _log_summary(pt: ProgramTrace) -> None:
    """One stderr line with every stage and every span's idle time, for the
    record (the metrics carry a few of them)."""
    idle = idle_ns(pt)
    spans = sorted({k for per in idle for k in per}, key=str)
    parts = [f"{pt.n_sweeps} lattice sweeps"]
    parts.append("idle ms/sweep " + ", ".join(
        f"{k}={per_sweep_ms(pt, [per.get(k, 0.0) for per in idle])!r}" for k in spans
    ))
    stages = stage_ns(pt)
    if stages is not None:
        names = sorted({k for per in stages for k in per})
        busy = [d.busy_ns for d in pt.red.devices]
        parts.append("device ns by stage " + ", ".join(
            f"{k}={[per.get(k, 0.0) for per in stages]!r}" for k in names
        ) + f"; busy ns {busy!r}")
    print("[perfbench] program trace: " + "; ".join(parts), file=sys.stderr, flush=True)


def stage_ms(ctx, stage: str):
    """Device time of one round stage per lattice round, in ms, mean over
    the chips; None when there is nothing to read."""
    pt = current() if ctx.red is not None else None
    stages = stage_ns(pt) if pt is not None else None
    if stages is None:
        return None
    per_sweep = per_sweep_ms(pt, [per.get(stage, 0.0) for per in stages])
    return None if per_sweep is None else per_sweep / ctx.traffic["rounds"]


def idle_ms(ctx, names: tuple):
    """Device-idle time per sweep under the ``names`` spans (innermost), in
    ms, mean over the chips; None when there is nothing to read."""
    pt = current() if ctx.red is not None else None
    if pt is None or not pt.n_sweeps:
        return None
    return per_sweep_ms(pt, [sum(per.get(n, 0.0) for n in names) for per in idle_ns(pt)])
