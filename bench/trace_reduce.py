"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to device metrics.

Planes named ``/device:TPU:<n>`` carry the device timeline: the ``XLA
Modules`` line has one event per program execution, the ``XLA Ops`` line
one per HLO op (a ``while`` op encloses the ops of its body, a Pallas
kernel is a custom call named after its kernel function). Host planes
carry the ``jax.profiler.TraceAnnotation`` spans of the run, among them
the window the benchmark traced (``bench.window``) and the program's
``repro.obs`` spans (``serve.submit``, ``queue.flush``, ...). All
timestamps share one clock.

* busy: the union of the program executions inside the window, per device,
  averaged over the devices;
* op time: the device durations of each op inside the window, by op name
  (``%rff_krls_bank_chunk_pallas.8 = ...`` counts as
  ``rff_krls_bank_chunk_pallas``), leaving out the control-flow ops that
  enclose others;
* idle gaps: the stretches of the window with no program running on
  device 0, each charged to the innermost host span open at its middle.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

WINDOW = "bench.window"
_SPAN = re.compile(r"^[a-z_]+(\.[a-z_0-9]+)+$")  # repro.obs / bench span names
_CONTAINERS = {"while", "conditional", "call"}


@dataclass
class DeviceTrace:
    window_s: float
    busy_s: float
    devices: int
    ops: dict = field(default_factory=dict)  # op name -> device seconds
    idle: dict = field(default_factory=dict)  # host span -> idle seconds
    gaps: int = 0


def op_name(event: str) -> str:
    """``%rff_krls_bank_chunk_pallas.8 = (...) custom-call(...)`` ->
    ``rff_krls_bank_chunk_pallas``; a module ``jit_step(123)`` ->
    ``jit_step``."""
    head = event.split(" = ", 1)[0].lstrip("%").split("(", 1)[0].strip()
    return re.sub(r"\.\d+$", "", head)


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that the disjoint sorted ``busy``
    intervals leave open."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans, points) -> list[str]:
    """For each time in ``points``, the name of the innermost of the
    properly nested ``spans`` (``(start, end, name)``, one thread) that
    covers it, or ``"none"``: one sweep with a stack of open spans."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    out = ["none"] * len(points)
    stack: list = []
    k = 0
    for i in sorted(range(len(points)), key=points.__getitem__):
        p = points[i]
        while k < len(spans) and spans[k][0] <= p:
            while stack and stack[-1][1] <= spans[k][0]:
                stack.pop()
            stack.append(spans[k])
            k += 1
        while stack and stack[-1][1] <= p:
            stack.pop()
        if stack:
            out[i] = stack[-1][2]
    return out


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def reduce(profile) -> DeviceTrace:
    """Device metrics of the ``bench.window`` span of a loaded profile."""
    host, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            devices.append(lines)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [
                    (e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                    for e in line.events
                    if _SPAN.match(e.name)
                ]
    windows = [(a, b) for a, b, name in host if name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    if not devices:
        raise ValueError("no /device:TPU plane in the trace")
    lo, hi = windows[0]
    busy_each, ops = [], {}
    for lines in devices:
        mods = [
            (e.start_ns * 1e-9, e.end_ns * 1e-9)
            for e in lines.get("XLA Modules", [])
        ]
        busy_each.append(union(clip(mods, lo, hi)))
        for e in lines.get("XLA Ops", []):
            name = op_name(e.name)
            if name.split(".")[0] in _CONTAINERS:
                continue
            seg = clip([(e.start_ns * 1e-9, e.end_ns * 1e-9)], lo, hi)
            if seg:
                ops[name] = ops.get(name, 0.0) + seg[0][1] - seg[0][0]
    idle_gaps = gaps(busy_each[0], lo, hi)
    labels = innermost(
        [s for s in host if s[2] != WINDOW], [(a + b) / 2 for a, b in idle_gaps]
    )
    idle: dict = {}
    for (a, b), label in zip(idle_gaps, labels):
        idle[label] = idle.get(label, 0.0) + (b - a)
    return DeviceTrace(
        window_s=hi - lo,
        busy_s=sum(sum(b - a for a, b in u) for u in busy_each) / len(devices),
        devices=len(devices),
        ops=ops,
        idle=idle,
        gaps=len(idle_gaps),
    )


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
