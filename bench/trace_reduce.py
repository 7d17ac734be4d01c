"""Reduce a JAX profiler trace to the benchmark's device numbers.

``from_profile`` reads the ``.xplane.pb`` a traced run wrote and returns
three lists of ``(name, start_ns, duration_ns)``: the operations that ran
on the device (the "XLA Ops" line of each ``/device:TPU:<n>`` plane), the
XLA modules (one per launched jitted program, "XLA Modules" line), and the
host spans the benchmark opened with ``jax.profiler.TraceAnnotation``
(names starting ``bench.``).  ``reduce`` turns them into:

  busy_s        union of the device-op intervals inside the window,
                averaged over the devices
  window_s      length of the window
  idle_share    1 - busy_s / window_s
  modules       {module name: device seconds}, the name without JAX's
                ``jit_`` prefix and the ``(n)`` instance suffix
  module_calls  {module name: launches}
  device_ops    [[op name, device seconds], ...], the 10 largest, each
                op named by ``op_name``
  idle_gaps     [[host span, seconds], ...], the 10 longest gaps between
                device ops, each named by the innermost benchmark span
                the host was in at the gap's midpoint ("none" outside any)
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

Event = tuple[str, float, float]          # name, start_ns, duration_ns

_SUFFIX = re.compile(r"\(\d+\)$")


_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def module_name(raw: str) -> str:
    name = _SUFFIX.sub("", raw.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(raw: str) -> str:
    """A device op's name as the trace gives it may be the whole HLO
    instruction; keep its name and the first shape it produces, as in
    ``%copy.19 u32[131072,1280]``."""
    name, sep, rest = raw.partition(" = ")
    if not sep:
        return raw
    shape = _SHAPE.search(rest)
    return f"{name} {shape.group(0)}" if shape else name


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def from_profile(path: str, span_prefix: str = "bench."
                 ) -> tuple[dict[str, list[Event]], dict[str, list[Event]],
                            list[Event]]:
    """(ops per device, modules per device, host spans) of one trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SYSTEM" not in plane.name:
            for line in plane.lines:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = evs
                elif line.name == "XLA Modules":
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(span_prefix))
    return ops, modules, spans


def _clip(events: list[Event], t0: float, t1: float):
    for _, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield a, b


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label(t: float, spans: list[Event]) -> str:
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "none"


def reduce(ops: dict[str, list[Event]], modules: dict[str, list[Event]],
           spans: list[Event], window: tuple[float, float],
           top: int = 10) -> dict:
    """Device numbers of one traced window (times in ns in, seconds out)."""
    t0, t1 = window
    window_s = (t1 - t0) * 1e-9
    devices = sorted(ops) or sorted(modules)
    busy_ns = 0.0
    gaps: list[tuple[float, float]] = []
    for dev in devices:
        spans_busy = union(_clip(ops.get(dev) or modules.get(dev, []), t0, t1))
        busy_ns += sum(b - a for a, b in spans_busy)
        if dev == devices[0]:
            edges = [t0] + [x for ab in spans_busy for x in ab] + [t1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    busy_s = busy_ns * 1e-9 / max(len(devices), 1)
    per_module: dict[str, float] = defaultdict(float)
    calls: dict[str, int] = defaultdict(int)
    for dev in devices:
        for name, s, d in modules.get(dev, []):
            if t0 <= s < t1:
                per_module[module_name(name)] += d * 1e-9
                calls[module_name(name)] += 1
    per_op: dict[str, float] = defaultdict(float)
    for dev in devices:
        for name, a, b in ((n, s, s + d) for n, s, d in ops.get(dev, [])):
            lo, hi = max(a, t0), min(b, t1)
            if hi > lo:
                per_op[op_name(name)] += (hi - lo) * 1e-9
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "modules": dict(per_module),
        "module_calls": dict(calls),
        "device_ops": [[n, s] for n, s in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label((a + b) / 2, spans), (b - a) * 1e-9]
                      for a, b in longest],
    }


def window_of(spans: list[Event], name: str = "bench.window"
              ) -> tuple[float, float]:
    """The traced window: the benchmark's span of that name."""
    for n, s, d in spans:
        if n == name:
            return s, s + d
    raise ValueError(f"no {name} span in the trace")
