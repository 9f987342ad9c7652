"""Reduction of a profiler trace to the numbers a traced run reports.

A `--trace 1` run records one `jax.profiler` trace around its measured
window; the window itself is the host span `bench.window`, and the runners
put `bench.*` spans around every call into a layer of the program.  From
the trace this module takes:

  busy_s       union of the intervals in which an XLA op ran on a chip,
               inside the window, averaged over the chips that ran any
  window_s     length of the `bench.window` span
  device_ops   device time by stable op name: the jitted program's name
               and the op's name, both without the numeric ids that change
               from build to build (`_sampled_chunk_forward/fusion`)
  programs     device time by jitted program
  idle_gaps    the longest stretches of the window in which the first
               chip ran nothing, each named by the innermost `bench.*`
               span the host was in at the gap's middle
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
_ID = re.compile(r"(\.\d+)+$|\(\d+\)$")
_HLO_NAME = re.compile(r"^%?([\w.\-]+) = ")
_FUSION_KIND = re.compile(r"kind=(k\w+)")


def stable(name: str) -> str:
    """A program or op name without build-dependent numeric ids."""
    name = _ID.sub("", name.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(hlo: str) -> str:
    """An XLA op event's stable name: its HLO instruction name without
    ids, and for a fusion its kind (kLoop, kInput, kOutput, ...)."""
    m = _HLO_NAME.match(hlo)
    name = stable(m.group(1) if m else hlo.split(" ")[0])
    kind = _FUSION_KIND.search(hlo)
    return f"{name}:{kind.group(1)}" if name == "fusion" and kind else name


def newest_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _containing(modules, t: float) -> str:
    """Name of the program (XLA module event) running at time t."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return ""


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name \
        and "NON_CORE" not in plane_name and "HOST" not in plane_name


def read_planes(xplane: Path):
    """(host spans, {device: op events}) from a trace file: each span and
    op as (name, start_ns, end_ns); an op also carries its program."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(xplane))
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        elif _is_device(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((ev.start_ns, ev.end_ns, ev.name)
                             for ev in lines.get("XLA Modules", []))
            ops = []
            for ev in lines.get("XLA Ops", []):
                program = _containing(modules, ev.start_ns)
                ops.append((op_name(ev.name), ev.start_ns, ev.end_ns,
                            program))
            if ops:
                devices[plane.name] = ops
    return spans, devices


def reduce_trace(spans, devices, top: int = 10) -> Optional[Dict]:
    """Busy and window seconds, op and program totals, and idle gaps, or
    None when the trace has no window span or no device op."""
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows or not devices:
        return None
    w0, w1 = windows[-1]
    busy, op_time, prog_time = [], {}, {}
    first_idle: List[Tuple[float, float]] = []
    for i, (dev, ops) in enumerate(sorted(devices.items())):
        clipped = []
        for name, s, e, program in ops:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            key = f"{stable(program)}/{stable(name)}" if program \
                else stable(name)
            op_time[key] = op_time.get(key, 0.0) + (e - s) * 1e-9
            if program:
                prog_time[stable(program)] = (prog_time.get(stable(program),
                                                            0.0)
                                              + (e - s) * 1e-9)
        union = _union(clipped)
        busy.append(sum(e - s for s, e in union) * 1e-9)
        if i == 0:
            edges = [w0] + [t for iv in union for t in iv] + [w1]
            first_idle = [(edges[k], edges[k + 1])
                          for k in range(0, len(edges), 2)
                          if edges[k + 1] > edges[k]]
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]

    def host_at(t: float) -> str:
        around = [(e - s, n) for n, s, e in inner if s <= t <= e]
        return min(around)[1] if around else "host.outside_spans"

    gaps = sorted(first_idle, key=lambda g: g[0] - g[1])[:top]
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    busy = [b for b in busy if b > 0]
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": (w1 - w0) * 1e-9,
        "device_ops": [[n, t] for n, t in by_time(op_time)],
        "programs": [[n, t] for n, t in by_time(prog_time)],
        "idle_gaps": [[host_at((s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps],
    }


def reduce_dir(trace_dir: Path) -> Optional[Dict]:
    return reduce_trace(*read_planes(newest_xplane(trace_dir)))
