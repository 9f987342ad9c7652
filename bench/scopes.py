#!/usr/bin/env python3
"""Device time by the program's named scopes, its host spans, and the
host-device clock offset, from one profiler trace.

    python3 bench/scopes.py <trace directory>

prints the table for the newest `.xplane.pb` under the directory: a
`--trace` run directory of `repro.launch.mc` or `repro.launch.serve`
(`<run-dir>/<run-id>/trace`), or a benchmark window's trace.  A trace
without a `bench.window` span is read over its whole length.

`reduce_dir` returns what `tracereduce.reduce_trace` returns for the same
trace, with these keys more:

  scopes          {"seconds": device seconds by scope, summed over chips,
                   "unscoped_raw_s": seconds of ops that carry no scope}.
                   An XLA op's scope is the outermost recognised component
                   (`SCOPE`) of its `tf_op` path, with the `jvp(...)` and
                   `transpose(...)` wrappers of the backward pass stripped:
                   `sample/s0b0/...` is `sample`, `transpose(jvp(s1b0))/...`
                   is `s1b0`.  An op with no `tf_op` (a copy the compiler
                   inserted) counts toward the next scoped op of the same
                   program execution, or the last one where none follows;
                   what no recognised scope takes is `other`.
  spans           {name: {"count", "seconds"}} of the `bench.*` and
                   `repro.*` host spans inside the window, clipped to it
  clock_offset_s  [low, high] seconds: what to add to a device time to put
                   it on the host clock, over the whole window.  A program
                   cannot start on the device before the host began to
                   enqueue it (`DoEnqueueProgram`), nor end after the host
                   began its completion callbacks (`CompleteCallbacks`);
                   both host events carry the execution's `run_id`, so
                   each execution bounds the offset from both sides.
                   [0, 0] where the trace has no such pair.
  idle_by_span    {name: seconds}: every idle gap of the first chip in
                   the window, by the span that names it (below)
  clock_segments  [[from, low, high], ...] seconds, `from` counted from
                   the window's start: where the bounds of successive
                   executions leave no common offset, the profiler's clock
                   alignment has stepped, and a new segment begins.
                   `clock_offset_s` spans the segments' bounds.

The busy and window seconds and the op and program totals are
`reduce_trace`'s own.  Its idle gaps are named by the innermost `bench.*`
or `repro.*` span around each gap's middle, moved onto the host clock by
the middle of its segment's offset.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tracereduce  # noqa: E402
import xspace  # noqa: E402

SCOPE = re.compile(r"^(sample|stem|head|train_planes|loss|adamw"
                   r"|s\d+b\d+|s\d+pool)$")
SPAN_PREFIXES = ("bench.", "repro.")
OTHER = "other"
_WRAPPER = re.compile(r"^\w*\((.*)\)$")


def scope_of(tf_op: str) -> Optional[str]:
    """The outermost recognised scope of an op's `tf_op` path, or None."""
    path = tf_op.rsplit(":", 1)[0]
    for part in path.split("/"):
        while (m := _WRAPPER.match(part)):
            part = m.group(1)
        if SCOPE.match(part):
            return part
    return None


@dataclasses.dataclass
class Trace:
    """What the reduction reads of a trace.  Times in host or device
    nanoseconds as the trace records them."""
    spans: List[Tuple[str, float, float]]        # bench.* and repro.*
    devices: Dict[str, List[Tuple[str, float, float, str]]]  # as read_planes
    tf_ops: Dict[str, List[Optional[str]]]       # each op's tf_op, or None
    modules: Dict[str, List[Tuple[float, float, int]]]  # start, end, run_id
    enqueued: Dict[Tuple[int, int], float]       # (ordinal, run_id) -> start
    completed: Dict[Tuple[int, int], float]      # (ordinal, run_id) -> start


def _ordinal(device: str) -> int:
    tail = device.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else 0


def read_trace(xplane: Path) -> Trace:
    """What the reduction reads of one `.xplane.pb` file."""
    spans, devices, tf_ops, modules = [], {}, {}, {}
    enqueued, completed = {}, {}
    for plane in xspace.read(xplane):
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
                    elif ev.name in ("DoEnqueueProgram", "CompleteCallbacks") \
                            and "run_id" in ev.stats:
                        into = enqueued if ev.name == "DoEnqueueProgram" \
                            else completed
                        key = (ev.stats.get("device_ordinal", 0),
                               ev.stats["run_id"])
                        into[key] = min(into.get(key, ev.start_ns),
                                        ev.start_ns)
        elif tracereduce._is_device(plane.name):
            lines = {line.name: line.events for line in plane.lines}
            mods = sorted((ev.start_ns, ev.end_ns, ev.name)
                          for ev in lines.get("XLA Modules", []))
            ops = lines.get("XLA Ops", [])
            if not ops:
                continue
            names = {n: tracereduce.op_name(n) for n in {ev.name for ev in ops}}
            devices[plane.name] = [
                (names[ev.name], ev.start_ns, ev.end_ns,
                 tracereduce._containing(mods, ev.start_ns)) for ev in ops]
            tf_ops[plane.name] = [ev.metadata.get("tf_op") or None
                                  for ev in ops]
            modules[plane.name] = sorted(
                (ev.start_ns, ev.end_ns, ev.stats.get("run_id", -1))
                for ev in lines.get("XLA Modules", []))
    return Trace(spans, devices, tf_ops, modules, enqueued, completed)


def clock_segments(trace: Trace) -> List[Tuple[float, float, float]]:
    """(from, low, high) nanoseconds: from device time `from` on, add
    between `low` and `high` to a device time to put it on the host clock.

    Each execution with both host events bounds the offset: `low` by its
    enqueue (the program cannot start before it), `high` by its
    completion callbacks (they cannot begin before it ends).  The bounds
    are intersected in order of start, and where the next execution's
    bounds leave no offset, the profiler's clock alignment has stepped,
    and a new segment starts there.  One segment of (0, 0) where no
    execution has both host events."""
    execs = []
    for dev, mods in trace.modules.items():
        for start, end, run_id in mods:
            key = (_ordinal(dev), run_id)
            if key in trace.enqueued and key in trace.completed:
                low = trace.enqueued[key] - start
                high = trace.completed[key] - end
                if low <= high:
                    execs.append((start, low, high))
    segments: List[List[float]] = []
    for start, low, high in sorted(execs):
        if segments and max(segments[-1][1], low) <= min(segments[-1][2],
                                                         high):
            segments[-1][1] = max(segments[-1][1], low)
            segments[-1][2] = min(segments[-1][2], high)
        else:
            segments.append([start, low, high])
    return [tuple(seg) for seg in segments] or [(0.0, 0.0, 0.0)]


def scope_seconds(trace: Trace, w0: float, w1: float
                  ) -> Tuple[Dict[str, float], float]:
    """Device seconds by scope inside [w0, w1] (device clock, as the op
    totals are clipped), and the raw seconds of ops with no `tf_op`."""
    totals: Dict[str, float] = collections.defaultdict(float)
    raw = 0.0
    scope = {}                     # tf_op -> scope: the paths repeat
    for dev, ops in trace.devices.items():
        starts = [m[0] for m in trace.modules.get(dev, [])]
        pending: Dict[int, float] = collections.defaultdict(float)
        last: Dict[int, str] = {}
        for k in sorted(range(len(ops)), key=lambda k: ops[k][1]):
            _, s, e, _ = ops[k]
            dt = (min(e, w1) - max(s, w0)) * 1e-9
            if dt <= 0:
                continue
            run = bisect.bisect_right(starts, s) - 1
            tf_op = trace.tf_ops[dev][k]
            if tf_op is None:
                raw += dt
                pending[run] += dt
                continue
            if tf_op not in scope:
                scope[tf_op] = scope_of(tf_op)
            if scope[tf_op] is None:
                totals[OTHER] += dt
                continue
            totals[scope[tf_op]] += dt + pending.pop(run, 0.0)
            last[run] = scope[tf_op]
        for run, dt in pending.items():
            totals[last.get(run, OTHER)] += dt
    return dict(totals), raw


def span_seconds(spans, w0: float, w1: float) -> Dict[str, Dict]:
    """Count and host seconds of each span name inside [w0, w1]."""
    out: Dict[str, Dict] = {}
    for name, s, e in spans:
        if name == tracereduce.WINDOW_SPAN or e < w0 or s > w1:
            continue
        t = out.setdefault(name, {"count": 0, "seconds": 0.0})
        t["count"] += 1
        t["seconds"] += (min(e, w1) - max(s, w0)) * 1e-9
    return out


def reduce(trace: Trace, top: int = 10) -> Optional[Dict]:
    spans = list(trace.spans)
    if not any(n == tracereduce.WINDOW_SPAN for n, _, _ in spans):
        ends = [t for n, s, e in spans for t in (s, e)] + [
            t for ops in trace.devices.values() for _, s, e, _ in ops
            for t in (s, e)]
        if not ends:
            return None
        spans.append((tracereduce.WINDOW_SPAN, min(ends), max(ends)))
    segments = clock_segments(trace)
    starts = [seg[0] for seg in segments]

    def to_device(t: float) -> float:
        """A host time on the device clock, by its segment's middle."""
        _, low, high = segments[max(bisect.bisect_right(starts, t) - 1, 0)]
        return t - (low + high) / 2

    # the gaps are found on the device clock: move the inner spans there
    on_device = [(n, s, e) if n == tracereduce.WINDOW_SPAN
                 else (n, to_device(s), to_device(e)) for n, s, e in spans]
    full = tracereduce.reduce_trace(on_device, trace.devices, sys.maxsize)
    if full is None:
        return None
    summary = {k: v[:top] if isinstance(v, list) else v
               for k, v in full.items()}
    w0, w1 = [(s, e) for n, s, e in spans
              if n == tracereduce.WINDOW_SPAN][-1]
    seconds, raw = scope_seconds(trace, w0, w1)
    summary["scopes"] = {"seconds": seconds, "unscoped_raw_s": raw}
    summary["spans"] = span_seconds(spans, w0, w1)
    idle: Dict[str, float] = {}
    for name, seconds in full["idle_gaps"]:
        idle[name] = idle.get(name, 0.0) + seconds
    summary["idle_by_span"] = idle
    summary["clock_offset_s"] = [min(seg[1] for seg in segments) * 1e-9,
                                 max(seg[2] for seg in segments) * 1e-9]
    summary["clock_segments"] = [[(t - w0) * 1e-9, low * 1e-9, high * 1e-9]
                                 for t, low, high in segments]
    return summary


def reduce_dir(trace_dir: Path) -> Optional[Dict]:
    return reduce(read_trace(tracereduce.newest_xplane(trace_dir)))


def report(summary: Dict, out=sys.stderr) -> None:
    """The per-scope table, the raw unscoped share, the offset interval,
    the spans and the named idle gaps, as text."""
    seconds = summary["scopes"]["seconds"]
    total = sum(seconds.values())
    covered = total - seconds.get(OTHER, 0.0)
    print(f"busy {summary['busy_s']:.6f} s of a {summary['window_s']:.6f} s"
          " window", file=out)
    print(f"{'scope':<14}{'device s':>14}{'share %':>10}", file=out)
    for name, t in sorted(seconds.items(), key=lambda kv: -kv[1]):
        print(f"{name:<14}{t:>14.6f}{100 * t / total:>10.2f}", file=out)
    raw = summary["scopes"]["unscoped_raw_s"]
    print(f"scoped after attribution {100 * covered / max(total, 1e-30):.2f}"
          f"% of op time; ops with no tf_op (raw) "
          f"{100 * raw / max(total, 1e-30):.2f}%", file=out)
    lo, hi = summary["clock_offset_s"]
    print(f"clock offset (device -> host) [{lo * 1e3:.4f}, {hi * 1e3:.4f}]"
          " ms, by segment:", ", ".join(
              f"from {t:.3f} s [{a * 1e3:.4f}, {b * 1e3:.4f}]"
              for t, a, b in summary["clock_segments"]), file=out)
    for name, t in sorted(summary["spans"].items()):
        print(f"span {name:<28}{t['count']:>7}{t['seconds']:>14.6f} s",
              file=out)
    for name, t in sorted(summary["idle_by_span"].items(),
                          key=lambda kv: -kv[1]):
        print(f"idle {t:10.6f} s in all gaps named {name}", file=out)
    for name, t in summary["idle_gaps"]:
        print(f"idle gap {t * 1e3:10.3f} ms in {name}", file=out)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__.split("\n\n")[1])
    result = reduce_dir(Path(sys.argv[1]))
    if result is None:
        sys.exit("no device op in the trace")
    report(result, sys.stdout)
