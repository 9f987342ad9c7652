"""Phase timers that split first-call (trace + compile + execute) latency
from steady-state throughput.

A single `wall_s` over a jitted loop conflates XLA compilation with the
steady state the system actually operates in — at small workloads the
compile dominates and every derived rate (chips/sec, steps/sec, tokens/sec)
is misleading.  `PhaseTimer` counts the FIRST lap separately (`compile_s`;
strictly it is first-call latency — on a warm jit cache it contains no
compilation, which is itself worth seeing) and derives rates from the
remaining laps only, falling back to the total when a phase ran one lap.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional


class _Lap:
    """Mutable handle yielded by `PhaseTimer.lap()`: set `.items` inside the
    block when the work amount is only known after it ran (e.g. tokens
    decoded until EOS)."""

    def __init__(self, items: float):
        self.items = items


class PhaseTimer:
    """Accumulates laps of one phase; first lap is the compile/warmup lap."""

    def __init__(self, phase: str, unit: str = "items"):
        self.phase = phase
        self.unit = unit
        self.compile_s = 0.0        # first-lap wall (includes jit compile)
        self.compile_items = 0.0
        self.steady_s = 0.0         # laps 2..n wall
        self.steady_items = 0.0
        self.laps = 0
        self.last_s = 0.0

    @contextlib.contextmanager
    def lap(self, items: float = 0.0, span: Optional[str] = None):
        """Time one lap; with `span`, the lap is also a host span of that
        name in a running profiler trace (`jax.profiler.TraceAnnotation`),
        so the trace and the timer measure the same interval."""
        if span is None:
            annotation = contextlib.nullcontext()
        else:
            from jax.profiler import TraceAnnotation
            annotation = TraceAnnotation(span)
        t0 = time.perf_counter()
        handle = _Lap(items)
        try:
            with annotation:
                yield handle
        finally:
            dt = time.perf_counter() - t0
            self.last_s = dt
            if self.laps == 0:
                self.compile_s += dt
                self.compile_items += handle.items
            else:
                self.steady_s += dt
                self.steady_items += handle.items
            self.laps += 1

    @property
    def total_s(self) -> float:
        return self.compile_s + self.steady_s

    @property
    def total_items(self) -> float:
        return self.compile_items + self.steady_items

    def rate(self) -> float:
        """Steady-state `unit`/sec (laps after the first); single-lap phases
        fall back to the total — the honest number when nothing amortized."""
        if self.laps >= 2 and self.steady_items > 0:
            return self.steady_items / max(self.steady_s, 1e-9)
        return self.total_items / max(self.total_s, 1e-9)

    def summary(self) -> Dict[str, float]:
        return {
            "phase": self.phase,
            "laps": self.laps,
            "compile_s": self.compile_s,
            "steady_s": self.steady_s,
            "total_s": self.total_s,
            self.unit: self.total_items,
            f"{self.unit}_per_sec": self.rate(),
        }

    def log_to(self, runlog, **extra) -> None:
        """Emit a `phase` event through a RunLog (no-op on NullRunLog)."""
        runlog.log_event("phase", **self.summary(), **extra)


class LatencyTracker:
    """Per-item latency accumulator with percentile summaries.

    The serving engine records one submit→response latency per request;
    `summary()` reports count/mean and the p50/p95/p99 the queue-latency
    benchmark rows and `serve_wave` RunLog events carry.  Values are kept
    raw (a float per item) — exact percentiles, same philosophy as
    `StreamingMoments`' exact quantiles."""

    def __init__(self, unit: str = "s"):
        self.unit = unit
        self._values: list = []

    def add(self, seconds: float) -> None:
        """Record one item's latency."""
        self._values.append(float(seconds))

    @property
    def count(self) -> int:
        """Number of recorded latencies."""
        return len(self._values)

    def summary(self) -> Dict[str, float]:
        """count/mean/p50/p95/p99 over everything recorded so far."""
        import numpy as np
        if not self._values:
            return {"count": 0.0}
        v = np.asarray(self._values, np.float64)
        return {"count": float(v.size), "mean": float(v.mean()),
                "p50": float(np.percentile(v, 50)),
                "p95": float(np.percentile(v, 95)),
                "p99": float(np.percentile(v, 99))}


def timed_step(step_fn, timer: PhaseTimer, block_on=None):
    """Wrap a jitted step so every call is one timer lap (first call =
    compile lap).  `block_on(result)` selects what to block_until_ready on;
    defaults to the whole result tree."""
    import jax

    def wrapped(*args, **kwargs):
        with timer.lap(items=1):
            out = step_fn(*args, **kwargs)
            jax.block_until_ready(out if block_on is None else block_on(out))
        return out

    return wrapped


def maybe_runlog(enabled: bool, name: str, *, args=None, root: str =
                 "experiments", run_id: Optional[str] = None):
    """`RunLog.create` when enabled, else the no-op singleton — the common
    CLI pattern behind `--run-dir`."""
    from repro.obs.runlog import NULL_RUNLOG, RunLog
    if not enabled:
        return NULL_RUNLOG
    return RunLog.create(name, args=args, root=root, run_id=run_id)
