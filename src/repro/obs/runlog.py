"""Run manifests + append-only metric event streams (the `repro.obs` core).

Every instrumented entry point — `launch.mc`, `launch.train`,
`examples/train_detector.py`, `benchmarks/mc_bench.py`, the serving engine —
speaks this one telemetry format.  A run is a directory:

  experiments/<run_id>/
    manifest.json     provenance: argv/args, git SHA, jax/jaxlib versions,
                      host, platform, device kind and count, timestamps
    metrics.jsonl     append-only event stream; one JSON object per line,
                      each with a monotonic `t` (seconds since run start)
                      and a `kind` ("chunk", "convergence", "phase", ...)
    *.npy             arrays persisted via `save_array` (per-chip metric
                      vectors from `McResult.per_chip`)
    trace/            optional `jax.profiler` trace (`--trace`)

`metrics.jsonl` is the run's evidence, not just its log: per-chunk events
carry the raw per-chip metric values, so replaying the stream through the
same Welford accumulators reproduces the reported population mean±std
bit-for-bit (tests/test_obs.py pins this).

`NullRunLog` (singleton `NULL_RUNLOG`, via `as_runlog(None)`) is the no-op
twin, so library code instruments unconditionally and pays nothing when no
run directory was requested.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import uuid
from pathlib import Path
from typing import Any, Dict, Optional


def git_sha() -> Optional[str]:
    """HEAD SHA of the source tree this module runs from (None outside git)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=Path(__file__).resolve().parent,
            capture_output=True, text=True, timeout=10)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except Exception:
        return None


def collect_env() -> Dict[str, Any]:
    """Host / toolchain / device metadata: what makes machine-relative
    numbers interpretable across machines (also merged into BENCH_mc.json).

    `platform`, `device_kind` and `device_count` are what JAX reports for
    the devices the run used; a JAX failure here propagates, so no run can
    record a device it did not see."""
    import platform
    import socket

    import jax
    import jaxlib
    dev = jax.devices()[0]
    return {
        "host": socket.gethostname(),
        "os": platform.platform(),
        "python": platform.python_version(),
        "cpu_count": os.cpu_count(),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
    }


def _jsonable(v):
    """numpy scalars/arrays and jax arrays -> plain python for json.dumps."""
    if hasattr(v, "tolist"):
        return v.tolist()
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, (str, int, bool)) or v is None:
        return v
    if isinstance(v, float):
        return v
    try:
        return float(v)
    except (TypeError, ValueError):
        return repr(v)


class RunLog:
    """Writer for one `experiments/<run_id>/` run directory."""

    def __init__(self, run_dir: Path, manifest: Dict[str, Any]):
        self.path = Path(run_dir)
        self.path.mkdir(parents=True, exist_ok=True)
        self.manifest = manifest
        self._t0 = time.perf_counter()
        self._events = self.path / "metrics.jsonl"
        self._tracing = False
        self._write_manifest()

    # ------------------------------------------------------------- creation

    @classmethod
    def create(cls, name: str, *, args: Optional[Dict[str, Any]] = None,
               root: str = "experiments",
               run_id: Optional[str] = None) -> "RunLog":
        """Create `root/<run_id>/` and write its manifest.

        `run_id` defaults to `<utc-timestamp>-<name>-<6 hex>` — sortable,
        collision-free across concurrent runs on one host.
        """
        run_id = run_id or (time.strftime("%Y%m%d-%H%M%S", time.gmtime())
                            + f"-{name}-{uuid.uuid4().hex[:6]}")
        manifest = {
            "run_id": run_id,
            "name": name,
            "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "argv": list(sys.argv),
            "args": _jsonable(args) if args is not None else None,
            "git_sha": git_sha(),
            "env": collect_env(),
            "status": "running",
        }
        return cls(Path(root) / run_id, manifest)

    def _write_manifest(self) -> None:
        (self.path / "manifest.json").write_text(
            json.dumps(self.manifest, indent=1, default=_jsonable))

    # --------------------------------------------------------------- events

    def log_event(self, kind: str, **fields) -> None:
        """Append one event line to metrics.jsonl."""
        ev = {"t": round(time.perf_counter() - self._t0, 6), "kind": kind}
        ev.update({k: _jsonable(v) for k, v in fields.items()})
        with self._events.open("a") as f:
            f.write(json.dumps(ev) + "\n")

    # ------------------------------------------------------------ artifacts

    def save_array(self, name: str, arr) -> Path:
        """Persist an array as `<name>.npy` under the run dir."""
        import numpy as np
        out = self.path / f"{name}.npy"
        out.parent.mkdir(parents=True, exist_ok=True)
        np.save(out, np.asarray(arr))
        return out

    def save_result(self, label: str, metrics: Dict[str, Dict[str, float]],
                    per_chip: Optional[Dict[str, Any]] = None,
                    **fields) -> None:
        """One sweep's summary event + its per-chip metric vectors as .npy."""
        self.log_event("result", label=label, metrics=metrics, **fields)
        for name, vec in (per_chip or {}).items():
            self.save_array(f"per_chip_{name}_{label}", vec)

    def write_text(self, name: str, text: str) -> Path:
        out = self.path / name
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(text)
        return out

    # -------------------------------------------------------------- tracing

    def start_trace(self) -> bool:
        """Capture a `jax.profiler` trace into `<run_dir>/trace/`.  A
        profiler failure raises: a run that asked for a trace never ends
        without one."""
        import jax
        jax.profiler.start_trace(str(self.path / "trace"))
        self._tracing = True
        return True

    def stop_trace(self) -> None:
        """Stop the trace started by `start_trace` (raises on failure)."""
        if not self._tracing:
            return
        import jax
        self._tracing = False
        jax.profiler.stop_trace()

    # ------------------------------------------------------------- finalize

    def finalize(self, status: str = "ok", **summary) -> None:
        self.stop_trace()
        self.manifest["status"] = status
        self.manifest["wall_s"] = round(time.perf_counter() - self._t0, 6)
        if summary:
            self.manifest["summary"] = _jsonable(summary)
        self._write_manifest()


class NullRunLog(RunLog):
    """No-op RunLog: library code logs unconditionally, callers that didn't
    ask for a run directory pay nothing and write nothing."""

    def __init__(self):          # noqa: super().__init__ deliberately skipped
        self.path = None
        self.manifest = {}
        self._tracing = False

    def log_event(self, kind: str, **fields) -> None:
        pass

    def save_array(self, name: str, arr):
        return None

    def save_result(self, label, metrics, per_chip=None, **fields) -> None:
        pass

    def write_text(self, name: str, text: str):
        return None

    def start_trace(self) -> bool:
        return False

    def stop_trace(self) -> None:
        pass

    def finalize(self, status: str = "ok", **summary) -> None:
        pass


NULL_RUNLOG = NullRunLog()


def as_runlog(obs: Optional[RunLog]) -> RunLog:
    """None -> the no-op singleton; a RunLog passes through."""
    return NULL_RUNLOG if obs is None else obs
