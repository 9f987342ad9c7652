#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `BENCHMARK.json`'s `workloads`.  Set-up (weights
and images from the seed, compile or cache load of every shape the cell
uses) is timed as `setup_s`; then the cell's runner measures for
`--seconds` and checks what the measured path produced against the plain
reference.  With `--trace 0` the result line carries the cell's end-to-end
metrics; with `--trace 1` a profiler trace covers the window and the line
carries the per-layer metrics, the device's busy time and a breakdown.

The last lines on standard error give each number compared beside its
limit; the last line on standard output is one JSON object.  On anything
but a TPU, or with fewer chips than the cell asks for, the run exits 3
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402
import tracereduce  # noqa: E402


class Run:
    """What a runner gets: the cell's files and arguments, the set-up and
    window clocks, the traced window, and the correctness checks."""

    def __init__(self, resolved, args, device):
        self.cell = resolved["cell"]
        self.conf = resolved["conf"]
        self.traffic = resolved["traffic"]
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.device = device
        self.setup_s = None
        self.window_s = None
        self.memory_peak_bytes = None
        self.checks = []          # (name, value, limit)
        self.counters = {}
        self.trace_dir = harness.OUT_DIR / "trace" / self.cell["name"]

    def setup_done(self) -> None:
        """Set-up ends here: process start to the first timed call."""
        self.setup_s = time.perf_counter() - T_START

    @contextlib.contextmanager
    def window(self):
        """The measured window, traced when the run asks for it."""
        import jax
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir))
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            self.window_s = time.perf_counter() - t0
            if self.trace:
                jax.profiler.stop_trace()

    def read_memory(self) -> None:
        """Peak device memory, read once the window has closed and before
        the reference runs."""
        self.memory_peak_bytes = harness.memory_peak_bytes()

    def check(self, name: str, value: float, limit: float) -> None:
        """A number compared: the run is correct only if value <= limit."""
        self.checks.append((name, float(value), float(limit)))


def per_layer(spec, run, trace_summary):
    """The per-layer metrics this cell reports that find something to
    read; a reader that finds nothing returns None and is left out."""
    view = {"counters": run.counters, "trace": trace_summary,
            "conf": run.conf, "traffic": run.traffic,
            "peaks": harness.load_json(harness.BENCH / "peaks.json"),
            "device_kind": run.device["kind"]}
    out = {}
    for m in harness.metrics_for(spec, run.cell["name"], "per_layer"):
        reader = harness.load_module(harness.BENCH / "metrics"
                                     / f"{m['name']}.py")
        value = reader.read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, spec=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = spec or harness.load_json(harness.SPEC_FILE)
    resolved = harness.resolve(spec, args.workload)
    try:
        device = harness.device_check(resolved["cell"]["chips"])
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.import_program()
    harness.enable_compile_cache()
    run = Run(resolved, args, device)
    outcome = harness.load_module(resolved["runner"]).run(run)
    run.check("failed", outcome["failed"], 0)

    result = {"correct": None, "attempted": outcome["attempted"],
              "failed": outcome["failed"]}
    if args.trace:
        summary = tracereduce.reduce_dir(run.trace_dir)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        metrics = per_layer(spec, run, summary)
        device = dict(device, busy_s=summary["busy_s"] if summary else 0.0,
                      window_s=summary["window_s"] if summary
                      else run.window_s)
    else:
        summary = None
        e2e = dict(outcome["e2e"], setup_s=run.setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in harness.metrics_for(spec, args.workload,
                                                "end_to_end")}
    device["memory_peak_bytes"] = run.memory_peak_bytes
    ok = bool(run.checks) and all(v <= lim for _, v, lim in run.checks)
    result["correct"] = ok
    result["metrics"] = metrics
    result["device"] = device
    if summary:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in run.checks}
    for n, v, lim in run.checks:
        print(f"check {n} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
