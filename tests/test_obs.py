"""Observability layer (repro.obs): run manifests, phase timers, convergence
telemetry — and the two contracts the layer exists for:

  * replaying a run's `metrics.jsonl` chunk events through fresh
    StreamingMoments reproduces the reported population mean±std
    BIT-FOR-BIT (the event stream is evidence, not just a log), and
  * a `stderr_target` early-stopped sweep returns exactly the same moments
    as the same-length PREFIX of the full run (chips are keyed by id, so
    adaptivity is statistically invisible).
"""
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.mc import McConfig, StreamingMoments, run_mc
from repro.obs import (NULL_RUNLOG, ConvergenceMonitor, NullRunLog, PhaseTimer,
                       RunLog, as_runlog, collect_env, maybe_runlog,
                       timed_step)

from test_mc import _layer


# ---------------------------------------------------------------- RunLog


class TestRunLog:
    def test_manifest_events_arrays_roundtrip(self, tmp_path):
        rl = RunLog.create("unit", args={"chips": 4, "arr": jnp.arange(2)},
                           root=str(tmp_path), run_id="r1")
        assert rl.path == tmp_path / "r1"
        man = json.loads((rl.path / "manifest.json").read_text())
        assert man["run_id"] == "r1" and man["status"] == "running"
        assert man["args"]["chips"] == 4
        assert man["args"]["arr"] == [0, 1]          # jax array -> jsonable
        assert man["env"]["jax"] == jax.__version__
        assert man["env"]["backend"] == jax.default_backend()

        rl.log_event("chunk", chips=2, values={"m": np.float32(0.5)})
        rl.log_event("phase", laps=3)
        evs = [json.loads(line) for line in
               (rl.path / "metrics.jsonl").read_text().splitlines()]
        assert [e["kind"] for e in evs] == ["chunk", "phase"]
        assert evs[0]["values"]["m"] == 0.5
        assert evs[0]["t"] >= 0.0

        p = rl.save_array("per_chip_m", jnp.asarray([1.0, 2.0]))
        np.testing.assert_array_equal(np.load(p), [1.0, 2.0])

        rl.finalize(status="ok", best=1.5)
        man = json.loads((rl.path / "manifest.json").read_text())
        assert man["status"] == "ok" and man["summary"]["best"] == 1.5
        assert man["wall_s"] >= 0.0

    def test_default_run_id_unique_and_named(self, tmp_path):
        a = RunLog.create("mc", root=str(tmp_path))
        b = RunLog.create("mc", root=str(tmp_path))
        assert a.path != b.path
        assert "-mc-" in a.path.name

    def test_null_runlog_is_silent(self, tmp_path):
        null = as_runlog(None)
        assert null is NULL_RUNLOG and isinstance(null, NullRunLog)
        assert null.path is None
        null.log_event("chunk", chips=2)
        assert null.save_array("x", np.zeros(2)) is None
        assert null.write_text("a.csv", "x") is None
        assert null.start_trace() is False
        null.finalize(status="ok")
        assert list(tmp_path.iterdir()) == []
        assert as_runlog(NULL_RUNLOG) is NULL_RUNLOG

    def test_maybe_runlog(self, tmp_path):
        assert maybe_runlog(False, "x") is NULL_RUNLOG
        rl = maybe_runlog(True, "x", root=str(tmp_path), run_id="y")
        assert rl.path == tmp_path / "y"

    def test_collect_env_has_toolchain(self):
        env = collect_env()
        for k in ("host", "python", "cpu_count", "jax", "jaxlib", "backend",
                  "platform", "device_kind", "device_count"):
            assert k in env
        dev = jax.devices()[0]
        assert env["platform"] == dev.platform
        assert env["device_kind"] == dev.device_kind
        assert env["device_count"] == jax.device_count()

    def test_collect_env_propagates_jax_errors(self, monkeypatch):
        def broken():
            raise RuntimeError("no backend")
        monkeypatch.setattr(jax, "devices", broken)
        with pytest.raises(RuntimeError, match="no backend"):
            collect_env()

    def test_trace_failure_raises(self, tmp_path, monkeypatch):
        """A run that asked for a trace must not end without one."""
        rl = RunLog.create("t", root=str(tmp_path), run_id="r")

        def broken(*_a, **_k):
            raise RuntimeError("profiler unavailable")
        monkeypatch.setattr(jax.profiler, "start_trace", broken)
        with pytest.raises(RuntimeError, match="profiler unavailable"):
            rl.start_trace()
        assert not rl._tracing


# ------------------------------------------------------------- PhaseTimer


class TestPhaseTimer:
    def test_first_lap_is_compile_rest_steady(self):
        t = PhaseTimer("p", unit="chips")
        for items in (4, 4, 4):
            with t.lap(items=items):
                pass
        assert t.laps == 3
        assert t.compile_items == 4 and t.steady_items == 8
        assert t.total_s == t.compile_s + t.steady_s
        # steady rate excludes the first lap entirely
        assert t.rate() == 8 / max(t.steady_s, 1e-9)

    def test_single_lap_falls_back_to_total(self):
        t = PhaseTimer("p")
        with t.lap(items=5):
            pass
        assert t.rate() == 5 / max(t.total_s, 1e-9)

    def test_lap_items_settable_inside_block(self):
        t = PhaseTimer("p", unit="tokens")
        with t.lap() as lap:
            lap.items = 17          # only known after the work ran
        assert t.compile_items == 17

    def test_summary_and_log_to(self, tmp_path):
        t = PhaseTimer("decode", unit="tokens")
        with t.lap(items=2):
            pass
        s = t.summary()
        assert s["phase"] == "decode" and s["tokens"] == 2
        rl = RunLog.create("u", root=str(tmp_path), run_id="r")
        t.log_to(rl, extra_field=1)
        ev = json.loads((rl.path / "metrics.jsonl").read_text())
        assert ev["kind"] == "phase" and ev["extra_field"] == 1

    def test_timed_step_wraps_jitted_fn(self):
        t = PhaseTimer("step", unit="steps")
        f = timed_step(jax.jit(lambda x: x * 2), t)
        for i in range(3):
            out = f(jnp.float32(i))
            assert float(out) == 2.0 * i
        assert t.laps == 3 and t.steady_items == 2


# ---------------------------------------------------- ConvergenceMonitor


class TestConvergenceMonitor:
    def test_unknown_metric_rejected(self):
        with pytest.raises(ValueError, match="not a tracked metric"):
            ConvergenceMonitor({"a": StreamingMoments()}, stderr_target=0.1,
                               stderr_metric="b")

    def test_no_target_never_converges_but_logs(self, tmp_path):
        sm = StreamingMoments()
        sm.update(jnp.asarray([0.1, 0.2, 0.3]))
        rl = RunLog.create("u", root=str(tmp_path), run_id="r")
        mon = ConvergenceMonitor({"m": sm}, runlog=rl)
        assert mon.after_chunk(0, 3) is False
        ev = json.loads((rl.path / "metrics.jsonl").read_text())
        assert ev["kind"] == "convergence"
        assert ev["metrics"]["m"]["count"] == 3.0
        assert math.isclose(ev["metrics"]["m"]["stderr"], sm.stderr())

    def test_gating_all_vs_single_metric(self):
        tight = StreamingMoments()
        tight.update(jnp.full((8,), 0.5))             # zero spread
        wide = StreamingMoments()
        wide.update(jnp.asarray([0.0, 1.0, 0.0, 1.0]))
        both = {"tight": tight, "wide": wide}
        assert ConvergenceMonitor(both, stderr_target=0.01).converged() \
            is False                                  # wide blocks ALL-gate
        assert ConvergenceMonitor(both, stderr_target=0.01,
                                  stderr_metric="tight").converged() is True


# ------------------------------------------------------- engine telemetry


def _tiny_run(tmp_path, run_id, **kw):
    from repro.core import ideal_ternary_matmul
    w, mapped, x = _layer(fan_in=64, n_out=16, batch=8, bias_rows=8)
    ref = (ideal_ternary_matmul(x, w) > 0).astype(jnp.float32)
    rl = RunLog.create("mc", root=str(tmp_path), run_id=run_id)
    res = run_mc(jax.random.PRNGKey(42), mapped, x, ref_bits=ref,
                 mc=McConfig(n_chips=8, chunk_size=2), obs=rl, **kw)
    return rl, res


class TestRunMcTelemetry:
    def test_run_emits_events_and_split_timing(self, tmp_path):
        rl, res = _tiny_run(tmp_path, "r1")
        evs = [json.loads(line) for line in
               (rl.path / "metrics.jsonl").read_text().splitlines()]
        kinds = [e["kind"] for e in evs]
        assert kinds[0] == "mc_start" and kinds[-1] == "mc_result"
        assert kinds.count("chunk") == 4 and kinds.count("convergence") == 4
        assert res.n_chips == 8
        assert res.compile_s > 0.0
        assert res.wall_s >= res.compile_s
        assert evs[-1]["compile_s"] == res.compile_s
        assert "steady" in res.summary_line()

    def test_jsonl_replay_reproduces_moments_bitwise(self, tmp_path):
        """The acceptance contract: per-chunk events carry the raw float32
        per-chip values; JSON round-trips them exactly, so refolding the
        stream through fresh StreamingMoments in file order reproduces the
        reported mean/std/quantiles BIT-FOR-BIT (dict equality, no atol)."""
        rl, res = _tiny_run(tmp_path, "r2")
        chunk_evs = [e for e in map(json.loads,
                     (rl.path / "metrics.jsonl").read_text().splitlines())
                     if e["kind"] == "chunk"]
        replay = {name: StreamingMoments()
                  for name in chunk_evs[0]["values"]}
        for ev in chunk_evs:
            for name, vals in ev["values"].items():
                replay[name].update(jnp.asarray(np.asarray(vals, np.float32)))
        assert set(replay) == set(res.metrics)
        for name, sm in replay.items():
            assert sm.summary() == res.metrics[name]
            np.testing.assert_array_equal(sm.per_chip, res.per_chip[name])

    def test_early_stop_equals_full_run_prefix(self, tmp_path):
        """The acceptance contract for adaptivity: with a stderr target the
        sweep stops at a chunk boundary, and its moments/per-chip values are
        EXACTLY the same-length prefix of the full run (chips keyed by id)."""
        _, full = _tiny_run(tmp_path, "full")
        chunk = 2
        vals = full.per_chip["bit_agreement"]

        def prefix_moments(name, n):
            sm = StreamingMoments()
            for lo in range(0, n, chunk):
                sm.update(jnp.asarray(full.per_chip[name][lo:lo + chunk]))
            return sm

        # pick the stderr reached after 2 chunks; the engine must stop at
        # the FIRST chunk boundary at/under it (possibly chunk 1)
        target = prefix_moments("bit_agreement", 4).stderr()
        stop_chunks = next(i for i in range(1, 5)
                           if prefix_moments("bit_agreement",
                                             i * chunk).stderr() <= target)

        rl, early = _tiny_run(tmp_path, "early", stderr_target=target,
                              stderr_metric="bit_agreement")
        assert early.n_chips == stop_chunks * chunk
        assert early.n_chips < full.n_chips
        for name in full.metrics:
            sm = prefix_moments(name, early.n_chips)
            assert early.metrics[name] == sm.summary()
            np.testing.assert_array_equal(early.per_chip[name], sm.per_chip)
        np.testing.assert_array_equal(early.per_chip["bit_agreement"],
                                      vals[:early.n_chips])
        kinds = [json.loads(line)["kind"] for line in
                 (rl.path / "metrics.jsonl").read_text().splitlines()]
        assert "early_stop" in kinds

    def test_no_obs_is_default_and_silent(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        _, mapped, x = _layer(fan_in=64, n_out=16, batch=8, bias_rows=8)
        res = run_mc(jax.random.PRNGKey(0), mapped, x,
                     mc=McConfig(n_chips=4, chunk_size=2))
        assert res.n_chips == 4
        assert not (tmp_path / "experiments").exists()


# ------------------------------------------------------------ CLI end-to-end


class TestMcCliRunDir:
    def test_layer_cli_emits_run_dir(self, tmp_path, monkeypatch, capsys):
        from repro.launch import mc as mc_cli
        monkeypatch.setattr(sys, "argv", [
            "mc", "--chips", "4", "--chunk", "2", "--batch", "8",
            "--fan-in", "32", "--n-out", "8", "--bias-rows", "4",
            "--ablation", "all", "--run-dir", str(tmp_path / "exp"),
            "--run-id", "cli1"])
        mc_cli.main()
        run = tmp_path / "exp" / "cli1"
        for f in ("manifest.json", "metrics.jsonl", "results.csv",
                  "report.json", "per_chip_bit_agreement_ideal.npy",
                  "per_chip_bit_agreement_all.npy",
                  "per_chip_ones_fraction_all.npy"):
            assert (run / f).exists(), f
        man = json.loads((run / "manifest.json").read_text())
        assert man["status"] == "ok" and man["args"]["chips"] == 4
        assert len(np.load(run / "per_chip_bit_agreement_all.npy")) == 4
        csv = (run / "results.csv").read_text().splitlines()
        assert csv[0].startswith("config,agree_mean")
        assert len(csv) == 3                          # header + ideal + all
        out = capsys.readouterr().out
        assert "run dir:" in out and "compile_s" in out
        report = json.loads((run / "report.json").read_text())
        assert set(report["results"]) == {"ideal", "all"}
        assert report["run_id"] == "cli1"
