"""The benchmark's own tests, on the CPU: FLOP counts, file resolution by
name, the chip check, the trace reduction, and the correctness comparison
(reference against the program, the control, and a planted fault)."""
import copy
import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402
import harness  # noqa: E402
import tracereduce  # noqa: E402

SPEC = harness.load_json(harness.SPEC_FILE)
TRACE = BENCH / "tests" / "data" / "small.xplane.pb"


def conf_of(name):
    return harness.load_json(BENCH / "configs" / f"{name}.json")


# ------------------------------------------------------------------ flops

@pytest.mark.parametrize("name,crossbars", [("irc_proposed", 7.085e10),
                                            ("irc_baseline", 7.878e10)])
def test_die_flops_match_hand_counts(name, crossbars):
    """2 planes x 2*P*R*N summed over the 14 group crossbars, P summing to
    2*147456 + 4*36864 + 8*9216 = 516096 positions."""
    conf = conf_of(name)
    assert flops.crossbar_flops(conf) == pytest.approx(crossbars, rel=1e-3)
    assert flops.stem_flops(conf["network"]) == pytest.approx(4.78e8,
                                                              rel=1e-3)
    assert flops.head_flops(conf["network"]) == pytest.approx(4.42e7,
                                                              rel=1e-3)
    assert flops.die_flops(conf) == pytest.approx(
        crossbars + 4.78e8 + 4.42e7, rel=1e-3)


def test_die_flops_at_smoke_geometry():
    """yolo_irc.smoke(): 32x32 input, stages 60/120 x 1 block, 16 bias
    rows; positions 16*16 and 8*8, rows 16 + 540."""
    sys.path.insert(0, str(harness.CHECKOUT / "src"))
    from repro.configs import yolo_irc
    cfg = dataclasses.asdict(yolo_irc.smoke())
    net = {k: list(v) if isinstance(v, tuple) else v
           for k, v in cfg.items() if k != "dtype"}
    conf = dict(conf_of("irc_proposed"), network=net)
    rows = 16 + 540
    assert flops.crossbar_flops(conf) == 4 * rows * 60 * (256 * 1 + 64 * 2)
    assert flops.stem_flops(net) == 2 * 256 * 27 * 60
    assert flops.head_flops(net) == 2 * (4 * 4) * 120 * (2 * 8)


def test_qat_flops_count_the_first_layer_once_per_image():
    conf = conf_of("irc_proposed")
    one = flops.qat_step_flops(conf, train_chips=1, batch=1)
    four = flops.qat_step_flops(conf, train_chips=4, batch=1)
    first_conv = 3 * 2.0 * 147456 * 540 * 60
    stem = 2 * flops.stem_flops(conf["network"])
    assert four - stem - first_conv == pytest.approx(
        4 * (one - stem - first_conv))


# ------------------------------------------------------------------ files

@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_by_name(cell):
    r = harness.resolve(SPEC, cell)
    assert r["runner"].is_file()
    assert r["traffic"]["kind"] == r["runner"].stem
    assert {m["name"] for m in harness.metrics_for(SPEC, cell, "end_to_end")
            } >= {"setup_s"}
    for m in harness.metrics_for(SPEC, cell, "per_layer"):
        reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)


def test_every_config_and_metric_file_is_used():
    for c in SPEC["configs"]:
        assert (harness.CHECKOUT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    names = {m["name"] for m in SPEC["per_layer"]}
    assert names == {p.stem for p in (BENCH / "metrics").glob("*.py")}
    mixes = {w["traffic"] for w in SPEC["workloads"]}
    assert mixes == {p.stem for p in (BENCH / "traffic").glob("*.json")}
    kinds = {harness.load_json(BENCH / "traffic" / f"{t}.json")["kind"]
             for t in mixes}
    assert kinds == {p.stem for p in (BENCH / "kinds").glob("*.py")}


def test_a_new_cell_of_an_existing_kind_is_data_only(tmp_path):
    """A traffic file and a `workloads` entry are all a new cell needs."""
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("tests"))
    first = harness.resolve(SPEC, SPEC["workloads"][0]["name"])
    traffic = dict(first["traffic"], effects="none")
    (root / "traffic" / "pop_ideal.json").write_text(json.dumps(traffic))
    spec = copy.deepcopy(SPEC)
    spec["workloads"].append(dict(SPEC["workloads"][0],
                                  name="proposed.pop_ideal",
                                  traffic="pop_ideal"))
    r = harness.resolve(spec, "proposed.pop_ideal", bench=root)
    assert r["traffic"]["effects"] == "none"
    assert harness.effects(r["traffic"]) == {k: False
                                            for k in harness.EFFECTS}
    assert r["runner"] == root / "kinds" / first["runner"].name


def test_unknown_chip_has_no_peaks():
    view = {"peaks": harness.load_json(BENCH / "peaks.json"),
            "device_kind": "TPU v5 lite"}
    assert harness.peak(view, "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError):
        harness.peak(dict(view, device_kind="cpu"), "bf16_flops_per_s")


# ------------------------------------------------------------------ chip check

def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "3000000001", "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_run_refuses_a_cpu():
    proc = _run_cli(harness.CHECKOUT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(harness.SPEC_FILE, tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench")
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)


# ------------------------------------------------------------------ trace

def test_trace_reduction_arithmetic():
    ms = 1_000_000
    spans = [("bench.window", 0, 100 * ms), ("bench.call", 0, 60 * ms),
             ("bench.score", 40 * ms, 60 * ms)]
    ops = [("fusion.12", 10 * ms, 30 * ms, "jit_step(7)"),
           ("fusion.40", 20 * ms, 35 * ms, "jit_step(7)"),
           ("copy.3", 70 * ms, 90 * ms, "jit_other"),
           ("fusion.9", 95 * ms, 130 * ms, "jit_step(7)")]
    r = tracereduce.reduce_trace(spans, {"/device:TPU:0": ops})
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.025 + 0.020 + 0.005)
    assert dict(r["programs"]) == pytest.approx({"step": 0.040,
                                                 "other": 0.020})
    assert dict(r["device_ops"])["step/fusion"] == pytest.approx(0.040)
    gaps = r["idle_gaps"]
    assert gaps[0] == ["bench.score", pytest.approx(0.035)]
    assert sorted(g[1] for g in gaps) == pytest.approx([0.005, 0.010, 0.035])
    assert tracereduce.reduce_trace(spans, {}) is None


def test_trace_reduction_of_a_recorded_chip_trace():
    """A trace recorded on a TPU v5e: three jitted calls inside
    `bench.window`, each inside a `bench.call` span."""
    r = tracereduce.reduce_dir(TRACE.parent)
    assert 0 < r["busy_s"] < r["window_s"]
    for name, seconds in r["device_ops"] + r["programs"]:
        assert seconds > 0 and not name[-1].isdigit()
    assert {g[0] for g in r["idle_gaps"]} <= {"bench.call",
                                             "host.outside_spans"}
