"""Named scopes and host spans that the program puts into a profiler trace.

The device trace of a TPU run carries each op's `jax.named_scope` path as
its `tf_op`; the host trace carries the `repro.*` spans of the population
loop and of serving.  On the CPU both can be checked without a chip: the
compiled programs keep the scope paths in their ops' `op_name` metadata,
and a CPU profiler trace holds the host spans."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import yolo_irc
from repro.core import NonidealConfig
from repro.data.detection import SyntheticDetectionData
from repro.mc import McConfig, run_mc_detector
from repro.mc.detector_mc import _sampled_chunk_forward, detector_planes
from repro.models import IRCDetector
from repro.optim import adamw_init
from repro.obs import PhaseTimer
from repro.serve import DetectorServeEngine
from repro.train.steps import make_det_qat_step

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPER = re.compile(r"^[\w]*\((.*)\)$")


def _scopes_in(compiled_text: str) -> set:
    """Every path component of the compiled ops' `op_name`s, with the
    `jvp(...)` / `transpose(...)` wrappers of the backward pass stripped."""
    found = set()
    for name in _OP_NAME.findall(compiled_text):
        for part in name.split("/"):
            while (m := _WRAPPER.match(part)):
                part = m.group(1)
            found.add(part)
    return found


def _detector_scopes(cfg) -> set:
    """`stem`, every `s{s}b{b}`, every `s{s}pool` and `head`."""
    blocks = {f"s{s}b{b}" for s, nb in enumerate(cfg.blocks_per_stage)
              for b in range(nb)}
    pools = {f"s{s}pool" for s in range(len(cfg.stage_channels))}
    return {"stem", "head"} | blocks | pools


def _smoke():
    cfg = yolo_irc.smoke()
    det = IRCDetector(cfg)
    params = det.init(jax.random.PRNGKey(0))
    data = SyntheticDetectionData(cfg.img_hw, cfg.n_classes, cfg.n_anchors,
                                  cfg.strides, seed=1)
    batch = data.batch_for_step(0, 2)
    return det, det.calibrate_bn(params, batch.images), batch


def test_chunk_program_carries_every_stage_scope():
    det, params, _ = _smoke()
    planes, meta = detector_planes(det, params)
    text = _sampled_chunk_forward.lower(
        params, jnp.zeros((1,) + det.cfg.img_hw + (3,)),
        jax.random.PRNGKey(1), jnp.arange(2, dtype=jnp.uint32), planes,
        det_cfg=det.cfg, spec=det.spec, cfg_ni=NonidealConfig.all(),
        sa_extra=0.0, meta=meta, use_kernel=False).compile().as_text()
    assert (_detector_scopes(det.cfg) | {"sample", "ir_drop"}
            <= _scopes_in(text))


def test_qat_step_carries_every_stage_and_step_scope():
    det, params, batch = _smoke()
    step = jax.jit(make_det_qat_step(det, train_chips=2,
                                     cfg_ni=NonidealConfig.all()))
    key = jax.random.PRNGKey(1)
    text = step.lower(params, adamw_init(params), batch.images,
                      batch.targets, jnp.float32(3e-3), key,
                      key).compile().as_text()
    found = _scopes_in(text)
    assert _detector_scopes(det.cfg) | {"train_planes", "loss",
                                        "adamw"} <= found
    # the backward pass of every block is traced under its own scope
    assert {"s0b0", "s1b0"} <= {
        m.group(1) for n in _OP_NAME.findall(text)
        for m in re.finditer(r"transpose\(jvp\((\w+)\)\)", n)}


def _host_spans(trace_dir) -> list:
    """Names of every host event in the newest trace under `trace_dir`."""
    path = sorted(trace_dir.rglob("*.xplane.pb"),
                  key=lambda p: p.stat().st_mtime)[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    return [ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def _count(names, prefix):
    return {n: names.count(n) for n in set(names) if n.startswith(prefix)}


def test_population_call_spans_in_a_cpu_trace(tmp_path):
    """One `run_mc_detector` call of 3 chunks: the planes once, and each
    chunk's dispatch, wait and score once."""
    det, params, batch = _smoke()
    boxes = [np.zeros((0, 4), np.float32)] * 2
    classes = [np.zeros((0,), np.int32)] * 2
    mc = McConfig(n_chips=6, chunk_size=2)
    run = lambda: run_mc_detector(jax.random.PRNGKey(3), det, params,
                                  batch.images, boxes, classes, mc=mc)
    run()                                      # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        res = run()
    assert res.n_chips == 6
    assert _count(_host_spans(tmp_path), "repro.") == {
        "repro.mc.planes": 1, "repro.mc.dispatch": 3, "repro.mc.wait": 3,
        "repro.mc.score": 3}


def test_serving_decode_span_in_a_cpu_trace(tmp_path):
    """One span per served wave: 3 requests in waves of 2 lanes."""
    det, params, batch = _smoke()
    eng = DetectorServeEngine(det, params, committee=2, batch_slots=2,
                              seed=11)
    imgs = [np.asarray(batch.images[i % 2]) for i in range(3)]
    eng.serve_batch(imgs[:1])                  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        eng.serve_batch(imgs)
    assert _count(_host_spans(tmp_path), "repro.") == {
        "repro.serve.decode": 2}


@pytest.mark.parametrize("span", [None, "repro.test.lap"])
def test_phase_timer_lap_with_and_without_span(tmp_path, span):
    timer = PhaseTimer("p")
    with jax.profiler.trace(str(tmp_path)):
        with timer.lap(items=3, span=span):
            pass
    assert timer.laps == 1 and timer.total_items == 3
    names = _count(_host_spans(tmp_path), "repro.")
    assert names == ({} if span is None else {span: 1})
