#!/usr/bin/env python3
"""Bring-up smoke of the detector population path on one TPU chip.

Drives the main path once, through the library functions the CLIs call, at
the paper's geometry (`yolo_irc.proposed()`: 576x1024 input, stages
60/120/240 x 2 blocks, ternary, single-shot, 32 bias rows) with random
weights made from --seed:

  1. device   platform / device_kind / count; anything but a TPU fails
  2. qat      3 ensemble-aware QAT steps (`make_det_qat_step`, 2 chips)
  3. mc       `calibrate_bn` + pipelined `run_mc_detector`, all effects on
  4. kernel   the first IRC layer through the compiled Pallas kernel
              (`use_kernel=True`), against its jnp oracle on the chip and
              against the same call on the host's CPU device
  5. serve    `DetectorServeEngine` (committee 2, one slot) answers 3
              requests; each committee is checked against `run_mc_detector`
              at the same request key and chip ids

  python chip_smoke.py                # one chip, phases 1-5
  python chip_smoke.py --four-chips   # only: run_mc sharded over the four
                                      # chips of one host vs one device

Timings are printed as information only.  The last stdout line is
{"ok": true, "device": {...}}; any failure exits non-zero before it.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import types
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# per-chip fraction of equal output bits the compiled kernel must reach
# against its oracle and against the CPU
MIN_BIT_AGREEMENT = 0.999
# a served committee against run_mc_detector at the same key and chip ids:
# |per-chip mAP@0.5 difference| and max |head-prediction difference|
SERVE_MAP_TOL = 0.0
SERVE_PRED_TOL = 0.0
# CPU cross-check: first-layer input rows (x full width) run on both devices
CPU_BAND_ROWS = 16
# population MC: chips, chips per chunk, evaluation images.  From
# `compiled.memory_analysis()` of `_sampled_chunk_forward` compiled for v5e
# at this geometry, all effects on: 4 chips x batch 1 per chunk needs
# 7.35 GiB of the 16 GB device, 8 x 1 needs 14.57 GiB (too close to fit).
MC_CHIPS = 8
MC_CHUNK = 4
MC_BATCH = 1


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def device_check(count: int | None = None) -> dict:
    """Phase 1: the device JAX sees; anything but a TPU fails."""
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    log("device", f"platform={d.platform} device_kind={d.device_kind} "
                  f"count={len(devs)}")
    check(d.platform == "tpu", f"JAX found no TPU (platform {d.platform!r})")
    if count is not None:
        check(len(devs) == count, f"need {count} devices, found {len(devs)}")
    return info


def import_repro():
    """The package lives next to this script; nothing else is searched."""
    check((SRC / "repro").is_dir(), f"repro package not found under {SRC}")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    log("setup", f"compile cache: {enable_compile_cache()}")


def phase_qat(det, data, *, seed: int, steps: int = 3, batch: int = 1,
              train_chips: int = 2):
    """Phase 2: ensemble-aware QAT steps; the loss must stay finite."""
    import jax
    import jax.numpy as jnp
    from repro.core import NonidealConfig
    from repro.optim import adamw_init
    from repro.train.steps import ensemble_key_for_step, make_det_qat_step

    params = det.init(jax.random.PRNGKey(seed))
    opt = adamw_init(params)
    step = jax.jit(make_det_qat_step(det, train_chips=train_chips,
                                     cfg_ni=NonidealConfig.all()))
    root = jax.random.PRNGKey(seed + 1)
    for s in range(steps):
        b = data.batch_for_step(s, batch)
        t0 = time.perf_counter()
        params, opt, loss = step(params, opt, b.images, b.targets,
                                 jnp.float32(3e-3), jax.random.fold_in(root, s),
                                 ensemble_key_for_step(root, s))
        loss = float(loss)
        log("qat", f"step {s} train_chips={train_chips} batch={batch} "
                   f"loss={loss:.6f} wall_s={time.perf_counter() - t0:.3f}")
        check(math.isfinite(loss), f"QAT step {s} loss is {loss}")
    return params


def phase_mc(det, params, data, *, seed: int, chips: int, chunk: int,
             batch: int):
    """Phase 3: population MC over every nonideal effect; every chip's
    mAP@0.5 must be finite."""
    import jax
    import numpy as np
    from repro.core import NonidealConfig
    from repro.mc import McConfig, run_mc_detector

    ev = data.batch_for_step(1000, batch)
    mc = McConfig(n_chips=chips, chunk_size=chunk,
                  cfg=NonidealConfig.all())
    res = run_mc_detector(jax.random.PRNGKey(seed + 2), det, params,
                          ev.images, ev.boxes, ev.classes, mc=mc)
    m = np.asarray(res.per_chip["map50"])
    log("mc", f"chips={res.n_chips} chunk={chunk} batch={batch} "
              f"map50 mean={res.metrics['map50']['mean']:.6f} "
              f"std={res.metrics['map50']['std']:.6f}")
    log("mc", f"chips_per_s={res.chips_per_sec:.4f} "
              f"compile_s={res.compile_s:.3f} wall_s={res.wall_s:.3f} "
              f"device_s={res.device_s:.3f} host_s={res.host_s:.3f} "
              "(one-off bring-up timings)")
    check(m.shape == (chips,), f"MC scored {m.shape} chips, wanted {chips}")
    check(bool(np.all(np.isfinite(m))), f"non-finite map50: {m}")


def _agreement(a, b):
    """Per-chip fraction of equal output bits of two [chips, ...] arrays."""
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return (a == b).reshape(a.shape[0], -1).mean(axis=1)


def phase_kernel(det, params, data, *, seed: int, chips: int,
                 band_rows: int = CPU_BAND_ROWS):
    """Phase 4: the first IRC layer through the compiled chip-batched Pallas
    kernel, against its jnp oracle on the chip (whole layer) and against
    the same call on the host's CPU device (a band of input rows: the CPU
    runs the kernel in interpret mode)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import NonidealConfig
    from repro.mc import build_detector_ensemble

    cfg = det.cfg
    ch = cfg.stage_channels[0]
    images = data.batch_for_step(1000, 1).images
    x0 = jax.jit(det.stem)(params, images)                # [1, H, W, ch]
    ens = build_detector_ensemble(
        jax.random.PRNGKey(seed + 2), det, params,
        chip_ids=jnp.arange(chips, dtype=jnp.uint32),
        cfg=NonidealConfig.all())
    groups = ens.layers["s0b0"]

    @functools.partial(jax.jit, static_argnames="impl")
    def layer(groups, x, impl):
        return det._gconv_ensemble(groups, x, ch, ch,
                                   cfg_ni=NonidealConfig.all(),
                                   use_kernel=True, kernel_impl=impl)

    t0 = time.perf_counter()
    compiled = layer.lower(groups, x0, impl="pallas").compile()
    t_compile = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text(),
          "the kernel did not compile to a Mosaic custom call")
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(groups, x0))
    t_run = time.perf_counter() - t0
    ref = jax.block_until_ready(layer(groups, x0, impl="ref"))
    agree_ref = _agreement(out, ref)
    log("kernel", f"first IRC layer {tuple(x0.shape)} x {chips} chips -> "
                  f"{tuple(out.shape)}; compile_s={t_compile:.3f} first "
                  f"run_s={t_run:.3f} (one-off bring-up timings)")
    log("kernel", "pallas(tpu) vs ref(tpu) per-chip bit agreement: "
                  + " ".join(f"{a:.7f}" for a in agree_ref))

    band = x0[:, :band_rows]
    out_band = layer(groups, band, impl="pallas")
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        out_cpu = layer(jax.device_put(groups, cpu), jax.device_put(band, cpu),
                        impl="pallas")
    agree_cpu = _agreement(out_band, out_cpu)
    log("kernel", f"pallas(tpu) vs pallas(cpu, interpret) on {band_rows} "
                  f"input rows, per-chip bit agreement: "
                  + " ".join(f"{a:.7f}" for a in agree_cpu))
    ones = float(np.asarray(out).mean())
    log("kernel", f"ones fraction {ones:.4f}")
    check(bool(np.all(agree_ref >= MIN_BIT_AGREEMENT)),
          f"kernel vs ref agreement {agree_ref} < {MIN_BIT_AGREEMENT}")
    check(bool(np.all(agree_cpu >= MIN_BIT_AGREEMENT)),
          f"kernel TPU vs CPU agreement {agree_cpu} < {MIN_BIT_AGREEMENT}")


def phase_serve(det, params, data, *, seed: int, committee: int = 2,
                requests: int = 3):
    """Phase 5: committee serving; boxes and finite population statistics
    for every request, committees equal to `run_mc_detector`'s."""
    import jax
    import numpy as np
    from repro.core import NonidealConfig
    from repro.mc import McConfig, run_mc_detector
    from repro.mc.detector_mc import _sampled_chunk_forward, detector_planes
    from repro.serve.detector import DetectorServeEngine
    from repro.train.det_loss import evaluate_map_per_chip

    cfg = det.cfg
    cfg_ni = NonidealConfig.all()
    reqs = data.batch_for_step(2000, requests)
    # random weights are not trained to detect: at the paper geometry no
    # committee-mean score clears the engine's 0.1 default, so every anchor
    # is a candidate and the smoke checks decoding + NMS, not accuracy
    eng = DetectorServeEngine(det, params, committee=committee,
                              batch_slots=1, cfg_ni=cfg_ni, seed=seed + 3,
                              keep_committee=True, conf_thresh=0.0)
    responses = eng.serve_batch(list(np.asarray(reqs.images)))
    stats = eng.stats()
    log("serve", f"{len(responses)} requests, committee {committee}, 1 slot; "
                 f"wave compile_s={stats['wave'].get('compile_s', 0.0):.3f} "
                 "(one-off bring-up timing)")
    planes, meta = detector_planes(det, params)
    chip_ids = jax.numpy.arange(committee, dtype=jax.numpy.uint32)
    root = jax.random.PRNGKey(seed + 3)
    for i, r in enumerate(responses):
        conf = r.confidence
        boxes = np.array([d.box for d in r.detections], np.float64)
        log("serve", f"request {r.request_id}: {len(r.detections)} boxes, "
                     f"score mean={conf['mean']:.6f} std={conf['std']:.6f}")
        check(len(r.detections) > 0, f"request {r.request_id}: no boxes")
        check(bool(np.all(np.isfinite(boxes))), "non-finite box")
        check(all(math.isfinite(v) for v in conf.values()),
              f"non-finite population statistics: {conf}")
        key = jax.random.fold_in(root, r.request_id)
        res = run_mc_detector(
            key, det, params, reqs.images[i:i + 1], [reqs.boxes[i]],
            [reqs.classes[i]],
            mc=McConfig(n_chips=committee, chunk_size=committee, cfg=cfg_ni))
        mine = evaluate_map_per_chip(r.committee[:, None], [reqs.boxes[i]],
                                     [reqs.classes[i]], cfg.n_anchors,
                                     cfg.n_classes)
        d_map = float(np.max(np.abs(mine - res.per_chip["map50"])))
        chunk = _sampled_chunk_forward(
            params, reqs.images[i:i + 1], key, chip_ids, planes,
            det_cfg=cfg, spec=det.spec, cfg_ni=cfg_ni, sa_extra=0.0,
            meta=meta)
        delta = np.abs(r.committee - np.asarray(chunk)[:, 0])
        d_pred = float(np.max(delta))
        log("serve", f"request {r.request_id}: |committee - run_mc_detector| "
                     f"map50 {d_map:.3g}, head predictions max {d_pred:.3g} "
                     f"(unequal fraction {float(np.mean(delta > 0)):.3g})")
        check(d_map <= SERVE_MAP_TOL,
              f"served committee mAP differs from run_mc_detector by {d_map}")
        check(d_pred <= SERVE_PRED_TOL,
              f"served committee predictions differ from run_mc_detector's "
              f"chunk forward by {d_pred}")


def run_one_chip(args) -> dict:
    info = device_check()
    import_repro()
    from repro.configs import yolo_irc
    from repro.data.detection import SyntheticDetectionData
    from repro.models import IRCDetector

    cfg = yolo_irc.proposed()
    det = IRCDetector(cfg)
    data = SyntheticDetectionData(img_hw=cfg.img_hw, stride=cfg.strides,
                                  n_classes=cfg.n_classes,
                                  n_anchors=cfg.n_anchors, seed=args.seed)
    log("setup", f"yolo_irc.proposed(): img {cfg.img_hw} stages "
                 f"{cfg.stage_channels} x {cfg.blocks_per_stage} "
                 f"{cfg.scheme} {cfg.accumulation} bias_rows={cfg.bias_rows}")
    t0 = time.perf_counter()
    params = phase_qat(det, data, seed=args.seed)
    params = det.calibrate_bn(params, data.batch_for_step(999, 2).images)
    log("qat", f"phase wall_s={time.perf_counter() - t0:.3f}")
    for name, fn, kw in (
            ("mc", phase_mc, dict(chips=MC_CHIPS, chunk=MC_CHUNK,
                                  batch=MC_BATCH)),
            ("kernel", phase_kernel, dict(chips=2)),
            ("serve", phase_serve, {})):
        t0 = time.perf_counter()
        fn(det, params, data, seed=args.seed, **kw)
        log(name, f"phase wall_s={time.perf_counter() - t0:.3f}")
    return info


def run_four_chips(args) -> dict:
    info = device_check(count=4)
    import_repro()
    phase_four_chips(seed=args.seed)
    return info


def phase_four_chips(*, seed: int, positions: int = 144 * 256):
    """`run_mc(mesh=make_host_mesh())` over every device of the host against
    the same run on a one-device mesh: per-chip results must be equal."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import NonidealConfig
    from repro.launch.mc import build_layer
    from repro.launch.mesh import make_host_mesh
    from repro.mc import McConfig, run_mc
    from repro.mc.ensemble import sample_ensemble, shard_ensemble

    # a stage-1 group crossbar of the paper detector at batch 1: 144x256
    # positions x (9*60 im2col + 32 bias) rows x 60 columns
    layer = types.SimpleNamespace(seed=seed, fan_in=540, n_out=60,
                                  scheme="ternary", bias_rows=32,
                                  batch=positions, density=0.5)
    mapped, x, ref_bits = build_layer(layer)
    key = jax.random.PRNGKey(seed)
    mc = McConfig(n_chips=16, chunk_size=8, cfg=NonidealConfig.all())
    mesh4 = make_host_mesh()
    mesh1 = make_host_mesh(jax.devices()[:1])

    ens = shard_ensemble(sample_ensemble(
        key, mapped, chip_ids=jnp.arange(mc.chunk_size, dtype=jnp.uint32),
        cfg=mc.cfg), mesh4)
    for s in ens.ep.addressable_shards:
        log("four", f"ep shard on device {s.device.id}: {tuple(s.data.shape)}")

    results = {}
    for name, mesh in (("sharded", mesh4), ("one_device", mesh1)):
        res = run_mc(key, mapped, x, ref_bits=ref_bits, mc=mc, mesh=mesh)
        results[name] = np.asarray(res.per_chip["bit_agreement"])
        log("four", f"{name}: bit_agreement mean="
                    f"{res.metrics['bit_agreement']['mean']:.7f} "
                    f"chips_per_s={res.chips_per_sec:.3f} "
                    f"compile_s={res.compile_s:.3f} (one-off timings)")
    equal = np.array_equal(results["sharded"], results["one_device"])
    log("four", f"per-chip bit_agreement sharded == one device: {equal}")
    check(equal, "sharded per-chip bit_agreement differs from one device: "
                 f"{results['sharded']} vs {results['one_device']}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded run_mc check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        info = run_four_chips(args) if args.four_chips else run_one_chip(args)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
