"""Whole-network chip-ensemble MC for the IRC detector (Table II, in the
paper's own units).

`repro.mc.engine` evaluates chip populations of ONE mapped layer and reports
bit-agreement proxies; the paper's headline result (3.85% mAP drop under all
nonideal effects vs. catastrophic baseline failure) is a statistic of the
WHOLE detector over sampled chips.  This module threads `ChipEnsemble`
through the detector stack:

  DetectorEnsemble / build_detector_ensemble
      pre-sampled per-layer, per-group chip planes.  Chip `c`, layer `l`
      (= s*10+b), group `g` is sampled with
      `fold_in(fold_in(fold_in(key, c), l), g)` — chip-consistent with
      `IRCDetector.apply`'s single-chip key discipline, so chip `c` of the
      ensemble path is bit-identical to `apply(mode="eval",
      key=fold_in(key, c))`.
  run_mc_detector / run_ablation_detector
      stream the population in chunks through the jitted ensemble structural
      path and fold each chip's HOST-side mAP@0.5 (`evaluate_map_per_chip`)
      into the engine's Welford/quantile accumulators — the same
      McConfig/McResult machinery as the layer-level sweeps.

All chips of a die design share the LRS placement planes, so each layer
ensemble stores ONE [rows, n_out] placement copy; only the effective
conductances ([chips, rows, n_out]) and SA keys are per chip.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import nonideal as ni
from repro.core.macro import MacroSpec
from repro.mc.engine import McConfig, McResult, TABLE2_ABLATION
from repro.mc.ensemble import ChipEnsemble, sample_ensemble_with_keys
from repro.mc.stats import StreamingMoments
from repro.obs import ConvergenceMonitor, PhaseTimer, RunLog, as_runlog


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DetectorEnsemble:
    """A chip population of the whole detector.

    layers:   block name ("s{s}b{b}") -> per-group `ChipEnsemble`s, in the
              group order of `IRCDetector.group_mappings`.
    chip_ids: [chips] global chip indices (fold_in stream positions), shared
              by every layer ensemble — one die is one draw of EVERY layer.
    """
    layers: Dict[str, Tuple[ChipEnsemble, ...]]
    chip_ids: jax.Array

    @property
    def n_chips(self) -> int:
        """Population size: number of sampled dies in this ensemble."""
        return self.chip_ids.shape[0]


def detector_layer_keys(key: jax.Array, chip_ids: jax.Array, layer_id: int,
                        g: int) -> jax.Array:
    """Per-chip keys of one detector (layer, group) crossbar:
    `fold_in(fold_in(fold_in(key, c), layer_id), g)` — THE key stream shared
    by the eval-time ensemble builder, the train-time surrogate sampler, and
    the single-chip structural path (`IRCDetector.apply(mode="eval")` folds
    the same layer_id = s*10+b and group g)."""
    return jax.vmap(lambda i: jax.random.fold_in(
        jax.random.fold_in(jax.random.fold_in(key, i), layer_id), g))(chip_ids)


def build_detector_ensemble(key: jax.Array, det, params, n_chips: int = 0, *,
                            chip_ids: Optional[jax.Array] = None,
                            cfg: ni.NonidealConfig = ni.NonidealConfig.all(),
                            device=None) -> DetectorEnsemble:
    """Sample a chip population of every group crossbar in the detector.

    Pass `chip_ids` to sample an arbitrary slice of the logical ensemble
    (how the streaming sweep bounds memory); the key chain per (chip, layer,
    group) matches the single-chip eval path exactly.  `device` selects the
    `repro.device` backend all layer planes are drawn from (None: analytic).
    """
    dcfg = det.cfg
    if chip_ids is None:
        chip_ids = jnp.arange(n_chips, dtype=jnp.uint32)
    layers: Dict[str, Tuple[ChipEnsemble, ...]] = {}
    for s, (ch, nb) in enumerate(zip(dcfg.stage_channels,
                                     dcfg.blocks_per_stage)):
        c_in = dcfg.stage_channels[max(0, s - 1)] if s else ch
        for b in range(nb):
            cin = max(c_in if b == 0 else ch, ch)   # widen-by-repetition
            name = f"s{s}b{b}"
            groups = []
            for g, mapped in enumerate(det.group_mappings(params[name],
                                                          cin, ch)):
                keys = detector_layer_keys(key, chip_ids, s * 10 + b, g)
                groups.append(sample_ensemble_with_keys(
                    keys, mapped, chip_ids=chip_ids, cfg=cfg, spec=det.spec,
                    device=device))
            layers[name] = tuple(groups)
    return DetectorEnsemble(layers=layers, chip_ids=chip_ids)


def build_train_ensemble(key: jax.Array, det, params, n_chips: int, *,
                         chip_ids: Optional[jax.Array] = None,
                         cfg: ni.NonidealConfig = ni.NonidealConfig.all(),
                         device=None) -> DetectorEnsemble:
    """Train-time chip population: per-layer DEVIATION planes, no eval-only
    extras (per-die bias calibration, sensing periphery state).

    Same plane sampling and `detector_layer_keys` stream as the eval builder
    — chip `c` here IS chip `c` of `build_detector_ensemble` — but each
    layer's ChipEnsemble carries (effective - nominal) conductance deltas
    (`deviation_planes`), so `mode="train_ensemble"` can add each chip's
    frozen linear variation error to the differentiable QAT pre-activation.
    Everything inside is jit-traceable: the QAT step rebuilds the planes from
    the CURRENT quantized weights every step while the chip identity (the
    variation masks' keys) advances only when the caller advances `key`
    (`resample_every` scheduling lives in `repro.train.steps`).
    """
    from repro.mc.ensemble import deviation_planes
    ens = build_detector_ensemble(key, det, params, n_chips,
                                  chip_ids=chip_ids, cfg=cfg, device=device)
    return DetectorEnsemble(
        layers={name: tuple(deviation_planes(g, det.spec, device)
                            for g in groups)
                for name, groups in ens.layers.items()},
        chip_ids=ens.chip_ids)


@functools.partial(jax.jit, static_argnames=("det_cfg", "spec", "cfg_ni",
                                             "sa_extra", "use_kernel",
                                             "kernel_impl", "device"))
def _ensemble_forward(params, images, ens: DetectorEnsemble, *, det_cfg,
                      spec: MacroSpec, cfg_ni: ni.NonidealConfig,
                      sa_extra: float,
                      use_kernel: Optional[bool] = None,
                      kernel_impl: str = "pallas", device=None) -> jax.Array:
    """Module-level jitted ensemble forward: the compile cache is keyed on
    the (hashable) detector config, so repeated `run_mc_detector` calls —
    chunk streams, ablation columns, benchmark reruns — reuse one program
    per shape instead of retracing a per-call closure."""
    from repro.models.detector import IRCDetector
    det = IRCDetector(det_cfg, spec)
    return det.apply(params, images, mode="ensemble", ensemble=ens,
                     cfg_ni=cfg_ni, sa_extra=sa_extra,
                     use_kernel=use_kernel, kernel_impl=kernel_impl,
                     device=device)


def detector_planes(det, params):
    """Hoist the per-layer `group_mappings` out of the chunk loop.

    `build_detector_ensemble` re-derives every group's mapped planes from
    the current params on every call — a per-chunk host cost (quantization,
    plane assembly) that is INVARIANT across chunks of one sweep.  This
    returns the same information split for the jitted chunk program:

      planes  nested tuple pytree of (g_pos, g_neg) arrays per layer/group
              (traced jit operands — donation-safe, no Python objects);
      meta    hashable static twin: per layer (name, layer_id = s*10+b,
              per-group (bias_rows, scheme, fan_in)).
    """
    dcfg = det.cfg
    planes, meta = [], []
    for s, (ch, nb) in enumerate(zip(dcfg.stage_channels,
                                     dcfg.blocks_per_stage)):
        c_in = dcfg.stage_channels[max(0, s - 1)] if s else ch
        for b in range(nb):
            cin = max(c_in if b == 0 else ch, ch)   # widen-by-repetition
            name = f"s{s}b{b}"
            group_maps = det.group_mappings(params[name], cin, ch)
            planes.append(tuple((m.g_pos, m.g_neg) for m in group_maps))
            meta.append((name, s * 10 + b,
                         tuple((m.bias_rows, m.scheme, m.fan_in)
                               for m in group_maps)))
    return tuple(planes), tuple(meta)


def _sample_and_forward(params, images, key, chip_ids, planes, *, det_cfg,
                        spec: MacroSpec, cfg_ni: ni.NonidealConfig,
                        sa_extra: float, meta,
                        use_kernel: Optional[bool] = None,
                        kernel_impl: str = "pallas", device=None) -> jax.Array:
    """Shared trace body of `_sampled_chunk_forward` and
    `committee_wave_forward`: rebuild each group's `MappedLayer` from the
    hoisted planes/meta, sample the chunk's `DetectorEnsemble` in-trace, and
    run the ensemble structural forward.  Keeping ONE body guarantees the
    serving wave traces the exact ops of the MC chunk program per lane.
    The sampling runs under the named scope `sample`."""
    from repro.core.mapping import MappedLayer
    from repro.models.detector import IRCDetector
    det = IRCDetector(det_cfg, spec)
    layers: Dict[str, Tuple[ChipEnsemble, ...]] = {}
    with jax.named_scope("sample"):
        for layer_planes, (name, layer_id, gmeta) in zip(planes, meta):
            groups = []
            for g, ((gp, gn), (bias_rows, scheme, fan_in)) in enumerate(
                    zip(layer_planes, gmeta)):
                mapped = MappedLayer(g_pos=gp, g_neg=gn, bias_rows=bias_rows,
                                     scheme=scheme, fan_in=fan_in)
                keys = detector_layer_keys(key, chip_ids, layer_id, g)
                groups.append(sample_ensemble_with_keys(
                    keys, mapped, chip_ids=chip_ids, cfg=cfg_ni, spec=spec,
                    device=device))
            layers[name] = tuple(groups)
    ens = DetectorEnsemble(layers=layers, chip_ids=chip_ids)
    return det.apply(params, images, mode="ensemble", ensemble=ens,
                     cfg_ni=cfg_ni, sa_extra=sa_extra,
                     use_kernel=use_kernel, kernel_impl=kernel_impl,
                     device=device)


@functools.partial(jax.jit, static_argnames=("det_cfg", "spec", "cfg_ni",
                                             "sa_extra", "meta",
                                             "use_kernel", "kernel_impl",
                                             "device"))
def _sampled_chunk_forward(params, images, key, chip_ids, planes, *, det_cfg,
                           spec: MacroSpec, cfg_ni: ni.NonidealConfig,
                           sa_extra: float, meta,
                           use_kernel: Optional[bool] = None,
                           kernel_impl: str = "pallas",
                           device=None) -> jax.Array:
    """Fused chunk program for the pipelined sweep: sample the chunk's
    `DetectorEnsemble` IN-TRACE (same `detector_layer_keys` stream and
    `sample_ensemble_with_keys` ops as the eager builder — the threefry
    sampling is bitwise deterministic, so the planes, and hence the
    predictions, are bit-identical to the serial path; pinned by
    tests/test_detector_mc.py) and run the ensemble forward, all in ONE
    dispatch.  Folding the sampling into the program removes the serial
    path's per-chunk eager-dispatch overhead and lets the whole chunk run
    asynchronously while the host scores the previous one."""
    return _sample_and_forward(params, images, key, chip_ids, planes,
                               det_cfg=det_cfg, spec=spec, cfg_ni=cfg_ni,
                               sa_extra=sa_extra, meta=meta,
                               use_kernel=use_kernel, kernel_impl=kernel_impl,
                               device=device)


@functools.partial(jax.jit, static_argnames=("det_cfg", "spec", "cfg_ni",
                                             "sa_extra", "meta",
                                             "use_kernel", "kernel_impl",
                                             "device"))
def committee_wave_forward(params, images, request_keys, chip_ids, planes, *,
                           det_cfg, spec: MacroSpec,
                           cfg_ni: ni.NonidealConfig, sa_extra: float, meta,
                           use_kernel: Optional[bool] = None,
                           kernel_impl: str = "pallas",
                           device=None) -> jax.Array:
    """One serving wave: every request lane gets its OWN chip committee.

    `images` is [slots, H, W, 3] and `request_keys` is [slots] stacked PRNG
    keys (one `fold_in(root, request_id)` per lane).  Each lane is traced as
    an independent `_sample_and_forward` at batch 1 — its committee sampling
    is keyed only by that lane's request key, so a request's draws cannot
    depend on which other requests share its wave (per-read SA noise shapes
    would otherwise couple lanes through the batch axis).  The lanes are
    unrolled into ONE jitted program (`slots` is a static shape), so a wave
    still costs a single dispatch; returns [slots, chips, gh, gw, ho].

    Lane `i` is bit-identical to
    `_sampled_chunk_forward(params, images[i:i+1], request_keys[i], ...)` —
    and hence to `run_mc_detector(fold_in(root, request_id), ...)` at the
    same chip ids — pinned by tests/test_serve_detector.py.
    """
    lanes = []
    for i in range(images.shape[0]):
        out = _sample_and_forward(
            params, images[i:i + 1], request_keys[i], chip_ids, planes,
            det_cfg=det_cfg, spec=spec, cfg_ni=cfg_ni, sa_extra=sa_extra,
            meta=meta, use_kernel=use_kernel, kernel_impl=kernel_impl,
            device=device)
        lanes.append(out[:, 0])                 # [chips, gh, gw, ho]
    return jnp.stack(lanes)


def run_mc_detector(key: jax.Array, det, params, images: jax.Array,
                    gt_boxes: List[np.ndarray],
                    gt_classes: List[np.ndarray], *,
                    mc: McConfig = McConfig(),
                    sa_extra: float = 0.0,
                    obs: Optional[RunLog] = None,
                    stderr_target: Optional[float] = None,
                    pipeline: bool = True,
                    use_kernel: Optional[bool] = None,
                    kernel_impl: str = "pallas") -> McResult:
    """Stream a chip population of the WHOLE detector over an eval batch.

    Per chunk: build the chunk's `DetectorEnsemble`, run ONE jitted
    ensemble structural forward (all chips, all layers), then fold each
    chip's host-side mAP@0.5 into the streaming accumulators.  The metric
    name is "map50"; chunking is statistically invisible (chip `c` is keyed
    by `fold_in(key, c)` regardless of chunk layout).

    `pipeline=True` (default) runs the double-buffered path: the group
    mappings are hoisted out of the loop (`detector_planes`), each chunk's
    ensemble sampling is fused into its jitted forward
    (`_sampled_chunk_forward`), and chunk k+1 is DISPATCHED before chunk k's
    host-side mAP matching — the device computes the next chunk while the
    host scores the current one.  Per-chip results are bit-identical to
    `pipeline=False` (same key stream, same sampled planes, same fold
    order; pinned by tests) — early stop triggers at the same chunk
    boundary, discarding at most the one extra in-flight chunk.

    `use_kernel`/`kernel_impl` route the grouped matmuls onto the Pallas
    chip-batched kernel (see `IRCDetector._gconv_ensemble`; None defers to
    the committed autotuning table).

    `params` should carry calibrated stem-BN running stats
    (`det.calibrate_bn`) — eval-mode normalization uses them.

    `obs` streams per-chunk events (raw per-chip mAPs + running stderr) into
    a run directory; `stderr_target` stops at the first chunk boundary where
    the mAP standard error reaches the target — identical moments to the
    same-length prefix of the full run (same engine semantics as `run_mc`).

    In a profiler trace the pipelined loop names its host phases:
    `repro.mc.planes` (`detector_planes`, once per call),
    `repro.mc.dispatch` (each chunk's dispatch, argument `chunk`),
    `repro.mc.wait` (the `device_s` lap) and `repro.mc.score` (the
    `host_s` lap: fetch and mAP scoring).
    """
    from repro.train.det_loss import evaluate_map_per_chip

    obs = as_runlog(obs)
    moments = {"map50": StreamingMoments(mc.quantiles)}
    monitor = ConvergenceMonitor(moments, stderr_target=stderr_target,
                                 runlog=obs, phase="mc_detector")
    timer = PhaseTimer("mc_detector_chunks", unit="chips")
    dev_timer = PhaseTimer("mc_detector_device", unit="chips")
    host_timer = PhaseTimer("mc_detector_host", unit="chips")
    obs.log_event("mc_start", phase="mc_detector", n_chips=mc.n_chips,
                  chunk_size=mc.chunk_size, stderr_target=stderr_target,
                  pipeline=pipeline,
                  device_model=(mc.device.name if mc.device is not None
                                else "analytic"))

    chunk_ids = [jnp.arange(lo, min(lo + mc.chunk_size, mc.n_chips),
                            dtype=jnp.uint32)
                 for lo in range(0, mc.n_chips, mc.chunk_size)]

    if pipeline:
        with jax.profiler.TraceAnnotation("repro.mc.planes"):
            planes, meta = detector_planes(det, params)

        def dispatch(i):
            """Launch chunk `i`'s sample+forward on device, without waiting."""
            with jax.profiler.TraceAnnotation("repro.mc.dispatch", chunk=i):
                return _sampled_chunk_forward(
                    params, images, key, chunk_ids[i], planes,
                    det_cfg=det.cfg, spec=det.spec, cfg_ni=mc.cfg,
                    sa_extra=sa_extra, meta=meta, use_kernel=use_kernel,
                    kernel_impl=kernel_impl, device=mc.device)

    inflight = None
    n_done = 0
    for chunk_i, ids in enumerate(chunk_ids):
        n_chunk = int(ids.shape[0])
        with timer.lap(items=n_chunk):
            if pipeline:
                with dev_timer.lap(items=n_chunk, span="repro.mc.wait"):
                    if inflight is None:
                        # first chunk: its jit compile is part of the
                        # first (compile) lap
                        inflight = dispatch(chunk_i)
                    preds_dev = jax.block_until_ready(inflight)
                if chunk_i + 1 < len(chunk_ids):
                    # double buffer: next chunk on device DURING host scoring
                    inflight = dispatch(chunk_i + 1)
            else:
                with dev_timer.lap(items=n_chunk):
                    ens = build_detector_ensemble(key, det, params,
                                                  chip_ids=ids, cfg=mc.cfg,
                                                  device=mc.device)
                    preds_dev = jax.block_until_ready(_ensemble_forward(
                        params, images, ens, det_cfg=det.cfg, spec=det.spec,
                        cfg_ni=mc.cfg, sa_extra=sa_extra,
                        use_kernel=use_kernel, kernel_impl=kernel_impl,
                        device=mc.device))
            with host_timer.lap(items=n_chunk, span="repro.mc.score"):
                preds = np.asarray(preds_dev)
                vals = jnp.asarray(evaluate_map_per_chip(
                    preds, gt_boxes, gt_classes, det.cfg.n_anchors,
                    det.cfg.n_classes))
        n_done += n_chunk
        moments["map50"].update(vals)
        obs.log_event("chunk", phase="mc_detector", chunk=chunk_i,
                      chip_lo=int(ids[0]), chips=n_done, wall_s=timer.last_s,
                      device_s=dev_timer.last_s, host_s=host_timer.last_s,
                      values={"map50": np.asarray(jnp.ravel(vals))})
        if monitor.after_chunk(chunk_i, n_done):
            obs.log_event("early_stop", chips=n_done, requested=mc.n_chips,
                          stderr_target=stderr_target)
            break

    res = McResult(
        n_chips=n_done,
        metrics={name: m.summary() for name, m in moments.items()},
        per_chip={name: m.per_chip for name, m in moments.items()},
        wall_s=timer.total_s, chips_per_sec=timer.rate(),
        compile_s=timer.compile_s,
        device_s=dev_timer.total_s, host_s=host_timer.total_s)
    obs.log_event("mc_result", phase="mc_detector", chips=n_done,
                  requested=mc.n_chips, wall_s=res.wall_s,
                  compile_s=res.compile_s, chips_per_sec=res.chips_per_sec,
                  device_s=res.device_s, host_s=res.host_s,
                  pipeline=pipeline, metrics=res.metrics)
    return res


def run_ablation_detector(key: jax.Array, det, params, images: jax.Array,
                          gt_boxes: List[np.ndarray],
                          gt_classes: List[np.ndarray], *,
                          ablations: Sequence[Tuple[str, ni.NonidealConfig]]
                          = TABLE2_ABLATION,
                          mc: McConfig = McConfig(),
                          obs: Optional[RunLog] = None,
                          stderr_target: Optional[float] = None,
                          pipeline: bool = True,
                          use_kernel: Optional[bool] = None,
                          kernel_impl: str = "pallas"
                          ) -> Dict[str, McResult]:
    """Table II for the detector: one population mAP sweep per effect
    column, same chip key stream across columns (each effect set resamples
    the same dies' variation)."""
    obs = as_runlog(obs)
    results = {}
    for name, cfg in ablations:
        obs.log_event("ablation_column", phase="mc_detector", column=name)
        results[name] = run_mc_detector(
            key, det, params, images, gt_boxes, gt_classes,
            mc=dataclasses.replace(mc, cfg=cfg), obs=obs,
            stderr_target=stderr_target, pipeline=pipeline,
            use_kernel=use_kernel, kernel_impl=kernel_impl)
    return results
