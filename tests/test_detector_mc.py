"""Whole-network chip-ensemble MC (repro.mc.detector_mc) + the detector
eval-path correctness fixes it depends on: eval-mode BN running stats,
scheme-derived QAT noise fractions, sign-preserving BN calibration, and the
DetectorEnsemble fold_in key discipline (chip c bit-identical to the
single-chip structural path)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import yolo_irc
from repro.core import NonidealConfig
from repro.core.crossbar import variation_noise_std
from repro.core.ternary import binary_activation
from repro.data.detection import SyntheticDetectionData
from repro.models import IRCDetector
from repro.models.detector import DetectorConfig
from repro.mc import (McConfig, build_detector_ensemble, run_mc_detector,
                      run_ablation_detector)
from repro.train.det_loss import evaluate_map_per_chip


def _detector(scheme="ternary", calib_batch=4, seed=0):
    cfg = yolo_irc.smoke(scheme)
    det = IRCDetector(cfg)
    params = det.init(jax.random.PRNGKey(seed))
    calib = jax.random.uniform(jax.random.PRNGKey(seed + 1),
                               (calib_batch, 32, 32, 3))
    params = det.calibrate_bn(params, calib)
    return det, params


class TestEvalPathFixes:
    def test_eval_batch_size_invariance(self):
        """Eval-mode outputs for one image must not depend on which other
        images share the batch (stem BN must use running stats, not batch
        statistics — MC chunking would otherwise change the metric)."""
        det, params = _detector("ternary")
        imgs = jax.random.uniform(jax.random.PRNGKey(2), (8, 32, 32, 3))
        key = jax.random.PRNGKey(3)
        out8 = det.apply(params, imgs, mode="eval", key=key)
        out1 = det.apply(params, imgs[:1], mode="eval", key=key)
        np.testing.assert_array_equal(np.asarray(out8[:1]), np.asarray(out1))

    def test_stem_feeds_first_irc_layer(self):
        """`stem` is the {0,1} input of the first IRC layer: running stats
        at eval (batch-invariant), batch statistics in train mode."""
        det, params = _detector("ternary")
        imgs = jax.random.uniform(jax.random.PRNGKey(2), (4, 32, 32, 3))
        x = det.stem(params, imgs)
        assert x.shape == (4, 16, 16, det.cfg.stage_channels[0])
        assert set(np.unique(np.asarray(x))) <= {0.0, 1.0}
        np.testing.assert_array_equal(np.asarray(det.stem(params, imgs[:1])),
                                      np.asarray(x[:1]))
        xt = det.stem(params, imgs[:1], mode="train")
        assert not np.array_equal(np.asarray(xt), np.asarray(x[:1]))

    def test_calibrate_bn_populates_stem_stats_both_designs(self):
        for scheme in ("ternary", "binary"):
            cfg = yolo_irc.smoke(scheme)
            det = IRCDetector(cfg)
            params = det.init(jax.random.PRNGKey(0))
            imgs = jax.random.uniform(jax.random.PRNGKey(1), (4, 32, 32, 3))
            cal = det.calibrate_bn(params, imgs)
            bn = cal["stem_bn"]
            assert float(jnp.max(jnp.abs(bn["mean"]))) > 0.0, scheme
            assert float(jnp.max(jnp.abs(bn["var"] - 1.0))) > 0.0, scheme

    def test_calibrate_bn_gamma_sign_invariance(self):
        """The in-memory BN fold is sign-preserving via |gamma| (train path
        and mapping); the calibration propagation must match, so flipping a
        block gamma's sign cannot change downstream calibrated stats."""
        cfg = yolo_irc.smoke("binary")
        det = IRCDetector(cfg)
        params = det.init(jax.random.PRNGKey(0))
        imgs = jax.random.uniform(jax.random.PRNGKey(1), (4, 32, 32, 3))
        # give block gammas mixed signs, then compare against |gamma|
        flipped = jax.tree.map(lambda x: x, params)
        for name in ("s0b0", "s1b0"):
            blk = dict(flipped[name])
            bn = dict(blk["bn"])
            sign = jnp.where(jnp.arange(bn["gamma"].shape[0]) % 2 == 0,
                             -1.0, 1.0)
            bn["gamma"] = bn["gamma"] * sign
            blk["bn"] = bn
            flipped[name] = blk
        cal_a = det.calibrate_bn(params, imgs)
        cal_b = det.calibrate_bn(flipped, imgs)
        for name in ("s0b0", "s1b0"):
            for stat in ("mean", "var"):
                np.testing.assert_array_equal(
                    np.asarray(cal_a[name]["bn"][stat]),
                    np.asarray(cal_b[name]["bn"][stat]), err_msg=name)
        # and the deployed eval path agrees too (|gamma| everywhere)
        key = jax.random.PRNGKey(5)
        out_a = det.apply(cal_a, imgs, mode="eval", key=key,
                          cfg_ni=NonidealConfig.all())
        out_b = det.apply(cal_b, imgs, mode="eval", key=key,
                          cfg_ni=NonidealConfig.all())
        np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))

    def test_qat_noise_fraction_follows_scheme(self):
        """The QAT surrogate's activated-LRS fraction must come from the
        quantized weights (binary -> ~1.0), not a hardcoded ternary 0.4."""
        cfg = DetectorConfig(img_hw=(16, 16), stage_channels=(60,),
                             blocks_per_stage=(1,), scheme="binary",
                             use_bn=False, n_anchors=2)
        det = IRCDetector(cfg)
        params = det.init(jax.random.PRNGKey(0))
        x = (jax.random.uniform(jax.random.PRNGKey(1), (2, 8, 8, 60))
             > 0.5).astype(jnp.float32)
        key = jax.random.PRNGKey(2)
        cfg_ni = NonidealConfig(device_variation=True)
        out = det._gconv(params["s0b0"], x, 60, 60, mode="train", key=key,
                         cfg_ni=cfg_ni)

        def reference(frac_fn):
            wq = det._gconv_weights(params["s0b0"], 60, 60)
            pre = jax.lax.conv_general_dilated(
                x, wq[..., 0], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            frac = frac_fn(wq)
            p_pair = (jnp.sum(x, axis=-1, keepdims=True) * frac
                      * 9.0 / 60 * det.cfg.group)   # exact op order of _gconv
            std = variation_noise_std(p_pair, det.spec.sigma_lrs)
            return binary_activation(
                pre + std * jax.random.normal(key, pre.shape))

        fixed = reference(lambda wq: jnp.mean(jnp.abs(wq)))   # == 1.0 here
        np.testing.assert_array_equal(np.asarray(out), np.asarray(fixed))
        buggy = reference(lambda wq: 0.4)                     # pre-PR value
        assert not np.array_equal(np.asarray(out), np.asarray(buggy))


class TestDetectorEnsemble:
    @pytest.mark.parametrize("scheme", ["ternary", "binary"])
    def test_bit_identity_vs_single_chip_eval(self, scheme):
        """fold_in key discipline: chip c of the ensemble path ==
        apply(mode="eval", key=fold_in(key, c)) bit-for-bit, both designs
        (ternary single-shot and binary partial-sum + in-memory BN)."""
        det, params = _detector(scheme)
        imgs = jax.random.uniform(jax.random.PRNGKey(2), (2, 32, 32, 3))
        key = jax.random.PRNGKey(21)
        cfg_ni = NonidealConfig.all()
        ens = build_detector_ensemble(key, det, params, 3, cfg=cfg_ni)
        out = det.apply(params, imgs, mode="ensemble", ensemble=ens,
                        cfg_ni=cfg_ni)
        assert out.shape[0] == 3
        for c in range(3):
            ref = det.apply(params, imgs, mode="eval",
                            key=jax.random.fold_in(key, c), cfg_ni=cfg_ni)
            np.testing.assert_array_equal(np.asarray(out[c]),
                                          np.asarray(ref))

    def test_ensemble_chips_distinct(self):
        det, params = _detector("ternary")
        ens = build_detector_ensemble(jax.random.PRNGKey(0), det, params, 2)
        g0 = ens.layers["s0b0"][0]
        assert float(jnp.max(jnp.abs(g0.ep[0] - g0.ep[1]))) > 0.0

    def test_evaluate_map_per_chip_shapes(self):
        data = SyntheticDetectionData(img_hw=(32, 32), stride=8)
        b = data.batch_for_step(0, batch=2)
        preds = np.asarray(jax.random.normal(jax.random.PRNGKey(0),
                                             (3, 2, 4, 4, 40)))
        vals = evaluate_map_per_chip(preds, b.boxes, b.classes, 5, 3)
        assert vals.shape == (3,) and vals.dtype == np.float32
        assert np.all((vals >= 0.0) & (vals <= 1.0))


class TestRunMcDetector:
    @pytest.mark.slow
    def test_population_map_stream(self):
        """Acceptance: >= 16 chips of the whole detector in a jitted chunk
        stream, mAP@0.5 mean/std/quantiles out, chunking invisible."""
        det, params = _detector("ternary")
        data = SyntheticDetectionData(img_hw=det.cfg.img_hw,
                                      stride=det.cfg.strides,
                                      n_classes=det.cfg.n_classes,
                                      n_anchors=det.cfg.n_anchors)
        b = data.batch_for_step(1000, 2)
        key = jax.random.PRNGKey(7)
        mc = McConfig(n_chips=16, chunk_size=16, cfg=NonidealConfig.all())
        res = run_mc_detector(key, det, params, b.images, b.boxes,
                              b.classes, mc=mc)
        m = res.metrics["map50"]
        assert res.n_chips == 16 and m["count"] == 16.0
        assert 0.0 <= m["mean"] <= 1.0 and m["std"] >= 0.0
        assert m["q05"] <= m["q50"] <= m["q95"]
        assert res.per_chip["map50"].shape == (16,)
        # chip c is keyed by fold_in(key, c) regardless of chunk layout
        res4 = run_mc_detector(key, det, params, b.images, b.boxes,
                               b.classes,
                               mc=dataclasses.replace(mc, chunk_size=4))
        np.testing.assert_array_equal(res.per_chip["map50"],
                                      res4.per_chip["map50"])

    @pytest.mark.slow
    def test_pipeline_bit_identical_to_serial(self):
        """The double-buffered pipeline (hoisted planes, in-trace sampling,
        next-chunk dispatch overlapping host mAP) must reproduce the serial
        loop's per-chip mAPs BIT-FOR-BIT — threefry sampling inside the
        fused chunk jit is bitwise-deterministic, so moving it in-trace and
        reordering dispatch against host work cannot change a single chip."""
        det, params = _detector("ternary")
        data = SyntheticDetectionData(img_hw=det.cfg.img_hw,
                                      stride=det.cfg.strides,
                                      n_classes=det.cfg.n_classes,
                                      n_anchors=det.cfg.n_anchors)
        b = data.batch_for_step(1000, 2)
        key = jax.random.PRNGKey(11)
        mc = McConfig(n_chips=6, chunk_size=2, cfg=NonidealConfig.all())
        res_p = run_mc_detector(key, det, params, b.images, b.boxes,
                                b.classes, mc=mc, pipeline=True)
        res_s = run_mc_detector(key, det, params, b.images, b.boxes,
                                b.classes, mc=mc, pipeline=False)
        np.testing.assert_array_equal(res_p.per_chip["map50"],
                                      res_s.per_chip["map50"])
        assert res_p.metrics["map50"] == res_s.metrics["map50"]
        # telemetry: both paths account the full loop body wall
        for r in (res_p, res_s):
            assert r.device_s >= 0.0 and r.host_s >= 0.0
            assert r.device_s + r.host_s <= r.wall_s + 1e-6

    @pytest.mark.slow
    def test_pipeline_early_stop_same_chunk_as_serial(self):
        """stderr_target early stop triggers at the same chunk boundary with
        identical surviving moments whether or not the next chunk was
        already dispatched (the pipeline only ever wastes the one inflight
        chunk, it never folds it in)."""
        det, params = _detector("ternary")
        data = SyntheticDetectionData(img_hw=det.cfg.img_hw,
                                      stride=det.cfg.strides,
                                      n_classes=det.cfg.n_classes,
                                      n_anchors=det.cfg.n_anchors)
        b = data.batch_for_step(1000, 2)
        key = jax.random.PRNGKey(11)
        mc = McConfig(n_chips=8, chunk_size=2, cfg=NonidealConfig.all())
        kw = dict(mc=mc, stderr_target=1e9)   # converges at first check
        res_p = run_mc_detector(key, det, params, b.images, b.boxes,
                                b.classes, pipeline=True, **kw)
        res_s = run_mc_detector(key, det, params, b.images, b.boxes,
                                b.classes, pipeline=False, **kw)
        assert res_p.n_chips == res_s.n_chips < 8
        np.testing.assert_array_equal(res_p.per_chip["map50"],
                                      res_s.per_chip["map50"])
        assert res_p.metrics["map50"] == res_s.metrics["map50"]

    @pytest.mark.slow
    def test_ablation_detector_runs_all_columns(self):
        det, params = _detector("ternary")
        data = SyntheticDetectionData(img_hw=det.cfg.img_hw,
                                      stride=det.cfg.strides,
                                      n_classes=det.cfg.n_classes,
                                      n_anchors=det.cfg.n_anchors)
        b = data.batch_for_step(1000, 2)
        res = run_ablation_detector(
            jax.random.PRNGKey(3), det, params, b.images, b.boxes,
            b.classes,
            ablations=(("ideal", NonidealConfig.none()),
                       ("all", NonidealConfig.all())),
            mc=McConfig(n_chips=4, chunk_size=4))
        assert set(res) == {"ideal", "all"}
        for r in res.values():
            assert r.per_chip["map50"].shape == (4,)
