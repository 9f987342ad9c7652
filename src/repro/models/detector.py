"""The paper's object-detection model (Fig. 11): YOLOv2-style backbone of
binary GROUP convolutions (group size 60) mapped onto IRC macros.

Two designs, matching the paper's ablation:
  * baseline: binary weights + in-memory BN + partial-sum accumulation
  * proposed: ternary weights (20/60/20), NO BN, single-shot accumulation,
    extra common-mode bias rows

Execution paths:
  * mode="train": differentiable QAT (STE quantizers + noise surrogate)
  * mode="eval":  full structural crossbar simulation per group (each group
    channel = one differential column pair; fan-in 3*3*60=540 cells + bias
    rows, exactly the paper's 636-cell mapping arithmetic)

First (stem) and last (head) layers are digital, as in the paper.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import nonideal as ni
from repro.core.crossbar import crossbar_forward
from repro.core.macro import MacroSpec, DEFAULT_MACRO
from repro.core.mapping import ternary_planes, binary_planes, fold_bn_to_bias_units
from repro.core.ternary import (ternary_quantize, binary_quantize,
                                binary_activation)
from repro.models.common import ParamSpec, materialize, logical_axes_tree

PyTree = Any


@dataclasses.dataclass(frozen=True)
class DetectorConfig:
    img_hw: Tuple[int, int] = (576, 1024)     # paper: 1024x576 (w x h)
    n_classes: int = 3                        # IVS 3cls
    n_anchors: int = 5
    group: int = 60                           # paper's group size
    # channel plan: stem -> stages (each stage = GConv blocks + downsample)
    stage_channels: Tuple[int, ...] = (60, 120, 240, 480)
    blocks_per_stage: Tuple[int, ...] = (1, 2, 2, 2)
    scheme: str = "ternary"                   # proposed | "binary" baseline
    use_bn: bool = False                      # baseline: in-memory BN
    accumulation: str = "single_shot"         # baseline: "partial_sum"
    bias_rows: int = 32
    partial_rows: int = 212                   # ~300uA limit at nominal V_WL
    dtype: Any = jnp.float32

    def __post_init__(self):
        # The PRNG layer_id lattice `s * 10 + b` (declared in
        # repro.analysis.keys.DECLARED_FOLD_LATTICES) is injective only
        # while every stage has fewer than 10 blocks; a deeper stage would
        # silently alias chip noise across layers.
        if any(nb >= 10 for nb in self.blocks_per_stage):
            raise ValueError(
                f"blocks_per_stage {self.blocks_per_stage} breaks the "
                f"s*10+b layer_id key lattice (needs every stage < 10 "
                f"blocks)")
        if len(self.blocks_per_stage) != len(self.stage_channels):
            raise ValueError(
                f"blocks_per_stage {self.blocks_per_stage} and "
                f"stage_channels {self.stage_channels} must align")

    @property
    def strides(self) -> int:
        return 2 ** (len(self.stage_channels) + 1)   # stem /2 + pools


class IRCDetector:
    """init/apply for the detector; `apply` returns raw head predictions
    [B, gh, gw, A*(5+C)]."""

    def __init__(self, cfg: DetectorConfig, spec: MacroSpec = DEFAULT_MACRO):
        self.cfg = cfg
        self.spec = spec

    def head_geometry(self) -> Tuple[int, int, int]:
        """(gh, gw, head_out) of `apply`'s raw predictions: the output grid
        after the stem + per-stage pools and the per-cell channel count
        `n_anchors * (5 + n_classes)`.  The serving engine, the shape
        contracts, and the decode helpers all derive prediction shapes from
        this one place."""
        cfg = self.cfg
        return (cfg.img_hw[0] // cfg.strides, cfg.img_hw[1] // cfg.strides,
                cfg.n_anchors * (5 + cfg.n_classes))

    # ------------------------------------------------------------ params
    def specs(self) -> Dict[str, PyTree]:
        cfg = self.cfg
        out: Dict[str, PyTree] = {
            # digital stem: 3x3 s2 conv to first stage width
            "stem": ParamSpec((3, 3, 3, cfg.stage_channels[0]),
                              (None, None, None, "mlp"), dtype=cfg.dtype),
            # stem BN carries running stats: eval mode must normalize with
            # CALIBRATION statistics (batch statistics at eval would make
            # outputs depend on batch composition — see `calibrate_bn`)
            "stem_bn": {"gamma": ParamSpec((cfg.stage_channels[0],), ("mlp",),
                                           init="ones", dtype=cfg.dtype),
                        "beta": ParamSpec((cfg.stage_channels[0],), ("mlp",),
                                          init="zeros", dtype=cfg.dtype),
                        "mean": ParamSpec((cfg.stage_channels[0],), ("mlp",),
                                          init="zeros", dtype=cfg.dtype),
                        "var": ParamSpec((cfg.stage_channels[0],), ("mlp",),
                                         init="ones", dtype=cfg.dtype)},
        }
        for s, (ch, nb) in enumerate(zip(cfg.stage_channels,
                                         cfg.blocks_per_stage)):
            c_in = cfg.stage_channels[max(0, s - 1)] if s else ch
            for b in range(nb):
                cin = c_in if b == 0 else ch
                blk: Dict[str, PyTree] = {
                    "w": ParamSpec((3 * 3 * cfg.group, cfg.group,
                                    max(cin, ch) // cfg.group),
                                   (None, "mlp", None), dtype=cfg.dtype),
                }
                if cfg.use_bn:
                    blk["bn"] = {
                        "gamma": ParamSpec((ch,), ("mlp",), init="ones",
                                           dtype=cfg.dtype),
                        "beta": ParamSpec((ch,), ("mlp",), init="zeros",
                                          dtype=cfg.dtype),
                        "mean": ParamSpec((ch,), ("mlp",), init="zeros",
                                          dtype=cfg.dtype),
                        "var": ParamSpec((ch,), ("mlp",), init="ones",
                                         dtype=cfg.dtype),
                    }
                out[f"s{s}b{b}"] = blk
        head_in = cfg.stage_channels[-1]
        out["head"] = ParamSpec(
            (1 * 1 * head_in, cfg.n_anchors * (5 + cfg.n_classes)),
            (None, "mlp"), dtype=cfg.dtype)
        out["head_b"] = ParamSpec((cfg.n_anchors * (5 + cfg.n_classes),),
                                  ("mlp",), init="zeros", dtype=cfg.dtype)
        return out

    def init(self, key: jax.Array) -> PyTree:
        return materialize(key, self.specs())

    def logical_axes(self) -> PyTree:
        return logical_axes_tree(self.specs())

    # ------------------------------------------------------------ blocks
    def _gconv_weights(self, blk: PyTree, cin: int, cout: int) -> jax.Array:
        """Per-group latent weights [(g) 540, group, n_groups] -> quantized
        full conv kernel [3,3,cin,cout] (block-diagonal across groups)."""
        cfg = self.cfg
        w = blk["w"]                         # [540, group, n_groups]
        n_groups = cout // cfg.group
        if cfg.scheme == "ternary":
            wq = ternary_quantize(w, axis=(0, 1))
        else:
            wq = binary_quantize(w)
        # assemble block-diagonal grouped kernel
        wq = wq.reshape(3, 3, cfg.group, cfg.group, n_groups)
        return wq

    def _gconv_pre(self, blk: PyTree, x4: jax.Array, cin: int, cout: int
                   ) -> Tuple[jax.Array, jax.Array]:
        """Differentiable QAT pre-activation shared by the single-draw and
        ensemble train paths: quantized grouped conv + (baseline) BN with
        the sign-preserving |gamma| fold.  [N,H,W,cin] -> ([N,H,W,cout],
        quantized kernel [3,3,g,g,ng]); the ensemble path folds its chips
        axis into N before calling."""
        cfg = self.cfg
        n_groups = cout // cfg.group
        wq = self._gconv_weights(blk, cin, cout)       # [3,3,g,g,ng]
        xg = x4.reshape(x4.shape[:-1] + (n_groups, cfg.group))
        outs = [jax.lax.conv_general_dilated(
            xg[..., g, :], wq[..., g], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
            for g in range(n_groups)]
        pre = jnp.concatenate(outs, axis=-1)           # [N,H,W,cout]
        if cfg.use_bn:
            bn = blk["bn"]
            mu = jnp.mean(pre, axis=(0, 1, 2))
            var = jnp.var(pre, axis=(0, 1, 2))
            # |gamma|: the in-memory BN fold (Fig. 13a) is only
            # sign-preserving for positive gamma, so the baseline QAT
            # constrains it (standard BNN-BN folding practice)
            pre = (jnp.abs(bn["gamma"]) * (pre - mu)
                   / jnp.sqrt(var + 1e-5) + bn["beta"])
        return pre, wq

    def _gconv(self, blk: PyTree, x: jax.Array, cin: int, cout: int, *,
               mode: str, key: jax.Array, cfg_ni: ni.NonidealConfig,
               sa_extra: float = 0.0, device=None) -> jax.Array:
        """Binary group conv + (baseline) BN + binary activation."""
        cfg = self.cfg
        # inputs are {0,1} activations from the previous layer
        if mode == "train":
            pre, wq = self._gconv_pre(blk, x, cin, cout)
            if cfg_ni.any():
                # QAT noise surrogate at the pre-activation level.  The
                # activated-LRS fraction comes from the quantized weights
                # (ternary 20/60/20 -> ~0.4, binary -> ~1.0), as in
                # `irc_linear_train`: the baseline's differential pairs are
                # ~100% LRS-active, so a hardcoded ternary fraction would
                # understate its p_pair.
                lrs_frac = jnp.mean(jnp.abs(jax.lax.stop_gradient(wq)))
                p_pair = jnp.sum(jax.lax.stop_gradient(x), axis=-1,
                                 keepdims=True) * lrs_frac * 9.0 / cin * cfg.group
                std = 0.0
                if cfg_ni.device_variation:
                    from repro.core.crossbar import variation_noise_std
                    std = std + variation_noise_std(p_pair, self.spec.sigma_lrs)
                if cfg_ni.sa_variation:
                    std = std + 0.5 * ni.sa_required_diff(p_pair, self.spec)
                if cfg_ni.device_variation or cfg_ni.sa_variation:
                    pre = pre + std * jax.random.normal(key, pre.shape)
            return binary_activation(pre)
        return self._gconv_structural(blk, x, cin, cout, key=key,
                                      cfg_ni=cfg_ni, sa_extra=sa_extra,
                                      device=device)

    def group_mappings(self, blk: PyTree, cin: int, cout: int) -> List:
        """Per-group `MappedLayer`s of one block (static per deployment).

        Shared by the single-chip structural path and the chip-ensemble
        builder (`repro.mc.detector_mc`): im2col row order is spatial-major,
        rows = (9, group), plus the scheme's bias / in-memory-BN rows.
        """
        cfg, spec = self.cfg, self.spec
        n_groups = cout // cfg.group
        wq = jax.lax.stop_gradient(self._gconv_weights(blk, cin, cout))
        wq = wq.reshape(9, cfg.group, cfg.group, n_groups)
        mappeds = []
        for g in range(n_groups):
            w_flat = wq[..., g].reshape(9 * cfg.group, cfg.group)
            if cfg.scheme == "ternary":
                mapped = ternary_planes(w_flat, bias_rows=cfg.bias_rows)
            else:
                bn_units = None
                if cfg.use_bn:
                    bn = blk["bn"]
                    sl = slice(g * cfg.group, (g + 1) * cfg.group)
                    bn_units = fold_bn_to_bias_units(
                        jnp.abs(bn["gamma"][sl]), bn["beta"][sl],
                        bn["mean"][sl], bn["var"][sl])
                mapped = binary_planes(w_flat, bn_bias_units=bn_units,
                                       spec=spec)
            mappeds.append(mapped)
        return mappeds

    def _im2col_groups(self, x: jax.Array, cin: int, n_groups: int
                       ) -> jax.Array:
        """[..., H, W, cin] {0,1} activations -> [..., H, W, n_groups,
        9*group] word-line patterns (spatial-major rows, matching
        `group_mappings`).  Leading dims beyond the batch (e.g. a chips
        axis) pass through untouched."""
        cfg = self.cfg
        lead = x.shape[:-3]
        H, W = x.shape[-3:-1]
        flat = x.reshape((-1,) + x.shape[-3:])
        patches = jax.lax.conv_general_dilated_patches(
            flat, (3, 3), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))   # [N,H,W,cin*9]
        patches = patches.reshape(lead + (H, W, cin, 9))
        xg = patches.reshape(lead + (H, W, n_groups, cfg.group, 9))
        return jnp.swapaxes(xg, -1, -2).reshape(
            lead + (H, W, n_groups, 9 * cfg.group))

    def _gconv_structural(self, blk: PyTree, x: jax.Array, cin: int,
                          cout: int, *, key: jax.Array,
                          cfg_ni: ni.NonidealConfig,
                          sa_extra: float = 0.0, device=None) -> jax.Array:
        """Full crossbar sim: im2col per group -> mapped planes -> SA bits."""
        cfg, spec = self.cfg, self.spec
        n_groups = cout // cfg.group
        B, H, W, _ = x.shape
        xg = self._im2col_groups(x, cin, n_groups)     # [B,H,W,ng,540]
        outs = []
        for g, mapped in enumerate(self.group_mappings(blk, cin, cout)):
            out = crossbar_forward(jax.random.fold_in(key, g),
                                   xg[..., g, :].reshape(B * H * W, -1),
                                   mapped, cfg=cfg_ni, spec=spec,
                                   accumulation=cfg.accumulation,
                                   partial_rows=cfg.partial_rows,
                                   sa_extra_units=sa_extra, device=device)
            outs.append(out.reshape(B, H, W, cfg.group))
        return jnp.concatenate(outs, axis=-1)

    def _gconv_ensemble(self, groups, x: jax.Array, cin: int, cout: int, *,
                        cfg_ni: ni.NonidealConfig,
                        sa_extra: float = 0.0,
                        output: str = "binary",
                        use_kernel: Optional[bool] = None,
                        kernel_impl: str = "pallas", device=None) -> jax.Array:
        """Ensemble-mode group conv: one vmapped `ensemble_apply` per group
        services every chip of a `DetectorEnsemble` layer.

        x is [B,H,W,cin] (chip-shared input — the first IRC layer; the
        chip-shared activated-LRS counts hoist out of the chips vmap) or
        [chips,B,H,W,cin] (chip-diverged activations downstream).  Returns
        [chips,B,H,W,cout]; chip `c` is bit-identical to the single-chip
        structural path with the corresponding folded key.

        `output` passes through to `ensemble_apply`: "binary" (eval-mode SA
        decisions) or "diff" (raw analog difference — how the train-ensemble
        path turns deviation planes into per-chip pre-activation errors).

        `use_kernel` routes the grouped im2col matmuls onto the fused
        chip-batched Pallas kernel (`ensemble_apply_kernel`, bit-identical
        on the binary/all-effects-off contracts pinned by
        tests/test_kernel_detector.py).  None (default) consults the
        committed autotuning table: the kernel runs only on geometries where
        a sweep on this backend measured it faster (single-shot accumulation
        only — the kernel's fused epilogue).  Forcing True with another
        accumulation mode raises.  `kernel_impl="ref"` swaps in the kernel's
        jnp oracle (interpret-free CI coverage of the routed path).
        """
        from repro.mc.engine import ensemble_apply, ensemble_apply_kernel
        from repro.kernels import autotune
        cfg = self.cfg
        n_groups = cout // cfg.group
        per_chip = x.ndim == 5
        B, H, W = x.shape[-4], x.shape[-3], x.shape[-2]
        xg = self._im2col_groups(x, cin, n_groups)
        if use_kernel and cfg.accumulation != "single_shot":
            raise ValueError(
                "use_kernel=True requires single_shot accumulation (fused "
                f"kernel epilogue); got {cfg.accumulation!r}")
        outs = []
        for g, ens in enumerate(groups):
            x_bits = xg[..., g, :].reshape(
                (x.shape[0], -1, 9 * cfg.group) if per_chip
                else (-1, 9 * cfg.group))
            route = use_kernel
            if route is None:
                # the kernel's fused epilogue bakes the ANALYTIC periphery;
                # auto-routing never picks it for a backend with its own
                route = (cfg.accumulation == "single_shot"
                         and (device is None or device.analytic_periphery)
                         and autotune.kernel_wins(ens.n_chips,
                                                  x_bits.shape[-2],
                                                  ens.n_out, ens.rows))
            if route:
                bm, bn, bk = autotune.best_blocks(ens.n_chips,
                                                  x_bits.shape[-2],
                                                  ens.n_out, ens.rows)
                out = ensemble_apply_kernel(ens, x_bits, cfg=cfg_ni,
                                            spec=self.spec,
                                            sa_extra_units=sa_extra,
                                            output=output,
                                            per_chip_x=per_chip,
                                            impl=kernel_impl,
                                            bm=bm, bn=bn, bk=bk,
                                            device=device)
            else:
                out = ensemble_apply(ens, x_bits, cfg=cfg_ni, spec=self.spec,
                                     accumulation=cfg.accumulation,
                                     partial_rows=cfg.partial_rows,
                                     sa_extra_units=sa_extra,
                                     output=output,
                                     per_chip_x=per_chip, device=device)
            outs.append(out.reshape(out.shape[0], B, H, W, cfg.group))
        return jnp.concatenate(outs, axis=-1)

    def _gconv_train_ensemble(self, blk: PyTree, groups, x: jax.Array,
                              cin: int, cout: int, *, key: jax.Array,
                              cfg_ni: ni.NonidealConfig,
                              use_kernel: Optional[bool] = None,
                              kernel_impl: str = "pallas",
                              device=None) -> jax.Array:
        """Ensemble-aware QAT group conv (paper Sec. V at population scale).

        The differentiable `mode="train"` pre-activation — chips axis folded
        into the batch so ONE conv serves every chip — plus, per chip of the
        pre-sampled deviation population (`repro.mc.build_train_ensemble`):

          * the chip's FROZEN linear device-variation error, computed by the
            shared ensemble machinery on (effective - nominal) conductance
            deltas (`output="diff"`, no stochastic terms) and added under
            stop_gradient exactly like the legacy noise surrogate;
          * a fresh per-read SA-offset draw (std 0.5*g(p_pair)) keyed
            `fold_in(block_key, chip_id)` so a chip's slice is invariant to
            the ensemble it is evaluated in.

        x is [B,H,W,cin] (chip-shared) or [chips,B,H,W,cin] downstream;
        returns [chips,B,H,W,cout] binary activations.
        """
        cfg = self.cfg
        n_chips = groups[0].n_chips
        xf = x.reshape((-1,) + x.shape[-3:])           # fold chips into batch
        pre, wq = self._gconv_pre(blk, xf, cin, cout)
        pre = pre.reshape(x.shape[:-1] + (cout,))
        if cfg_ni.device_variation:
            dev = self._gconv_ensemble(groups, x, cin, cout,
                                       cfg_ni=ni.NonidealConfig.none(),
                                       output="diff",
                                       use_kernel=use_kernel,
                                       kernel_impl=kernel_impl,
                                       device=device)
            pre = pre + jax.lax.stop_gradient(dev)     # adds the chips axis
        if pre.ndim == 4:                              # no variation term:
            pre = jnp.broadcast_to(pre[None], (n_chips,) + pre.shape)
        if cfg_ni.sa_variation:
            lrs_frac = jnp.mean(jnp.abs(jax.lax.stop_gradient(wq)))
            p_pair = jnp.sum(jax.lax.stop_gradient(x), axis=-1,
                             keepdims=True) * lrs_frac * 9.0 / cin * cfg.group
            std = 0.5 * ni.sa_required_diff(p_pair, self.spec)
            eps = jax.vmap(lambda c: jax.random.normal(
                jax.random.fold_in(key, c), pre.shape[1:]))(
                groups[0].chip_ids)
            pre = pre + std * eps
        return binary_activation(pre)

    # ------------------------------------------------------------ BN calib
    def calibrate_bn(self, params: PyTree, images: jax.Array,
                     key: Optional[jax.Array] = None) -> PyTree:
        """Populate BN running stats from a calibration batch.

        BOTH designs need the digital stem's running stats: eval mode
        normalizes with them (batch statistics at eval would tie outputs to
        batch composition).  The baseline additionally stores each block's
        in-memory BN stats, which `binary_planes` folds into bias cells at
        deployment; the block propagation uses |gamma|, matching the
        sign-preserving fold of the train path and the mapping.
        """
        cfg = self.cfg
        params = jax.tree.map(lambda x: x, params)  # shallow copy
        x = jax.lax.conv_general_dilated(
            images.astype(cfg.dtype), params["stem"], (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        bn = dict(params["stem_bn"])
        mu, var = jnp.mean(x, (0, 1, 2)), jnp.var(x, (0, 1, 2))
        bn["mean"], bn["var"] = mu, var
        params["stem_bn"] = bn
        if not cfg.use_bn:
            return params
        x = binary_activation(bn["gamma"] * (x - mu) / jnp.sqrt(var + 1e-5)
                              + bn["beta"])
        for s, (ch, nb) in enumerate(zip(cfg.stage_channels,
                                         cfg.blocks_per_stage)):
            c_in = cfg.stage_channels[max(0, s - 1)] if s else ch
            for b in range(nb):
                cin = c_in if b == 0 else ch
                if cin < ch:
                    x = jnp.concatenate([x] * (ch // cin), axis=-1)
                    cin = ch
                blk = dict(params[f"s{s}b{b}"])
                wq = self._gconv_weights(blk, cin, ch)
                xg = x.reshape(x.shape[:-1] + (ch // cfg.group, cfg.group))
                outs = [jax.lax.conv_general_dilated(
                    xg[..., g, :], wq[..., g], (1, 1), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
                    for g in range(ch // cfg.group)]
                pre = jnp.concatenate(outs, axis=-1)
                mu, var = jnp.mean(pre, (0, 1, 2)), jnp.var(pre, (0, 1, 2))
                bnp = dict(blk["bn"])
                bnp["mean"], bnp["var"] = mu, var
                blk["bn"] = bnp
                params[f"s{s}b{b}"] = blk
                pre = (jnp.abs(bnp["gamma"]) * (pre - mu)
                       / jnp.sqrt(var + 1e-5) + bnp["beta"])
                x = binary_activation(pre)
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "SAME")
        return params

    # ------------------------------------------------------------ forward
    def stem(self, params: PyTree, images: jax.Array, *,
             mode: str = "eval") -> jax.Array:
        """Digital stem: 3x3/2 conv + BN + binary activation -> the {0,1}
        [B, H/2, W/2, stage_channels[0]] input of the first IRC layer."""
        cfg = self.cfg
        x = jax.lax.conv_general_dilated(
            images.astype(cfg.dtype), params["stem"], (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        bn = params["stem_bn"]
        if mode in ("train", "train_ensemble"):
            mu = jnp.mean(x, axis=(0, 1, 2))
            var = jnp.var(x, axis=(0, 1, 2))
        else:
            # eval/ensemble: running stats from `calibrate_bn` — batch
            # statistics here would make deployed outputs depend on batch
            # composition (and MC chunking would change the metric)
            mu, var = bn["mean"], bn["var"]
        x = bn["gamma"] * (x - mu) / jnp.sqrt(var + 1e-5) + bn["beta"]
        return binary_activation(x)

    def apply(self, params: PyTree, images: jax.Array, *, mode: str = "train",
              key: Optional[jax.Array] = None,
              cfg_ni: ni.NonidealConfig = ni.NonidealConfig.none(),
              sa_extra: float = 0.0, ensemble=None,
              use_kernel: Optional[bool] = None,
              kernel_impl: str = "pallas", device=None) -> jax.Array:
        """images [B,H,W,3] in [0,1] -> head predictions [B,gh,gw,A*(5+C)].

        mode="train": differentiable QAT; mode="eval": single-chip structural
        sim (chip identity = `key`); mode="ensemble": every chip of a
        pre-sampled `repro.mc.DetectorEnsemble` at once — returns
        [chips,B,gh,gw,A*(5+C)], chip `c` bit-identical to mode="eval" with
        key `fold_in(base_key, c)`; mode="train_ensemble": differentiable
        ensemble-aware QAT — `ensemble` carries DEVIATION planes
        (`repro.mc.build_train_ensemble`) and the returned
        [chips,B,gh,gw,A*(5+C)] predictions see each chip's frozen variation
        error plus fresh per-read SA noise (chips folded into the batch by
        the loss).

        `use_kernel`/`kernel_impl` (ensemble modes only) control the
        Pallas-kernel routing of the grouped crossbar matmuls — see
        `_gconv_ensemble`; None defers to the committed autotuning table.

        `device` is the `repro.device` backend for the structural/ensemble
        periphery terms (None: analytic); an ensemble's PLANES already carry
        the backend they were sampled with, so pass the same backend here.
        The `mode="train"` noise surrogate stays analytic by design — it is
        a calibrated QAT proxy, not a physics path.

        The stem, each block `s{s}b{b}` (with its widening concat), each
        stage's max pool `s{s}pool` and the head run under `jax.named_scope`s
        of those names, which a device trace carries in each op's `tf_op`.
        """
        cfg = self.cfg
        key = key if key is not None else jax.random.PRNGKey(0)
        with jax.named_scope("stem"):
            x = self.stem(params, images, mode=mode)

        for s, (ch, nb) in enumerate(zip(cfg.stage_channels,
                                         cfg.blocks_per_stage)):
            c_in = cfg.stage_channels[max(0, s - 1)] if s else ch
            for b in range(nb):
                name = f"s{s}b{b}"
                cin = c_in if b == 0 else ch
                with jax.named_scope(name):
                    if cin < ch:   # widen by repetition before the block
                        x = jnp.concatenate([x] * (ch // cin), axis=-1)
                        cin = ch
                    if mode == "ensemble":
                        x = self._gconv_ensemble(
                            ensemble.layers[name], x, cin, ch,
                            cfg_ni=cfg_ni, sa_extra=sa_extra,
                            use_kernel=use_kernel, kernel_impl=kernel_impl,
                            device=device)
                    elif mode == "train_ensemble":
                        x = self._gconv_train_ensemble(
                            params[name], ensemble.layers[name], x, cin, ch,
                            key=jax.random.fold_in(key, s * 10 + b),
                            cfg_ni=cfg_ni, use_kernel=use_kernel,
                            kernel_impl=kernel_impl, device=device)
                    else:
                        x = self._gconv(params[name], x, cin, ch, mode=mode,
                                        key=jax.random.fold_in(key,
                                                               s * 10 + b),
                                        cfg_ni=cfg_ni, sa_extra=sa_extra,
                                        device=device)
            with jax.named_scope(f"s{s}pool"):
                wd = (1,) * (x.ndim - 3) + (2, 2, 1)
                x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, wd, wd,
                                          "SAME")
        with jax.named_scope("head"):
            return x @ params["head"] + params["head_b"]
