"""Tests for quantizers, mapping, crossbar forward, and calibration
(paper Secs. IV-B, Table I) — the system invariants the paper argues for."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MacroSpec, NonidealConfig,
                        ternary_quantize, binary_quantize, binary_activation,
                        ternary_fractions, ternary_planes, binary_planes,
                        extend_inputs, fold_bn_to_bias_units,
                        crossbar_forward, ideal_ternary_matmul,
                        IRCLinear, IRCLinearConfig,
                        calibrate_bias, sa_error_rates, layer_current_stats,
                        DEFAULT_MACRO, apply_nonlinearity, ir_drop_factors)
from repro.core.crossbar import _accumulate
from repro.device import AnalyticDeviceModel


class TestQuantizers:
    def test_ternary_fractions_regulated(self):
        # paper Sec. IV-B.1: 20/60/20 distribution regulation
        w = jax.random.normal(jax.random.PRNGKey(0), (4096,))
        f = ternary_fractions(ternary_quantize(w))
        np.testing.assert_allclose(np.asarray(f), [0.2, 0.6, 0.2], atol=0.01)

    def test_ternary_grouped_axis(self):
        w = jax.random.normal(jax.random.PRNGKey(1), (8, 512))
        wt = ternary_quantize(w, axis=(1,))
        for g in range(8):
            f = ternary_fractions(wt[g])
            np.testing.assert_allclose(np.asarray(f), [0.2, 0.6, 0.2], atol=0.02)

    def test_ste_gradients_flow(self):
        w = jax.random.normal(jax.random.PRNGKey(2), (64, 8))
        x = jax.random.normal(jax.random.PRNGKey(3), (4, 64))
        def loss(w):
            return jnp.sum(x @ ternary_quantize(w))
        g = jax.grad(loss)(w)
        assert float(jnp.sum(jnp.abs(g))) > 0.0
        # clipped STE: no gradient far outside [-1, 1]
        g2 = jax.grad(lambda w: jnp.sum(ternary_quantize(w)))(jnp.full((4,), 5.0))
        np.testing.assert_allclose(np.asarray(g2), 0.0)

    def test_binary_activation_range(self):
        x = jnp.array([-2.0, -0.1, 0.0, 0.1, 2.0])
        np.testing.assert_allclose(np.asarray(binary_activation(x)),
                                   [0, 0, 0, 1, 1])


class TestMapping:
    def test_ternary_plane_semantics(self):
        w = jnp.array([[1.0], [-1.0], [0.0]])
        m = ternary_planes(w)
        np.testing.assert_allclose(np.asarray(m.g_pos[:, 0]), [1, 0, 0])
        np.testing.assert_allclose(np.asarray(m.g_neg[:, 0]), [0, 1, 0])

    def test_bias_rows_common_mode(self):
        # bias rows are LRS on BOTH planes -> differential unchanged
        w = ternary_quantize(jax.random.normal(jax.random.PRNGKey(0), (128, 16)))
        x = (jax.random.uniform(jax.random.PRNGKey(1), (4, 128)) > 0.5
             ).astype(jnp.float32)
        d0 = crossbar_forward(jax.random.PRNGKey(2), x, ternary_planes(w, 0),
                              output="diff")
        d32 = crossbar_forward(jax.random.PRNGKey(2), x, ternary_planes(w, 32),
                               output="diff")
        np.testing.assert_allclose(np.asarray(d0), np.asarray(d32), atol=0.02)

    def test_binary_reference_line_current(self):
        # reference bit-line carries ~p/2 for p activated rows
        w = binary_quantize(jax.random.normal(jax.random.PRNGKey(0), (512, 4)))
        m = binary_planes(w)
        x = jnp.ones((1, 512))
        ref_current = x @ m.g_neg
        np.testing.assert_allclose(np.asarray(ref_current), 256.0)

    def test_binary_mapping_computes_sign(self):
        w = binary_quantize(jax.random.normal(jax.random.PRNGKey(3), (256, 8)))
        x = (jax.random.uniform(jax.random.PRNGKey(4), (16, 256)) > 0.5
             ).astype(jnp.float32)
        out = crossbar_forward(jax.random.PRNGKey(5), x, binary_planes(w))
        # sign(I_conv - I_ref) == sign(x @ w) when x@w != 0
        ref = x @ w
        mask = jnp.abs(ref) > 1.0
        agree = jnp.mean((out > 0.5) == (ref > 0), where=mask)
        assert float(agree) > 0.99

    def test_bn_folding_matches_bn_sign(self):
        key = jax.random.PRNGKey(6)
        y = jax.random.normal(key, (1000,)) * 10
        gamma, beta = jnp.array(2.0), jnp.array(1.5)
        mean, var = jnp.array(3.0), jnp.array(4.0)
        bn_out = gamma * (y - mean) / jnp.sqrt(var + 1e-5) + beta
        bias = fold_bn_to_bias_units(gamma, beta, mean, var)
        np.testing.assert_array_equal(np.asarray(bn_out > 0),
                                      np.asarray(y + bias > 0))

    def test_extend_inputs_prepends_ones(self):
        w = jnp.zeros((8, 2))
        m = ternary_planes(w, bias_rows=4)
        x = jnp.zeros((3, 8))
        xe = extend_inputs(x, m)
        assert xe.shape == (3, 12)
        np.testing.assert_allclose(np.asarray(xe[:, :4]), 1.0)


class TestCrossbarForward:
    def _setup(self, fan_in=540, n_out=32, seed=0):
        w = ternary_quantize(jax.random.normal(jax.random.PRNGKey(seed),
                                               (fan_in, n_out)))
        x = (jax.random.uniform(jax.random.PRNGKey(seed + 1),
                                (8, fan_in)) > 0.5).astype(jnp.float32)
        return w, x

    def test_ideal_matches_matmul(self):
        w, x = self._setup()
        d = crossbar_forward(jax.random.PRNGKey(2), x, ternary_planes(w),
                             output="diff")
        np.testing.assert_allclose(np.asarray(d),
                                   np.asarray(ideal_ternary_matmul(x, w)),
                                   atol=0.05)

    def test_single_shot_nonlinearity_sign_invariant(self):
        # Sec. IV-B.3: with one-shot accumulation the (monotone)
        # nonlinearity cancels in the differential comparison
        w, x = self._setup()
        ref = ideal_ternary_matmul(x, w)
        d = crossbar_forward(jax.random.PRNGKey(2), x, ternary_planes(w, 32),
                             cfg=NonidealConfig(nonlinearity=True),
                             accumulation="single_shot", output="diff")
        mask = jnp.abs(ref) > 2.0  # away from the fit's junction glitch
        assert float(jnp.mean((d > 0) == (ref > 0), where=mask)) > 0.995

    def test_partial_sum_current_inflated(self):
        # Fig. 8(a): external accumulation of partial sums inflates current
        w, x = self._setup()
        kwargs = dict(cfg=NonidealConfig(nonlinearity=True), output="diff")
        i_ss = crossbar_forward(jax.random.PRNGKey(2), x, ternary_planes(w),
                                accumulation="single_shot", **kwargs)
        # compare accumulated POSITIVE line current via diff vs all-pos weights
        w_pos = jnp.abs(w)
        i_ss_pos = crossbar_forward(jax.random.PRNGKey(2), x,
                                    ternary_planes(w_pos),
                                    accumulation="single_shot", **kwargs)
        i_ps_pos = crossbar_forward(jax.random.PRNGKey(2), x,
                                    ternary_planes(w_pos),
                                    accumulation="partial_sum", **kwargs)
        assert float(jnp.mean(i_ps_pos)) > float(jnp.mean(i_ss_pos)) * 1.1

    def test_device_variation_changes_results_mildly(self):
        w, x = self._setup()
        ref = ideal_ternary_matmul(x, w)
        out = crossbar_forward(jax.random.PRNGKey(7), x, ternary_planes(w, 32),
                               cfg=NonidealConfig(device_variation=True))
        agree = float(jnp.mean((out > 0.5) == (ref > 0)))
        assert 0.6 < agree < 1.0

    def test_binary_output_values(self):
        w, x = self._setup()
        out = crossbar_forward(jax.random.PRNGKey(2), x, ternary_planes(w, 32),
                               cfg=NonidealConfig.all())
        assert set(np.unique(np.asarray(out))) <= {0.0, 1.0}

    def test_deterministic_given_key(self):
        w, x = self._setup()
        a = crossbar_forward(jax.random.PRNGKey(9), x, ternary_planes(w, 32),
                             cfg=NonidealConfig.all())
        b = crossbar_forward(jax.random.PRNGKey(9), x, ternary_planes(w, 32),
                             cfg=NonidealConfig.all())
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _paper_blocks(nb: int, seed: int = 0):
    """Block currents and LRS counts of a few bit-lines at the paper's
    scale: up to 32 activated cells per 32-row block under log-normal
    variation, so a few to a few hundred units per line."""
    k_c, k_v = jax.random.split(jax.random.PRNGKey(seed))
    counts = jax.random.randint(k_c, (3, 16, nb, 60), 0, 33
                                ).astype(jnp.float32)
    blocks = counts * jnp.exp(DEFAULT_MACRO.sigma_lrs
                              * jax.random.normal(k_v, counts.shape))
    return blocks, counts


def _factor_path(blocks, counts, cfg, accumulation, partial_rows):
    """The line current by the definition: blocks weighted by
    `ir_drop_factors`, summed per partial-sum chunk or over the line."""
    nb = blocks.shape[-2]
    dropped = blocks * ir_drop_factors(blocks, DEFAULT_MACRO.ir_alpha,
                                       axis=-2)
    k = nb if accumulation == "single_shot" else (
        partial_rows // DEFAULT_MACRO.ir_block)
    total = 0.0
    for lo in range(0, nb, k):
        i = jnp.sum(dropped[..., lo:lo + k, :], axis=-2)
        if cfg.nonlinearity:
            i = apply_nonlinearity(i, jnp.sum(counts[..., lo:lo + k, :],
                                              axis=-2))
        total = total + i
    return total


class TestIrDropAccumulation:
    @pytest.mark.parametrize("nb", [18, 20])
    @pytest.mark.parametrize("nonlinearity", [False, True],
                             ids=["linear", "nonlinear"])
    @pytest.mark.parametrize("accumulation", ["single_shot", "partial_sum"])
    def test_one_pass_equals_factor_path(self, accumulation, nonlinearity,
                                         nb):
        """The one-pass recurrence gives the factor path's line current to
        float32 rounding; at nb 20 and 212-row chunks the last chunk holds
        2 blocks of 6."""
        blocks, counts = _paper_blocks(nb)
        cfg = NonidealConfig(ir_drop=True, nonlinearity=nonlinearity)
        i_line, p_total = _accumulate(blocks, counts, cfg, DEFAULT_MACRO,
                                      accumulation, 212)
        want = _factor_path(blocks, counts, cfg, accumulation, 212)
        assert float(jnp.max(want)) > 100.0
        np.testing.assert_allclose(np.asarray(i_line), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(p_total),
                                      np.asarray(jnp.sum(counts, axis=-2)))

    def test_backend_with_own_periphery_keeps_its_hook(self):
        """A backend that clears `analytic_periphery` and brings its own
        IR-drop factors is weighted by them, not by the analytic pass."""
        calls = []

        @dataclasses.dataclass(frozen=True)
        class HalfDrop(AnalyticDeviceModel):
            name = "half-drop"

            @property
            def analytic_periphery(self):
                return False

            def ir_drop_factors(self, block_currents, spec=DEFAULT_MACRO,
                                axis=-1):
                calls.append(axis)
                return jnp.full_like(block_currents, 0.5)

        blocks, counts = _paper_blocks(18, seed=1)
        i_line, _ = _accumulate(blocks, counts, NonidealConfig(ir_drop=True),
                                DEFAULT_MACRO, "single_shot", 256,
                                device=HalfDrop())
        assert calls == [-2]
        np.testing.assert_allclose(np.asarray(i_line),
                                   np.asarray(0.5 * jnp.sum(blocks, axis=-2)),
                                   rtol=1e-6)

    @pytest.mark.parametrize("accumulation,rows", [("single_shot", 572),
                                                   ("partial_sum", 636)])
    def test_no_block_square_dot(self, accumulation, rows):
        """At the paper's stage-0 shapes (4 dies x 147456 positions x 60
        columns) the IR-dropped accumulation lowers to elementwise work:
        no dot takes an [nb, nb] operand."""
        nb = -(-rows // DEFAULT_MACRO.ir_block)
        f32 = lambda *d: jax.ShapeDtypeStruct(d, jnp.float32)
        acc = jax.jit(lambda b, c: _accumulate(
            b, c, NonidealConfig.all(), DEFAULT_MACRO, accumulation, 212))
        text = acc.lower(f32(4, 147456, nb, 60),
                         f32(4, 147456, nb, 60)).as_text(debug_info=True)
        square = re.compile(rf"tensor<{nb}x{nb}xf32>")
        dots = [ln for ln in text.splitlines() if "dot_general" in ln]
        assert not [ln for ln in dots if square.search(ln)]
        assert "ir_drop" in text


class TestCalibration:
    def _stats(self, n=4000, diff_std=8.0, p_base=20.0, seed=0):
        key = jax.random.PRNGKey(seed)
        k1, k2 = jax.random.split(key)
        # near-symmetric current pairs around a LOW common mode (the paper's
        # Table I situation: symmetric conv data, currents near the floor)
        common = p_base + jax.random.uniform(k1, (n,)) * 10.0
        diff = diff_std * jax.random.normal(k2, (n,))
        i_pos = common + 0.5 * diff
        i_neg = common - 0.5 * diff
        return i_pos, i_neg, i_pos + i_neg

    def test_bias_reduces_lower_bound_failures(self):
        i_pos, i_neg, p = self._stats()
        r0 = sa_error_rates(i_pos, i_neg, p, 0.0)
        r32 = sa_error_rates(i_pos, i_neg, p, 32.0)
        assert float(r32["below_lower_bound"]) < float(r0["below_lower_bound"])
        assert float(r0["below_lower_bound"]) > 0.5  # catastrophic w/o bias

    def test_bias_increases_sa_variation_errors(self):
        # Table I: the trade-off direction — bias slightly raises variation errors
        i_pos, i_neg, p = self._stats()
        r0 = sa_error_rates(i_pos, i_neg, p, 0.0)
        r32 = sa_error_rates(i_pos, i_neg, p, 32.0)
        assert float(r32["sensing_variation"]) >= float(r0["sensing_variation"])

    def test_calibrate_picks_nonzero_bias_when_needed(self):
        i_pos, i_neg, p = self._stats()
        best, report = calibrate_bias(i_pos, i_neg, p)
        assert best > 0
        assert report[best]["total"] < report[0]["total"]

    def test_layer_current_stats_shapes(self):
        w = ternary_quantize(jax.random.normal(jax.random.PRNGKey(0), (540, 16)))
        x = (jax.random.uniform(jax.random.PRNGKey(1), (8, 540)) > 0.5
             ).astype(jnp.float32)
        ip, ineg, p = layer_current_stats(jax.random.PRNGKey(2), x,
                                          ternary_planes(w, 0))
        assert ip.shape == ineg.shape == p.shape == (8 * 16,)
        assert bool(jnp.all(p >= 0))


class TestIRCLinear:
    def test_train_eval_shapes_and_grads(self):
        lin = IRCLinear(IRCLinearConfig(fan_in=256, fan_out=8, bias_rows=16))
        params = lin.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 256))
        def loss(p):
            y = lin.apply(p, x, key=jax.random.PRNGKey(2), mode="train",
                          cfg=NonidealConfig.all())
            return jnp.sum(y)
        g = jax.grad(loss)(params)
        assert g["w"].shape == (256, 8)
        assert float(jnp.sum(jnp.abs(g["w"]))) > 0

    def test_eval_tiling_matches_untiled_diff(self):
        # fan_in > macro rows: tiled digital combination == single big matmul
        small_spec = MacroSpec(rows=128, hrs_leak=0.0)
        lin = IRCLinear(IRCLinearConfig(fan_in=300, fan_out=4, bias_rows=8,
                                        output="diff"), spec=small_spec)
        params = lin.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 300))
        d = lin.apply(params, x, key=jax.random.PRNGKey(2), mode="eval")
        w_q = jax.lax.stop_gradient(lin.quantized_weights(params))
        ref = ideal_ternary_matmul((x > 0).astype(jnp.float32), w_q)
        np.testing.assert_allclose(np.asarray(d), np.asarray(ref), atol=1e-3)


class TestMultiTileSensing:
    """Regression: multi-tile layers must NOT silently drop the SA periphery
    (offset, stochastic variation, sensing-range clamp) — each macro's
    front-end applies to its own partial difference before the digital
    combine."""

    def _lin(self, fan_out=6):
        small_spec = MacroSpec(rows=128)
        lin = IRCLinear(IRCLinearConfig(fan_in=300, fan_out=fan_out,
                                        bias_rows=8), spec=small_spec)
        params = lin.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 300))
        return lin, params, x, small_spec

    @pytest.mark.parametrize("cfg", [
        NonidealConfig(sa_variation=True),
        NonidealConfig(sensing_range=True),
        NonidealConfig(sa_variation=True, sensing_range=True)])
    def test_sa_effects_not_dropped(self, cfg):
        lin, params, x, _ = self._lin()
        assert len(lin.map_to_planes(params)) > 1   # actually multi-tile
        key = jax.random.PRNGKey(2)
        out_none = lin.apply(params, x, key=key, mode="eval",
                             cfg=NonidealConfig.none())
        out_cfg = lin.apply(params, x, key=key, mode="eval", cfg=cfg)
        assert not np.array_equal(np.asarray(out_none), np.asarray(out_cfg))

    def test_matches_per_tile_sensed_reference(self):
        """The layer output == per-tile `sensed_diff` outputs combined
        digitally and thresholded (pins the per-tile sensing model)."""
        lin, params, x, spec = self._lin()
        cfg = NonidealConfig.all()
        key = jax.random.PRNGKey(3)
        out = lin.apply(params, x, key=key, mode="eval", cfg=cfg,
                        sa_extra_units=1.0)
        x_bits = (x > 0).astype(jnp.float32)
        total, offset = 0.0, 0
        for t, tile in enumerate(lin.map_to_planes(params)):
            lead = tile.rows - tile.fan_in
            x_t = x_bits[..., offset:offset + tile.rows - lead]
            offset += tile.rows - lead
            total = total + crossbar_forward(
                jax.random.fold_in(key, t), x_t, tile, cfg=cfg, spec=spec,
                sa_extra_units=1.0, output="sensed_diff")
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray((total > 0).astype(jnp.float32)))

    def test_single_tile_sensed_diff_matches_resolve_sa(self):
        """Thresholding one tile's sensed difference at zero reproduces the
        binary SA decisions bit-for-bit (same key discipline)."""
        w = ternary_quantize(jax.random.normal(jax.random.PRNGKey(4),
                                               (200, 12)))
        x = (jax.random.uniform(jax.random.PRNGKey(5), (32, 200)) > 0.5
             ).astype(jnp.float32)
        mapped = ternary_planes(w, bias_rows=16)
        cfg = NonidealConfig.all()
        key = jax.random.PRNGKey(6)
        bits = crossbar_forward(key, x, mapped, cfg=cfg)
        sensed = crossbar_forward(key, x, mapped, cfg=cfg,
                                  output="sensed_diff")
        np.testing.assert_array_equal(
            np.asarray(bits), np.asarray((sensed > 0).astype(jnp.float32)))
