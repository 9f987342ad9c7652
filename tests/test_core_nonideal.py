"""Unit tests for the nonideal-effect models (paper Sec. III)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DEFAULT_MACRO, NonidealConfig, wl_point,
                        nonlinearity_ratio,
                        ir_drop_factors, apply_ir_drop, sample_variation_mask,
                        sa_required_diff, sensing_failure, resolve_sa)


class TestNonlinearity:
    def test_ratio_zero_is_one(self):
        assert float(nonlinearity_ratio(jnp.array(0.0))) == 1.0

    def test_paper_coefficients_spot_values(self):
        # direct evaluation of the published piecewise quartics
        def poly_lo(p):
            return (1.0286e-8 * p**4 - 3.79e-6 * p**3 + 5.3e-4 * p**2
                    - 3.92e-2 * p + 2.5)
        def poly_hi(p):
            return (1.8063e-11 * p**4 - 3.204e-8 * p**3 + 2.2495e-5 * p**2
                    - 8.057e-3 * p + 1.707)
        for p in (1, 30, 77, 140):
            np.testing.assert_allclose(float(nonlinearity_ratio(jnp.array(p))),
                                       poly_lo(p), rtol=1e-5)
        for p in (141, 205, 300):
            np.testing.assert_allclose(float(nonlinearity_ratio(jnp.array(p))),
                                       poly_hi(p), rtol=1e-5)

    def test_clamped_beyond_fit_domain(self):
        r320 = float(nonlinearity_ratio(jnp.array(320.0)))
        r1000 = float(nonlinearity_ratio(jnp.array(1000.0)))
        assert r320 == pytest.approx(r1000)
        assert 0.0 < r1000 < 1.0

    def test_current_monotone_within_pieces(self):
        # physical accumulated current p*ratio(p) is monotone within each
        # polynomial piece (the published fit has a small junction glitch)
        p = jnp.arange(0, 141)
        cur = p * nonlinearity_ratio(p)
        assert bool(jnp.all(jnp.diff(cur) > 0))
        p = jnp.arange(141, 321)
        cur = p * nonlinearity_ratio(p)
        assert bool(jnp.all(jnp.diff(cur) > 0))

    def test_small_p_inflation(self):
        # Fig. 8: small partial sums are inflated (ratio > 1 for small p)
        assert float(nonlinearity_ratio(jnp.array(3.0))) > 1.5


class TestDeviceVariation:
    def test_lognormal_median_and_sigma(self):
        key = jax.random.PRNGKey(0)
        m = sample_variation_mask(key, (200_000,), sigma=0.4245)
        logm = jnp.log(m)
        assert float(jnp.median(m)) == pytest.approx(1.0, abs=0.02)
        assert float(jnp.std(logm)) == pytest.approx(0.4245, rel=0.02)

    def test_law_of_large_numbers(self):
        # Sec. III-B: summing 1024 cells tightens the relative distribution
        key = jax.random.PRNGKey(1)
        m = sample_variation_mask(key, (2000, 1024), sigma=0.4245)
        single_rel = float(jnp.std(m[:, 0]) / jnp.mean(m[:, 0]))
        summed = jnp.sum(m, axis=1)
        sum_rel = float(jnp.std(summed) / jnp.mean(summed))
        assert sum_rel < single_rel / 10  # sqrt(1024)=32x tightening

    def test_sigma_tracks_wl_voltage(self):
        # lower WL voltage -> higher sigma (paper Fig. 14 x-axis)
        _, s_low = wl_point(0.40)
        _, s_mid = wl_point(0.44)
        _, s_high = wl_point(0.48)
        assert s_low > s_mid > s_high
        assert s_mid == pytest.approx(0.4245)


class TestIRDrop:
    def test_no_drop_with_zero_alpha(self):
        blocks = jnp.ones((4, 32))
        f = ir_drop_factors(blocks, alpha=0.0)
        np.testing.assert_allclose(np.asarray(f), 1.0)

    def test_drop_increases_with_distance(self):
        # Fig. 10 blue line: same 32-LRS block placed farther from the
        # driver loses more current
        alpha = DEFAULT_MACRO.ir_alpha
        drops = []
        for pos in range(0, 32, 8):
            blocks = jnp.zeros((32,)).at[pos].set(32.0)
            total = float(apply_ir_drop(blocks, alpha))
            drops.append(32.0 - total)
        assert all(b >= a - 1e-6 for a, b in zip(drops, drops[1:]))
        assert drops[-1] > drops[0]

    def test_more_current_more_drop(self):
        # Fig. 10 red line: 160 cells in blocks 0-4 drop more than 32 in one
        alpha = DEFAULT_MACRO.ir_alpha
        one = jnp.zeros((32,)).at[4].set(32.0)
        five = jnp.zeros((32,)).at[:5].set(32.0)
        loss_one = 32.0 - float(apply_ir_drop(one, alpha))
        loss_five = 160.0 - float(apply_ir_drop(five, alpha))
        assert loss_five > loss_one

    def test_block0_sees_no_wire(self):
        blocks = jnp.zeros((32,)).at[0].set(32.0)
        f = ir_drop_factors(blocks, DEFAULT_MACRO.ir_alpha)
        assert float(f[0]) == pytest.approx(1.0)

    # nb: the IR-block counts of the detector's 572-row group crossbars, as
    # is (18) and padded by the kernel to bk = 128, 256, 512 (20, 24, 32);
    # shape/axis: every layout branch (last, second-to-last, leading axis)
    @pytest.mark.parametrize("nb", [18, 20, 24, 32])
    @pytest.mark.parametrize("shape,axis", [((3, None), -1),
                                            ((None, 5), -2),
                                            ((None, 2, 3), 0)])
    def test_values_match_suffix_cumsum(self, nb, shape, axis):
        # the wire segment feeding block k carries the suffix sum of the
        # block currents; block b sees the drop of segments 1..b
        shape = tuple(nb if d is None else d for d in shape)
        rng = np.random.default_rng(nb)
        blocks = rng.uniform(0.0, 32.0, shape)
        alpha = 4 * DEFAULT_MACRO.ir_alpha
        moved = np.moveaxis(blocks, axis, -1)
        suffix = np.cumsum(moved[..., ::-1], axis=-1)[..., ::-1]
        cum = np.cumsum(suffix, axis=-1) - suffix[..., :1]
        want = np.moveaxis(np.clip(1.0 - alpha * cum, 0.0, 1.0), -1, axis)
        got = ir_drop_factors(jnp.asarray(blocks, jnp.float32), alpha,
                              axis=axis)
        assert 0.0 < want.min() < want.max() <= 1.0   # no clipping at 0
        np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=2e-6)


class TestSA:
    def test_required_diff_grows_with_p(self):
        # Fig. 9: more activated LRS cells -> larger required difference
        g = sa_required_diff(jnp.array([0.0, 100.0, 300.0]))
        assert float(g[0]) < float(g[1]) < float(g[2])
        assert float(g[0]) == pytest.approx(2.0)

    def test_sensing_failure_bounds(self):
        spec = DEFAULT_MACRO
        lo, hi = spec.sense_low_units, spec.sense_high_units
        i_pos = jnp.array([lo - 1.0, lo + 1.0, hi + 1.0, 100.0])
        i_neg = jnp.array([100.0, lo + 1.0, 100.0, 100.0])
        f = sensing_failure(i_pos, i_neg, spec)
        assert f.tolist() == [True, False, True, False]

    def test_resolve_ideal(self):
        key = jax.random.PRNGKey(0)
        out = resolve_sa(key, jnp.array([100.0, 50.0]), jnp.array([50.0, 100.0]),
                         jnp.array([150.0, 150.0]), NonidealConfig.none())
        assert out.tolist() == [1.0, 0.0]

    def test_out_of_range_randomized(self):
        # far below the sensing floor -> output is a coin flip
        key = jax.random.PRNGKey(0)
        n = 2000
        i_pos = jnp.full((n,), 5.0)
        i_neg = jnp.full((n,), 2.0)
        cfg = NonidealConfig(sensing_range=True)
        out = resolve_sa(key, i_pos, i_neg, i_pos + i_neg, cfg)
        assert 0.4 < float(jnp.mean(out)) < 0.6
