"""Pallas kernel validation: shape/dtype/effect sweeps against the pure-jnp
oracles (interpret mode on CPU), block-shape sweeps, hypothesis properties,
and bit-exact consistency with the core structural simulation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:             # hypothesis optional: property tests skip,
    # example-based tests still run (see requirements-dev.txt)
    def given(*_a, **_k):
        return lambda f: pytest.mark.skip(
            reason="hypothesis not installed")(f)

    def settings(*_a, **_k):
        return lambda f: f

    class _NoStrategies:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _NoStrategies()

from repro.core import (DEFAULT_MACRO, NonidealConfig,
                        ternary_quantize, ternary_planes, crossbar_forward)
from repro.kernels import (IrcEpilogueParams, irc_mvm, irc_mvm_ref,
                           ternary_matmul, ternary_matmul_ref,
                           irc_mvm_from_mapped)


def _mk_inputs(B, R, N, seed=0, lrs_frac=0.2, sigma=0.4245):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    gp = (jax.random.uniform(ks[0], (R, N)) < lrs_frac).astype(jnp.float32)
    gn = ((jax.random.uniform(ks[1], (R, N)) < lrs_frac).astype(jnp.float32)
          * (1 - gp))
    vp = jnp.exp(sigma * jax.random.normal(ks[2], (R, N)))
    vn = jnp.exp(sigma * jax.random.normal(ks[3], (R, N)))
    ep = gp * vp + (1 - gp) * 1e-4
    en = gn * vn + (1 - gn) * 1e-4
    x = (jax.random.uniform(ks[4], (B, R)) < 0.5).astype(jnp.float32)
    eps = jax.random.normal(ks[5], (B, N))
    rnd = jax.random.bernoulli(ks[6], 0.5, (B, N)).astype(jnp.float32)
    return x, ep, en, gp, gn, eps, rnd


SHAPES = [(1, 32, 1), (4, 100, 17), (16, 640, 96), (8, 1024, 128),
          (2, 1000, 200), (5, 63, 130)]


class TestIrcMvmKernel:
    @pytest.mark.parametrize("shape", SHAPES)
    def test_matches_ref_all_effects(self, shape):
        B, R, N = shape
        args = _mk_inputs(B, R, N, seed=hash(shape) % 1000)
        params = IrcEpilogueParams()
        out = irc_mvm(*args, params)
        ref = irc_mvm_ref(*args, params)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("flag", ["apply_nonlinearity", "apply_ir",
                                      "apply_sa", "apply_range"])
    def test_single_effect_toggles(self, flag):
        args = _mk_inputs(8, 320, 64, seed=7)
        base = {f: False for f in ["apply_nonlinearity", "apply_ir",
                                   "apply_sa", "apply_range"]}
        base[flag] = True
        params = IrcEpilogueParams(**base)
        np.testing.assert_array_equal(np.asarray(irc_mvm(*args, params)),
                                      np.asarray(irc_mvm_ref(*args, params)))

    def test_diff_output_close(self):
        args = _mk_inputs(8, 512, 64, seed=3)
        params = IrcEpilogueParams(output="diff")
        out = irc_mvm(*args, params)
        ref = irc_mvm_ref(*args, params)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=1e-5)

    @pytest.mark.parametrize("blocks", [(8, 128, 32), (8, 128, 128),
                                        (16, 256, 256), (8, 256, 512)])
    def test_block_shape_sweep(self, blocks):
        bm, bn, bk = blocks
        args = _mk_inputs(16, 1024, 256, seed=11)
        params = IrcEpilogueParams()
        out = irc_mvm(*args, params, bm=bm, bn=bn, bk=bk)
        ref = irc_mvm_ref(*args, params)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_bf16_planes(self):
        x, ep, en, gp, gn, eps, rnd = _mk_inputs(4, 256, 32, seed=5)
        params = IrcEpilogueParams(apply_sa=False, apply_range=False,
                                   output="diff")
        out = irc_mvm(x, ep.astype(jnp.bfloat16), en.astype(jnp.bfloat16),
                      gp, gn, eps, rnd, params)
        ref = irc_mvm_ref(x, ep.astype(jnp.bfloat16), en.astype(jnp.bfloat16),
                          gp, gn, eps, rnd, params)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=1e-2)

    def test_output_binary_values(self):
        args = _mk_inputs(8, 640, 64, seed=9)
        out = irc_mvm(*args, IrcEpilogueParams())
        assert set(np.unique(np.asarray(out))) <= {0.0, 1.0}

    @settings(max_examples=15, deadline=None)
    @given(B=st.integers(1, 9), R=st.integers(16, 700),
           N=st.integers(1, 150), seed=st.integers(0, 2**16))
    def test_property_kernel_equals_oracle(self, B, R, N, seed):
        args = _mk_inputs(B, R, N, seed=seed)
        params = IrcEpilogueParams()
        np.testing.assert_array_equal(
            np.asarray(irc_mvm(*args, params)),
            np.asarray(irc_mvm_ref(*args, params)))

    def test_consistency_with_core_crossbar(self):
        """Kernel path == repro.core.crossbar_forward given the same key."""
        w = ternary_quantize(jax.random.normal(jax.random.PRNGKey(0), (540, 64)))
        mapped = ternary_planes(w, bias_rows=32)
        x = (jax.random.uniform(jax.random.PRNGKey(1), (8, 540)) > 0.5
             ).astype(jnp.float32)
        cfg = NonidealConfig.all()
        key = jax.random.PRNGKey(42)
        core_out = crossbar_forward(key, x, mapped, cfg=cfg,
                                    spec=DEFAULT_MACRO,
                                    accumulation="single_shot")
        kern_out = irc_mvm_from_mapped(key, x, mapped, cfg, DEFAULT_MACRO)
        assert float(jnp.mean(core_out == kern_out)) > 0.995


class TestTargetPlatform:
    def test_follows_default_device(self):
        """A TPU host runs a kernel on its CPU device under
        `jax.default_device(cpu)`; interpret mode must follow that device."""
        from repro.kernels.ops import target_platform
        assert target_platform() == jax.default_backend()
        with jax.default_device(jax.devices("cpu")[0]):
            assert target_platform() == "cpu"
        with jax.default_device("cpu"):
            assert target_platform() == "cpu"

    def test_interpret_call_under_cpu_default_device(self):
        args = _mk_inputs(8, 160, 24, seed=4)
        params = IrcEpilogueParams()
        with jax.default_device(jax.devices("cpu")[0]):
            out = irc_mvm(*args, params)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(irc_mvm_ref(*args, params)))


class TestTernaryMatmulKernel:
    @pytest.mark.parametrize("shape", [(1, 16, 1), (33, 300, 77),
                                       (128, 512, 128), (200, 1000, 40)])
    def test_matches_ref(self, shape):
        B, K, N = shape
        k1, k2 = jax.random.split(jax.random.PRNGKey(sum(shape)))
        w = jax.random.randint(k1, (K, N), -1, 2, dtype=jnp.int8)
        x = jax.random.normal(k2, (B, K))
        np.testing.assert_allclose(np.asarray(ternary_matmul(x, w)),
                                   np.asarray(ternary_matmul_ref(x, w)),
                                   rtol=1e-6, atol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        w = jax.random.randint(k1, (256, 64), -1, 2, dtype=jnp.int8)
        x = jax.random.normal(k2, (16, 256)).astype(dtype)
        out = ternary_matmul(x, w)
        ref = ternary_matmul_ref(x, w)
        tol = 1e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=tol, atol=tol * 10)

    @settings(max_examples=10, deadline=None)
    @given(B=st.integers(1, 40), K=st.integers(8, 600), N=st.integers(1, 90),
           seed=st.integers(0, 2**16))
    def test_property_matches_ref(self, B, K, N, seed):
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        w = jax.random.randint(k1, (K, N), -1, 2, dtype=jnp.int8)
        x = jax.random.normal(k2, (B, K))
        np.testing.assert_allclose(np.asarray(ternary_matmul(x, w)),
                                   np.asarray(ternary_matmul_ref(x, w)),
                                   rtol=1e-6, atol=1e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("shape", [(2, 64, 16, 16, 16),
                                       (4, 128, 32, 32, 64),
                                       (1, 100, 16, 32, 32),
                                       (2, 256, 64, 128, 128)])
    def test_matches_ref(self, shape):
        from repro.kernels import flash_attention, flash_attention_ref
        H, S, hd, bq, bk = shape
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(S), 3)
        q = jax.random.normal(k1, (H, S, hd))
        k = jax.random.normal(k2, (H, S, hd))
        v = jax.random.normal(k3, (H, S, hd))
        out = flash_attention(q, k, v, bq=bq, bk=bk)
        ref = flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)

    def test_bf16(self):
        from repro.kernels import flash_attention, flash_attention_ref
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(k1, (2, 128, 32)).astype(jnp.bfloat16)
        k = jax.random.normal(k2, (2, 128, 32)).astype(jnp.bfloat16)
        v = jax.random.normal(k3, (2, 128, 32)).astype(jnp.bfloat16)
        out = flash_attention(q, k, v, bq=64, bk=64)
        ref = flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=3e-2, rtol=3e-2)

    @settings(max_examples=8, deadline=None)
    @given(H=st.integers(1, 4), S=st.sampled_from([32, 64, 96, 160]),
           hd=st.sampled_from([16, 32]), seed=st.integers(0, 2**16))
    def test_property_matches_ref(self, H, S, hd, seed):
        from repro.kernels import flash_attention, flash_attention_ref
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(k1, (H, S, hd))
        k = jax.random.normal(k2, (H, S, hd))
        v = jax.random.normal(k3, (H, S, hd))
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, bq=32, bk=32)),
            np.asarray(flash_attention_ref(q, k, v)), atol=2e-5, rtol=1e-4)
