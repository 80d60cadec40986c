"""Pallas mixed-precision matmul vs the pure-jnp oracle (ref.py).

Sweeps shapes (aligned + ragged), dtypes, word-lengths w_Q, operand
slices k, ST/SA variants, and channel-wise scales.  interpret=True runs
the kernel body on CPU — bit-exact integer math, so assert_array_equal.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; the rest still run
    from _hypothesis_stub import given, settings, st

from repro.core import packing
from repro.core.packing import PlaneFormat
from repro.kernels.mpmm import ops, ref
from repro.kernels.mpmm.ops import TileShape

WK = [(w, k) for w in (1, 2, 4, 8) for k in (1, 2, 4) if k <= w] + [(8, 8)]


def make_case(rng, m, kdim, n, w_bits, k, channel_wise=False):
    a = jnp.asarray(rng.integers(-128, 128, (m, kdim)), jnp.int8)
    lo, hi = -(2 ** (w_bits - 1)), 2 ** (w_bits - 1) - 1
    w_int = jnp.asarray(rng.integers(lo, hi + 1, (kdim, n)), jnp.int32)
    fmt = PlaneFormat(w_bits=w_bits, k=k, k_dim=kdim)
    planes = packing.pack_planes(w_int, fmt, axis=-2)
    colsum = jnp.sum(w_int, axis=0, dtype=jnp.int32).reshape(1, n)
    if channel_wise:
        gamma = jnp.asarray(rng.uniform(0.001, 0.01, (1, n)), jnp.float32)
    else:
        gamma = jnp.full((1, n), 0.005, jnp.float32)
    return a, planes, gamma, colsum, fmt


class TestXlaImpl:
    @pytest.mark.parametrize("w_bits,k", WK)
    def test_matches_ref(self, w_bits, k, rng):
        a, planes, gamma, colsum, fmt = make_case(rng, 32, 64, 48, w_bits, k)
        y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128)
        y = ops.mpmm(a, planes, gamma, colsum, fmt=fmt, impl="xla")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


class TestPallasKernel:
    @pytest.mark.parametrize("w_bits,k", WK)
    def test_matches_ref_aligned(self, w_bits, k, rng):
        a, planes, gamma, colsum, fmt = make_case(rng, 128, 128, 128, w_bits, k)
        y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128)
        y = ops.mpmm(a, planes, gamma, colsum, fmt=fmt, impl="pallas")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))

    @pytest.mark.parametrize("shape", [(1, 8, 16), (17, 96, 40),
                                       (130, 256, 136), (64, 72, 200)])
    def test_ragged_shapes(self, shape, rng):
        m, kdim, n = shape
        a, planes, gamma, colsum, fmt = make_case(rng, m, kdim, n, 4, 2)
        y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128)
        y = ops.mpmm(a, planes, gamma, colsum, fmt=fmt, impl="pallas")
        assert y.shape == (m, n)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))

    @pytest.mark.parametrize("variant", ["st", "sa"])
    def test_variants_identical_result(self, variant, rng):
        """Sum-Together vs Sum-Apart consolidate identically (IV-A)."""
        a, planes, gamma, colsum, fmt = make_case(rng, 64, 96, 80, 4, 1)
        y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128)
        y = ops.mpmm(a, planes, gamma, colsum, fmt=fmt, impl="pallas",
                     variant=variant)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))

    @pytest.mark.parametrize("tile", [TileShape(8, 128, 128),
                                      TileShape(16, 256, 128),
                                      TileShape(32, 128, 256)])
    def test_tile_shapes(self, tile, rng):
        """PE-array-dims analogue: result invariant to the tile choice."""
        a, planes, gamma, colsum, fmt = make_case(rng, 48, 160, 144, 2, 2)
        y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128)
        y = ops.mpmm(a, planes, gamma, colsum, fmt=fmt, impl="pallas",
                     tile=tile)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))

    def test_channel_wise_gamma(self, rng):
        a, planes, gamma, colsum, fmt = make_case(
            rng, 32, 64, 48, 4, 2, channel_wise=True)
        y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128)
        y = ops.mpmm(a, planes, gamma, colsum, fmt=fmt, impl="pallas")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))

    def test_out_dtype_bf16(self, rng):
        a, planes, gamma, colsum, fmt = make_case(rng, 16, 32, 24, 4, 4)
        y = ops.mpmm(a, planes, gamma, colsum, fmt=fmt, impl="pallas",
                     out_dtype=jnp.bfloat16)
        assert y.dtype == jnp.bfloat16

    def test_batched_lead_dims(self, rng):
        """(B, S, K) activations flatten through the kernel."""
        a, planes, gamma, colsum, fmt = make_case(rng, 24, 64, 48, 4, 2)
        a3 = a.reshape(2, 12, 64)
        y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128)
        y = ops.mpmm(a3, planes, gamma, colsum, fmt=fmt, impl="pallas")
        np.testing.assert_array_equal(
            np.asarray(y.reshape(24, -1)), np.asarray(y_ref))


class TestEndToEnd:
    @pytest.mark.parametrize("w_bits,k", [(4, 2), (2, 2), (8, 4), (1, 1)])
    def test_prepare_and_run_close_to_float(self, w_bits, k, rng):
        """Float path: quant -> mpmm -> dequant tracks the fp matmul."""
        kdim, n = 128, 64
        x = jnp.asarray(rng.normal(0, 1, (32, kdim)), jnp.float32)
        w = jnp.asarray(rng.normal(0, 0.05, (kdim, n)), jnp.float32)
        ga = jnp.asarray(4.0 * 1.0 / 255, jnp.float32)  # acts ~ [0, 4]
        x = jnp.abs(x)  # unsigned activation regime (paper Eq. 5)
        from repro.core import quant
        gw = quant.init_step_size(w, quant.weight_spec(w_bits))
        params = ops.prepare_weights(w, gw, w_bits=w_bits, k=k, gamma_a=ga)
        y = ops.mpmm_packed(x, params, ga, impl="pallas")
        y_fp = x @ w
        # quantization error scales with 1/2^w; just sanity-check corr.
        corr = np.corrcoef(np.asarray(y).ravel(), np.asarray(y_fp).ravel())[0, 1]
        floor = {1: 0.55, 2: 0.85, 4: 0.98, 8: 0.98}[w_bits]
        assert corr > floor

    def test_xla_pallas_bitwise_identical(self, rng):
        a, planes, gamma, colsum, fmt = make_case(rng, 56, 112, 72, 4, 2)
        yx = ops.mpmm(a, planes, gamma, colsum, fmt=fmt, impl="xla")
        yp = ops.mpmm(a, planes, gamma, colsum, fmt=fmt, impl="pallas")
        np.testing.assert_array_equal(np.asarray(yx), np.asarray(yp))


@settings(max_examples=25, deadline=None)
@given(
    m=st.integers(1, 64),
    kdim=st.integers(8, 160),
    n=st.integers(8, 96),
    wk=st.sampled_from(WK),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_pallas_equals_oracle(m, kdim, n, wk, seed):
    w_bits, k = wk
    rng = np.random.default_rng(seed)
    a, planes, gamma, colsum, fmt = make_case(rng, m, kdim, n, w_bits, k)
    y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128)
    y = ops.mpmm(a, planes, gamma, colsum, fmt=fmt, impl="pallas")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


class TestFusedEpilogue:
    """ST vs SA vs ref bit-exactness for every EpilogueSpec combination
    on odd (padding-forcing) shapes — the fused BN/ReLU/residual path."""

    M, KD, N = 37, 200, 72
    COMBOS = [(b, r, s) for b in (False, True) for r in (False, True)
              for s in (False, True)]

    def _epilogue_case(self, rng, w_bits, k, bn, resid):
        a, planes, gamma, colsum, fmt = make_case(
            rng, self.M, self.KD, self.N, w_bits, k)
        scale = (jnp.asarray(rng.uniform(0.5, 2.0, (1, self.N)), jnp.float32)
                 if bn else None)
        shift = (jnp.asarray(rng.normal(0, 1, (1, self.N)), jnp.float32)
                 if bn else None)
        res = (jnp.asarray(rng.normal(0, 1, (self.M, self.N)), jnp.float32)
               if resid else None)
        return a, planes, gamma, colsum, fmt, scale, shift, res

    @pytest.mark.parametrize("combo", COMBOS)
    @pytest.mark.parametrize("variant", ["st", "sa"])
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_bit_exact_vs_ref(self, combo, variant, impl, rng):
        bn, relu, resid = combo
        spec = ops.EpilogueSpec(bn=bn, relu=relu, residual=resid)
        a, planes, gamma, colsum, fmt, scale, shift, res = (
            self._epilogue_case(rng, 4, 2, bn, resid))
        y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128,
                             epilogue=spec, scale=scale, shift=shift,
                             residual=res)
        y = ops.mpmm(a, planes, gamma, colsum, scale, shift, res,
                     fmt=fmt, impl=impl, variant=variant, epilogue=spec)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))

    @pytest.mark.parametrize("w_bits,k", WK)
    def test_full_epilogue_all_formats(self, w_bits, k, rng):
        spec = ops.EpilogueSpec(bn=True, relu=True, residual=True)
        a, planes, gamma, colsum, fmt, scale, shift, res = (
            self._epilogue_case(rng, w_bits, k, True, True))
        y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128,
                             epilogue=spec, scale=scale, shift=shift,
                             residual=res)
        for impl in ("xla", "pallas"):
            y = ops.mpmm(a, planes, gamma, colsum, scale, shift, res,
                         fmt=fmt, impl=impl, epilogue=spec)
            np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))

    def test_epilogue_out_dtype_override(self, rng):
        spec = ops.EpilogueSpec(relu=True, out_dtype=jnp.bfloat16)
        a, planes, gamma, colsum, fmt = make_case(rng, 16, 32, 24, 4, 4)
        for impl in ("xla", "pallas"):
            y = ops.mpmm(a, planes, gamma, colsum, fmt=fmt, impl=impl,
                         epilogue=spec)
            assert y.dtype == jnp.bfloat16

    def test_mismatched_operands_rejected(self, rng):
        a, planes, gamma, colsum, fmt = make_case(rng, 16, 32, 24, 4, 4)
        with pytest.raises(ValueError):
            ops.mpmm(a, planes, gamma, colsum,
                     jnp.ones((1, 24), jnp.float32), None, None,
                     fmt=fmt, impl="xla")  # scale without an EpilogueSpec

    def test_residual_with_batched_lead_dims(self, rng):
        a, planes, gamma, colsum, fmt = make_case(rng, 24, 64, 48, 4, 2)
        a3 = a.reshape(2, 12, 64)
        res = jnp.asarray(rng.normal(0, 1, (2, 12, 48)), jnp.float32)
        spec = ops.EpilogueSpec(residual=True)
        y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128,
                             epilogue=spec, residual=res.reshape(24, 48))
        y = ops.mpmm(a3, planes, gamma, colsum, None, None, res,
                     fmt=fmt, impl="pallas", epilogue=spec)
        np.testing.assert_array_equal(
            np.asarray(y.reshape(24, -1)), np.asarray(y_ref))


class TestDigitCache:
    """The decode-once-per-(j,k) digit cache in the pallas kernel."""

    def test_cached_equals_uncached(self, rng):
        from repro.kernels.mpmm import kernel as K
        a, planes, gamma, colsum, fmt = make_case(rng, 128, 256, 128, 4, 2)
        kw = dict(fmt=fmt, act_zero=128, tile=(64, 128, 128))
        y_c = K.mpmm_pallas(a, planes, gamma, colsum, cache_digits=True, **kw)
        y_u = K.mpmm_pallas(a, planes, gamma, colsum, cache_digits=False, **kw)
        np.testing.assert_array_equal(np.asarray(y_c), np.asarray(y_u))

    def test_large_strip_disables_cache(self, rng):
        """ops falls back to per-step decode when the decoded strip would
        blow the VMEM budget; results are identical either way."""
        from repro.core import dse
        from repro.kernels.mpmm import ops as O
        # 8 planes x 8192 K x 128 bn = 8 MiB decoded strip: strictly over
        # the 4 MiB budget, so ops must take the cache_digits=False path.
        a, planes, gamma, colsum, fmt = make_case(rng, 32, 8192, 64, 8, 1)
        tile = O.TileShape(32, 512, 128)
        strip = dse.digit_cache_bytes(8192, dse.TileCandidate(32, 512, 128),
                                      fmt)
        assert strip > dse.DIGIT_CACHE_BUDGET_BYTES, strip
        y = O.mpmm(a, planes, gamma, colsum, fmt=fmt, impl="pallas",
                   tile=tile)
        y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


class TestAutotunedDefault:
    def test_default_tile_comes_from_dse(self, rng):
        """tile=None resolves through the DSE autotuner, not 128^3."""
        t = ops.autotune_tile(256, 1024, 1024, w_bits=4, k=2)
        assert isinstance(t, ops.TileShape)
        a, planes, gamma, colsum, fmt = make_case(rng, 64, 96, 80, 4, 2)
        y_ref = ref.mpmm_ref(a, planes, fmt, gamma, act_zero=128)
        y = ops.mpmm(a, planes, gamma, colsum, fmt=fmt, impl="pallas")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
