"""Public mixed-precision-matmul API: padding, impl dispatch, weight prep.

Three implementations, all bit-exact to `ref.mpmm_ref`:

  * ``pallas``: the TPU kernel (kernel.py): one fused contraction per
                grid step with the plane axis folded into N, decoded
                digits cached per N tile, and the epilogue (BN / ReLU /
                residual) fused into the K-final step.  interpret=True
                off-TPU (core/flags.default_interpret).
  * ``xla``:    one int8 contraction against weights recombined in-graph
                from the packed digit planes (a disjoint-bit-field OR —
                the ST adder tree folded into the operand).  The packed
                planes remain the real HBM buffers (memory term ∝
                w_Q/8); the multi-pod dry-run lowers this path.
  * ``auto``:   pallas on TPU, xla elsewhere.

When ``tile`` is None the pallas tile comes from the paper's Eq. 1-3
cost model (core/dse.autotune_tile), per layer shape, cached in-process.

Weight preparation (``prepare_weights``) happens once at deployment —
the FPGA analogue is loading a new CNN's weights without re-synthesizing
the bitstream (the paper's on-the-fly word-length switch).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dse as _dse
from repro.core import flags as _flags
from repro.core import packing, quant
from repro.core.packing import PlaneFormat
from repro.kernels.mpmm import conv_kernel as _conv_kernel
from repro.kernels.mpmm import epilogue as _epi
from repro.kernels.mpmm import kernel as _kernel
from repro.kernels.mpmm import ref as _ref
from repro.kernels.mpmm.epilogue import EpilogueSpec

__all__ = [
    "TileShape",
    "EpilogueSpec",
    "MpmmParams",
    "quantize_activations",
    "prepare_weights",
    "mpmm",
    "mpmm_packed",
    "conv_mpmm",
    "conv_implicit_feasible",
    "autotune_tile",
]


@dataclasses.dataclass(frozen=True)
class TileShape:
    """Pallas tile (bm, bk, bn) — the PE-array-dims analogue (DESIGN.md §2)."""

    bm: int = 128
    bk: int = 128
    bn: int = 128

    def as_tuple(self) -> Tuple[int, int, int]:
        return (self.bm, self.bk, self.bn)


def autotune_tile(
    m: int, kdim: int, n: int, *, w_bits: int, k: int, variant: str = "st"
) -> TileShape:
    """DSE-driven per-layer tile (DESIGN.md §4).

    Thin TileShape view over ``core.dse.autotune_tile``, which memoizes
    per problem shape — no second cache here.
    """
    cand = _dse.autotune_tile(m, kdim, n, w_bits=w_bits, k=k, variant=variant)
    return TileShape(*cand.as_tuple())


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MpmmParams:
    """Deployed (packed) weights of one linear layer.

    Arrays (pytree leaves):
      planes: uint8 (P, ceil(K/(8//k)), N) packed digit planes.
      colsum: int32 (1, N) column sums of the integer codes.
      gamma:  f32   (1, N) combined scale gamma_a * gamma_w.
    Static (aux data): the PlaneFormat and activation bias.
    """

    planes: jax.Array
    colsum: jax.Array
    gamma: jax.Array
    fmt: PlaneFormat = dataclasses.field(metadata={"static": True})
    act_zero: int = 128

    def tree_flatten(self):
        return (self.planes, self.colsum, self.gamma), (self.fmt, self.act_zero)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, fmt=aux[0], act_zero=aux[1])

    @property
    def hbm_bytes(self) -> int:
        return int(self.planes.size) + 8 * int(self.colsum.size)


def quantize_activations(
    x: jax.Array, gamma_a: jax.Array, a_bits: int = 8, signed: bool = False
) -> jax.Array:
    """float -> int8 activation codes.

    Default (paper Eq. 5): unsigned codes u in [0, 2^a) stored biased
    (u - 2^{a-1}) so the MXU sees a signed operand; pair with
    ``act_zero = 2^{a-1}``.  ``signed=True`` emits symmetric signed
    codes in [-2^{a-1}, 2^{a-1}) with ``act_zero = 0`` — for inputs
    that straddle zero (e.g. mean-normalized images at a CNN stem),
    where unsigned clamping would destroy every negative value.
    """
    half = 2 ** (a_bits - 1)
    if signed:
        return jnp.clip(jnp.round(x / gamma_a), -half, half - 1).astype(jnp.int8)
    u = jnp.clip(jnp.round(x / gamma_a), 0, 2 * half - 1)
    return (u - half).astype(jnp.int8)


def prepare_weights(
    w: jax.Array,
    gamma_w: jax.Array,
    *,
    w_bits: int,
    k: int,
    gamma_a: jax.Array,
    a_bits: int = 8,
    channel_wise: bool = False,
) -> MpmmParams:
    """Pack trained FP weights (K, N) for deployment.

    gamma_w: scalar (per-tensor) or [N] (per-channel — the paper's
    channel-wise quantization); gamma_a: scalar activation step size.
    """
    kdim, n = w.shape
    spec = quant.weight_spec(w_bits, channel_axis=-1 if channel_wise else None)
    w_int = quant.quantize_int(w, gamma_w, spec)  # int32 codes (K, N)
    fmt = PlaneFormat(w_bits=w_bits, k=k, k_dim=kdim)
    planes = packing.pack_planes(w_int, fmt, axis=-2)
    colsum = jnp.sum(w_int, axis=0, dtype=jnp.int32).reshape(1, n)
    gamma = (jnp.broadcast_to(jnp.asarray(gamma_w, jnp.float32), (n,))
             * jnp.asarray(gamma_a, jnp.float32)).reshape(1, n)
    return MpmmParams(
        planes=planes, colsum=colsum, gamma=gamma, fmt=fmt,
        act_zero=2 ** (a_bits - 1),
    )


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    pw = [(0, 0)] * x.ndim
    pw[axis] = (0, pad)
    return jnp.pad(x, pw)


def combined_int8_weights(planes_u8: jax.Array, fmt: PlaneFormat) -> jax.Array:
    """Packed digit planes (P, Kp, N) uint8 -> W_int (K, N) int8, in-graph.

    The planes are disjoint k-bit fields of the w_Q-bit two's-complement
    code, so recombination is a byte-level OR of shifted fields followed
    by one arithmetic sign-extension — the entire ST adder tree folded
    into the weight operand at zero dot cost.  Bit-exact to
    ``packing.combine_planes(unpack_planes(...))`` for every w_Q <= 8.
    """
    f = fmt.digits_per_byte
    k = fmt.k
    if fmt.planes == 1 and f == 1:
        # w_Q == k == 8: the single packed plane already IS the int8
        # weight (one two's-complement byte per code) — reinterpret in
        # place instead of running the shift/stack/reshape pipeline,
        # whose overhead made the fused path slower than the per-plane
        # loop for w8/k8 (BENCH_kernel.json showed 0.88x).
        return planes_u8[0, : fmt.k_dim].astype(jnp.int8)
    mask = jnp.uint8((1 << k) - 1)
    parts = [(planes_u8 >> jnp.uint8(k * i)) & mask for i in range(f)]
    kp, n = planes_u8.shape[-2], planes_u8.shape[-1]
    # (P, Kp, f, N) -> (P, K_padded, N): field index minor within a byte.
    dig = jnp.stack(parts, axis=-2).reshape(fmt.planes, kp * f, n)
    w = dig[0]
    for p in range(1, fmt.planes):
        w = w | (dig[p] << jnp.uint8(k * p))
    w = w[: fmt.k_dim].astype(jnp.int8)  # drop K packing pad; reinterpret
    if fmt.signed and fmt.w_bits < 8:
        sh = jnp.int8(8 - fmt.w_bits)
        w = jax.lax.shift_right_arithmetic(jax.lax.shift_left(w, sh), sh)
    return w


def _xla_impl(
    a_biased: jax.Array,
    planes_u8: jax.Array,
    gamma: jax.Array,
    colsum: jax.Array,
    fmt: PlaneFormat,
    act_zero: int,
    out_dtype,
    epilogue: Optional[EpilogueSpec] = None,
    scale: Optional[jax.Array] = None,
    shift: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
) -> jax.Array:
    """Single fused int8 contraction against recombined weights.

    Replaces the seed's P sequential per-plane dots: the shift-add moves
    into the operand (``combined_int8_weights``), so compute cost is one
    int8 GEMM regardless of the plane count, while the packed planes
    stay the HBM-resident buffers (memory term ∝ w_Q/8 unchanged).
    """
    w8 = combined_int8_weights(planes_u8, fmt)  # (K, N) int8
    acc = jax.lax.dot_general(
        a_biased, w8, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return _epi.finish(
        acc, gamma, colsum, act_zero=act_zero, spec=epilogue,
        scale=scale, shift=shift, residual=residual,
        out_dtype=_epi.resolve_out_dtype(epilogue, out_dtype))


def _on_tpu() -> bool:
    return not _flags.default_interpret()


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "act_zero", "tile", "variant", "impl",
                     "out_dtype", "epilogue"),
)
def mpmm(
    a_biased: jax.Array,
    planes: jax.Array,
    gamma: jax.Array,
    colsum: jax.Array,
    scale: Optional[jax.Array] = None,
    shift: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    *,
    fmt: PlaneFormat,
    act_zero: int = 128,
    tile: Optional[TileShape] = None,
    variant: str = "st",
    impl: str = "auto",
    out_dtype=jnp.float32,
    epilogue: Optional[EpilogueSpec] = None,
) -> jax.Array:
    """y[..., N] = epilogue(gamma * ((a_biased + act_zero) @ W_int)).

    a_biased: int8 (..., K); planes: uint8 (P, Kp, N); gamma/colsum (1, N).
    scale/shift: f32 (1, N) when ``epilogue.bn``; residual: (..., N) with
    the same leading shape as ``a_biased`` when ``epilogue.residual``.
    ``tile=None`` autotunes (bm, bk, bn) from the DSE cost model.
    """
    _epi.validate_operands(epilogue, scale, shift, residual)
    lead = a_biased.shape[:-1]
    kdim = a_biased.shape[-1]
    n = planes.shape[-1]
    a2 = a_biased.reshape(-1, kdim)
    res2 = residual.reshape(-1, n) if residual is not None else None

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"

    if impl == "xla":
        out = _xla_impl(a2, planes, gamma, colsum, fmt, act_zero, out_dtype,
                        epilogue, scale, shift, res2)
        return out.reshape(*lead, n)

    # pallas: pick a tile (DSE autotuner unless pinned), pad every dim to
    # it, then slice back.
    t = tile or autotune_tile(a2.shape[0], kdim, n, w_bits=fmt.w_bits,
                              k=fmt.k, variant=variant)
    f = fmt.digits_per_byte
    bm, bk, bn = t.bm, max(t.bk, f), t.bn
    bk = bk + (-bk) % f
    a_p = _pad_to(_pad_to(a2, 0, bm), 1, bk)
    # pad K on packed axis in byte units; pad N.
    planes_p = _pad_to(_pad_to(planes, 1, bk // f), 2, bn)
    gamma_p = _pad_to(gamma, 1, bn)
    colsum_p = _pad_to(colsum, 1, bn)
    scale_p = _pad_to(scale, 1, bn) if scale is not None else None
    shift_p = _pad_to(shift, 1, bn) if shift is not None else None
    res_p = (_pad_to(_pad_to(res2, 0, bm), 1, bn)
             if res2 is not None else None)
    fmt_p = PlaneFormat(w_bits=fmt.w_bits, k=fmt.k,
                        k_dim=planes_p.shape[1] * f, signed=fmt.signed)
    tile_cand = _dse.TileCandidate(bm, bk, bn)
    cache = (_dse.digit_cache_bytes(fmt_p.k_dim, tile_cand, fmt_p)
             <= _dse.DIGIT_CACHE_BUDGET_BYTES)
    out = _kernel.mpmm_pallas(
        a_p, planes_p, gamma_p, colsum_p,
        fmt=fmt_p, act_zero=act_zero, tile=(bm, bk, bn), variant=variant,
        out_dtype=out_dtype, epilogue=epilogue, scale=scale_p,
        shift=shift_p, residual=res_p, cache_digits=cache,
    )
    return out[: a2.shape[0], :n].reshape(*lead, n)


def conv_implicit_feasible(c_in: int, fmt: PlaneFormat) -> bool:
    """Whether the pallas implicit-GEMM conv kernel can run this layer.

    Each kernel position's C-slice must start at a byte boundary of the
    packed K axis (C divisible by 8//k).  Layers that fail (e.g. a
    3-channel stem under k=2) keep the im2col dataflow.
    """
    return c_in % fmt.digits_per_byte == 0


# Largest integer magnitude an f32 accumulator holds exactly; below it
# the direct-conv XLA path may run the conv in f32 (fast Eigen/MXU conv)
# and stay bit-exact.  XLA's *integer* conv lowers to a naive loop on
# CPU (~40x slower), so this fast path is what makes the direct dataflow
# beat materialized im2col end to end on the CI backend.
_F32_EXACT_BOUND = 1 << 24


def _xla_conv_impl(
    a_biased: jax.Array,     # int8 (B, H, W, C) biased codes, unpadded
    planes_u8: jax.Array,    # uint8 (P, K//f, N)
    gamma: jax.Array,
    colsum: jax.Array,
    fmt: PlaneFormat,
    act_zero: int,
    kh: int, kw: int, stride: int, padding: str,
    out_dtype,
    epilogue: Optional[EpilogueSpec] = None,
    scale: Optional[jax.Array] = None,
    shift: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
) -> jax.Array:
    """Direct conv against recombined int8 weights — no patch buffer.

    The packed digit planes are recombined in-graph (the same bit-field
    OR as the matmul path) and reshaped HWIO; the conv runs on the raw
    feature map, spatially pre-padded with the biased zero code
    ``-act_zero`` so ``u = s + act_zero`` holds at every tap including
    padding — which keeps the colsum zero-point correction a conv-shaped
    identity: y_int = conv(s, W) + act_zero * colsum.
    """
    c = a_biased.shape[-1]
    n = planes_u8.shape[-1]
    w8 = combined_int8_weights(planes_u8, fmt)          # (K, N) int8
    w_hwio = w8.reshape(kh, kw, c, n)                   # im2col (kh,kw,C) order
    xp = _ref.pad_spatial(a_biased, kh, kw, stride, padding,
                          fill=-act_zero)
    dn = ("NHWC", "HWIO", "NHWC")
    bound = kh * kw * c * 128 * (1 << (fmt.w_bits - 1))
    if bound <= _F32_EXACT_BOUND:
        # Every partial sum is an integer of magnitude <= bound, exactly
        # representable in f32 under any accumulation order — bit-exact.
        acc = jax.lax.conv_general_dilated(
            xp.astype(jnp.float32), w_hwio.astype(jnp.float32),
            (stride, stride), "VALID", dimension_numbers=dn,
        ).astype(jnp.int32)
    else:
        acc = jax.lax.conv_general_dilated(
            xp, w_hwio, (stride, stride), "VALID", dimension_numbers=dn,
            preferred_element_type=jnp.int32,
        )
    return _epi.finish(
        acc, gamma, colsum, act_zero=act_zero, spec=epilogue,
        scale=scale, shift=shift, residual=residual,
        out_dtype=_epi.resolve_out_dtype(epilogue, out_dtype),
    )


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "act_zero", "kh", "kw", "stride", "padding",
                     "bn", "variant", "impl", "out_dtype", "epilogue"),
)
def conv_mpmm(
    a_biased: jax.Array,     # int8 (B, H, W, C) biased activation codes
    planes: jax.Array,       # uint8 (P, (kh*kw*C)//f, N)
    gamma: jax.Array,        # f32 (1, N)
    colsum: jax.Array,       # int32 (1, N)
    scale: Optional[jax.Array] = None,
    shift: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,    # (B, Ho, Wo, N)
    *,
    fmt: PlaneFormat,
    act_zero: int = 128,
    kh: int,
    kw: int,
    stride: int = 1,
    padding: str = "SAME",
    bn: Optional[int] = None,
    variant: str = "st",
    impl: str = "auto",
    out_dtype=jnp.float32,
    epilogue: Optional[EpilogueSpec] = None,
) -> jax.Array:
    """Implicit-GEMM convolution over packed planes -> (B, Ho, Wo, N).

    The conv analogue of ``mpmm``: same weight bytes, same epilogue
    contract, but the patch matrix is never materialized.  ``impl``:
    ``pallas`` = the implicit-GEMM kernel (conv_kernel.py), ``xla`` =
    direct ``lax.conv_general_dilated`` against recombined int8 weights,
    ``auto`` = pallas on TPU, xla elsewhere.  Bit-exact vs
    ``ref.conv_ref`` (and hence vs the materialized-im2col path).
    """
    _epi.validate_operands(epilogue, scale, shift, residual)
    b, h, w, c = a_biased.shape
    n = planes.shape[-1]

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"

    if impl == "xla":
        return _xla_conv_impl(
            a_biased, planes, gamma, colsum, fmt, act_zero,
            kh, kw, stride, padding, out_dtype, epilogue, scale, shift,
            residual)

    if not conv_implicit_feasible(c, fmt):
        raise ValueError(
            f"pallas implicit-GEMM conv needs C divisible by the packed "
            f"digits-per-byte: C={c}, 8//k={fmt.digits_per_byte} — route "
            f"this layer to dataflow='im2col' or impl='xla'")
    xp = _ref.pad_spatial(a_biased, kh, kw, stride, padding,
                          fill=-act_zero)
    ho = (xp.shape[1] - kh) // stride + 1
    wo = (xp.shape[2] - kw) // stride + 1

    if bn is None:
        conv = _dse.ConvShape(batch=b, h=h, w=w, c_in=c, c_out=n,
                              kh=kh, kw=kw, stride=stride, padding=padding)
        choice = _dse.choose_conv_dataflow(
            conv, w_bits=fmt.w_bits, k=fmt.k, variant=variant)
        bn = choice.tile_implicit.bn if choice.tile_implicit else 128
    planes_p = _pad_to(planes, 2, bn)
    gamma_p = _pad_to(gamma, 1, bn)
    colsum_p = _pad_to(colsum, 1, bn)
    scale_p = _pad_to(scale, 1, bn) if scale is not None else None
    shift_p = _pad_to(shift, 1, bn) if shift is not None else None
    res_p = _pad_to(residual, 3, bn) if residual is not None else None
    n_k = kh * kw
    cache = n_k * c * fmt.planes * bn <= _dse.DIGIT_CACHE_BUDGET_BYTES
    out = _conv_kernel.conv_mpmm_pallas(
        xp, planes_p, gamma_p, colsum_p,
        fmt=fmt, act_zero=act_zero, kh=kh, kw=kw, stride=stride,
        out_hw=(ho, wo), bn=bn, variant=variant, out_dtype=out_dtype,
        epilogue=epilogue, scale=scale_p, shift=shift_p, residual=res_p,
        cache_digits=cache,
    )
    return out[..., :n]


def mpmm_packed(
    x: jax.Array,
    params: MpmmParams,
    gamma_a: jax.Array,
    *,
    a_bits: int = 8,
    tile: Optional[TileShape] = None,
    variant: str = "st",
    impl: str = "auto",
    out_dtype=jnp.float32,
    epilogue: Optional[EpilogueSpec] = None,
    scale: Optional[jax.Array] = None,
    shift: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
) -> jax.Array:
    """Float-in/float-out convenience: quantize acts, run mpmm, dequant."""
    a = quantize_activations(x, gamma_a, a_bits)
    return mpmm(
        a, params.planes, params.gamma, params.colsum,
        scale, shift, residual,
        fmt=params.fmt, act_zero=params.act_zero, tile=tile,
        variant=variant, impl=impl, out_dtype=out_dtype, epilogue=epilogue,
    )
