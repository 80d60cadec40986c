"""Pallas TPU kernel: convolution as implicit GEMM over packed digit planes.

The im2col serve path materializes an (M, kh·kw·C) patch matrix in HBM
before every conv — ~9x the activation bytes for a 3x3 kernel, plus a
full extra memory round-trip.  The FPGA design this repo reproduces
never does that: the dataflow streams the feature map once and forms
patches on the fly next to the PE array.  This kernel is the TPU
analogue — patches exist only as VMEM gathers:

  * Grid = (N/bn, B, Ho): one output row (b, oh) of one N tile per step.
    The whole K = kh·kw·C reduction runs inside the step, so the int32
    accumulator never leaves registers/VMEM temporaries.
  * The activation BlockSpec fetches the *raw padded* feature map of
    image b once (its block index only changes with b); the step reads
    input rows ``oh*stride + ki`` from it.  Each row is widened to int32
    in a VMEM scratch, and the (Wo, C) patch strip of kernel column kj
    is one 32-bit strided load ``row[kj : kj+(Wo-1)*s+1 : s]`` from it:
    Mosaic refuses an int8 load at a sublane offset it cannot prove
    aligned, and any strided load of non-32-bit data.  ki and kj are
    static (unrolled), so every offset is a compile-time constant.
  * Weights arrive exactly as in the matmul kernel (uint8 packed digit
    planes, K = kh·kw·C in im2col (kh, kw, C) order), viewed as
    (P, kh·kw, C/f, N) so each kernel position is a leading-dim index,
    and feed the same one-contraction-per-position digit-plane dot: the
    (Wo, C) strip against the decoded (C, P*bn) digit block, 2^{kp}
    shifts post-dot.
  * The fused EpilogueSpec (BN / residual / ReLU) runs on the int32
    accumulator at the end of the step — identical op order to mpmm
    (epilogue.finish), so conv output is bit-exact vs the im2col
    reference.

Constraints (callers route through ops.conv_mpmm / nn.qconv_serve_apply,
which fall back to im2col when violated): C divisible by the packed
digits-per-byte f = 8//k, so every kernel position starts at a byte
boundary of the packed K axis; activations pre-padded spatially with
``-act_zero`` (the biased code of a float 0 — what im2col's zero padding
quantizes to, keeping the colsum zero-point correction exact).

The digit cache mirrors kernel.py §2.2: the decoded (C, P*bn) strip of
each kernel position is cached per N tile at the first (b, oh) step and
reused by every later output row — one decode per (j, position) instead
of B·Ho of them.  While the cache is on, the B and Ho dims are
"arbitrary" (the decode-at-first-step ordering must not be split across
Megacore cores); N stays parallel.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import flags
from repro.core.packing import PlaneFormat
from repro.kernels.mpmm import epilogue as _epi
from repro.kernels.mpmm.epilogue import EpilogueSpec
from repro.kernels.mpmm.kernel import (VMEM_LIMIT_BYTES, _decode_block,
                                       _shift_add)

__all__ = ["conv_mpmm_pallas"]


def _conv_kernel(
    x_ref, w_ref, gamma_ref, colsum_ref, *rest,
    fmt: PlaneFormat, act_zero: int, kh: int, kw: int, stride: int,
    wo: int, out_dtype, variant: str, epilogue: Optional[EpilogueSpec],
    cache_digits: bool,
):
    """One grid step: every kernel position of one output row."""
    n_epi = (2 if epilogue is not None and epilogue.bn else 0) + (
        1 if epilogue is not None and epilogue.residual else 0)
    epi_in = rest[:n_epi]
    out_ref = rest[n_epi]
    row_ref = rest[n_epi + 1]
    dig_ref = rest[n_epi + 2] if cache_digits else None
    epi_refs = {}
    if epilogue is not None and epilogue.bn:
        epi_refs["scale"], epi_refs["shift"] = epi_in[0], epi_in[1]
    if epilogue is not None and epilogue.residual:
        epi_refs["residual"] = epi_in[-1]

    c = x_ref.shape[-1]
    bn = out_ref.shape[-1]
    oh = pl.program_id(2)
    if cache_digits:
        first_row = (pl.program_id(1) == 0) & (oh == 0)

        @pl.when(first_row)
        def _decode():
            for pos in range(kh * kw):
                dig_ref[pos] = _decode_block(w_ref[:, pos], fmt, c)

    # Sum-Together keeps one accumulator; Sum-Apart one per plane,
    # combined by the deferred shift-add below.
    accs = [jnp.zeros((wo, bn), jnp.int32)
            for _ in range(1 if variant == "st" else fmt.planes)]
    for ki in range(kh):
        row_ref[...] = x_ref[0, oh * stride + ki].astype(jnp.int32)
        for kj in range(kw):
            pos = ki * kw + kj
            digits = (dig_ref[pos] if cache_digits
                      else _decode_block(w_ref[:, pos], fmt, c))
            # The implicit patch: output column wo' reads input column
            # wo'*stride + kj of the row.
            strip = row_ref[pl.ds(kj, wo, stride=stride), :].astype(jnp.int8)
            partial = jax.lax.dot_general(
                strip, digits, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )                              # (Wo, P*bn) int32
            if variant == "st":
                accs[0] = accs[0] + _shift_add(partial, fmt, bn)
            else:
                for p in range(fmt.planes):
                    accs[p] = accs[p] + partial[:, p * bn:(p + 1) * bn]
    acc = accs[0]
    for p in range(1, len(accs)):          # Sum-Apart deferred shift-add
        acc = acc + accs[p] * (1 << (fmt.k * p))
    out_ref[0, 0] = _epi.finish(
        acc, gamma_ref[...], colsum_ref[...],
        act_zero=act_zero, spec=epilogue,
        scale=epi_refs["scale"][...] if "scale" in epi_refs else None,
        shift=epi_refs["shift"][...] if "shift" in epi_refs else None,
        residual=(epi_refs["residual"][0, 0] if "residual" in epi_refs
                  else None),
        out_dtype=out_dtype,
    )


def conv_mpmm_pallas(
    x_padded: jax.Array,   # int8 (B, H_pad, W_pad, C), spatially pre-padded
    packed: jax.Array,     # uint8 (P, (kh*kw*C)//f, N), N padded to bn
    gamma: jax.Array,      # f32 (1, N)
    colsum: jax.Array,     # int32 (1, N)
    *,
    fmt: PlaneFormat,
    act_zero: int,
    kh: int,
    kw: int,
    stride: int,
    out_hw: Tuple[int, int],
    bn: int,
    variant: str = "st",
    out_dtype=jnp.float32,
    epilogue: Optional[EpilogueSpec] = None,
    scale: Optional[jax.Array] = None,      # f32 (1, N) when epilogue.bn
    shift: Optional[jax.Array] = None,      # f32 (1, N) when epilogue.bn
    residual: Optional[jax.Array] = None,   # (B, Ho, Wo, N)
    cache_digits: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Tiled pallas_call -> (B, Ho, Wo, N).  Caller pads N and space.

    ``x_padded`` must already carry the conv's spatial padding, filled
    with the biased zero code ``-act_zero``; ``out_hw`` is the (Ho, Wo)
    implied by the original padding/stride.  ``packed`` is the standard
    mpmm plane layout over K = kh*kw*C in (kh, kw, C) order — the same
    bytes the im2col path consumes, no conv-specific repack.
    """
    b, h_pad, w_pad, c = x_padded.shape
    p, kp, n = packed.shape
    ho, wo = out_hw
    f = fmt.digits_per_byte
    assert c % f == 0, (c, f)
    assert kp * f == kh * kw * c, (kp, f, kh, kw, c)
    assert n % bn == 0, (n, bn)
    assert (ho - 1) * stride + kh <= h_pad, (ho, stride, kh, h_pad)
    assert (wo - 1) * stride + kw <= w_pad, (wo, stride, kw, w_pad)
    n_j, n_k = n // bn, kh * kw
    grid = (n_j, b, ho)  # N outermost (digit cache), output rows inner

    if interpret is None:
        interpret = flags.default_interpret()
    if out_dtype is None:
        out_dtype = jnp.float32
    out_dtype = _epi.resolve_out_dtype(epilogue, out_dtype)

    # (P, kh*kw*C/f, N) -> (P, kh*kw, C/f, N): a free row-major view that
    # makes each kernel position a leading-dim index in the kernel.
    packed = packed.reshape(p, n_k, c // f, n)
    in_specs = [
        # The whole padded image b; re-fetched only when b changes.
        pl.BlockSpec((1, h_pad, w_pad, c), lambda j, bb, oh: (bb, 0, 0, 0)),
        pl.BlockSpec((p, n_k, c // f, bn), lambda j, bb, oh: (0, 0, 0, j)),
        pl.BlockSpec((1, bn), lambda j, bb, oh: (0, j)),
        pl.BlockSpec((1, bn), lambda j, bb, oh: (0, j)),
    ]
    operands = [x_padded, packed, gamma, colsum]
    if epilogue is not None and epilogue.bn:
        in_specs += [pl.BlockSpec((1, bn), lambda j, bb, oh: (0, j))] * 2
        operands += [scale, shift]
    if epilogue is not None and epilogue.residual:
        in_specs.append(pl.BlockSpec(
            (1, 1, wo, bn), lambda j, bb, oh: (bb, oh, 0, j)))
        operands.append(residual)

    scratch = [pltpu.VMEM((w_pad, c), jnp.int32)]   # widened input row
    if cache_digits:
        scratch.append(pltpu.VMEM((n_k, c, p * bn), jnp.int8))

    return pl.pallas_call(
        functools.partial(
            _conv_kernel, fmt=fmt, act_zero=act_zero, kh=kh, kw=kw,
            stride=stride, wo=wo, out_dtype=out_dtype, variant=variant,
            epilogue=epilogue, cache_digits=cache_digits,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, wo, bn),
                               lambda j, bb, oh: (bb, oh, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, ho, wo, n), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            # Same Megacore rule as the matmul kernel: with the digit
            # cache on, the decode-at-first-output-row ordering makes the
            # B and Ho dims order-dependent, so only N may be split.
            dimension_semantics=(
                ("parallel", "arbitrary", "arbitrary") if cache_digits
                else ("parallel", "parallel", "parallel")),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*operands)
