"""Pallas TPU kernel for the mixed-precision matmul (BP-ST/SA-1D PE array).

Hardware mapping (DESIGN.md §2):

  * PE array dims (H, W, D)  ->  BlockSpec tile (bm, bk, bn): the 3-D MAC
    loop-nest tiling the paper's DSE optimizes (Eq. 1-3) becomes the VMEM
    tile choice here.
  * PPG operand slice k      ->  digit-plane width of the packed weights;
    all P planes feed ONE MXU contraction per grid step — the plane axis
    is folded into the N axis of the dot and the 2^{kp} shifts applied
    post-dot (``_shift_add``), so a step costs one
    (bm, bk) @ (bk, P*bn) int8 pass instead of P sequential passes.
  * Sum-Together adder tree  ->  one int32 accumulator tile, shift-add
    across planes (`variant='st'`).
  * Sum-Apart registers      ->  one accumulator tile per plane, combined
    in the epilogue (`variant='sa'`) -- P× the accumulator VMEM, exactly
    the register overhead the paper charges SA with.
  * Post-processing pipeline ->  the fused epilogue (epilogue.py): BN /
    residual / ReLU run on the accumulator tile in VMEM, no HBM round
    trip for the int32 partials.

Grid: (N/bn, M/bm, K/bk) — N-tiles OUTERMOST so the uint8->int8 digit
decode of a weight block can be cached in a VMEM scratch and reused
across all M tiles: block (j, kk) is decoded once at the first M step
(i == 0) and read back from the cache for i > 0, i.e. once per (j, k)
rather than once per grid step.  K stays innermost ("arbitrary") so the
accumulator scratch carries across K steps.  ``dimension_semantics``
marks j parallel; i is "arbitrary" while the digit cache is on (its
decode-at-i==0 ordering must not be split across Megacore cores) and
parallel otherwise.  Weights arrive as uint8 packed digit planes
(P, K/(8//k), N);
HBM->VMEM traffic is w_Q/8 of an int8 weight buffer, which is what turns
word-length reduction into a memory-roofline win on TPU.

Activations are int8 *biased* codes (s = u - act_zero); the unsigned
correction act_zero * colsum(W) is folded into the epilogue.
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
from repro.core.roofline import TPU_V5E
from repro.kernels.mpmm import epilogue as _epi
from repro.kernels.mpmm.epilogue import EpilogueSpec

__all__ = ["mpmm_pallas", "VMEM_LIMIT_BYTES"]

# Scoped VMEM each kernel asks the TPU compiler for: half of v5e's
# 128 MiB.  Tiles are budgeted (core/dse) against the 16 MiB a kernel
# gets by default, but the working-set model leaves out the compiler's
# own temporaries (int32 digit fields, sublane padding of the stacked
# fields, operand relayouts): compiles at the edge of that budget
# allocated up to 2.5x the model's figure.  The headroom keeps every
# budget-feasible tile compilable without shrinking it per call.
VMEM_LIMIT_BYTES = int(0.5 * TPU_V5E.vmem_bytes)


def _decode_block(w_u8: jax.Array, fmt: PlaneFormat, bk: int) -> jax.Array:
    """uint8 (P, bkp, bn) -> int8 digits (bk, P*bn), plane-major columns.

    Digits are interleaved 8//k per byte along K (core/packing.pack_bits):
    K index = byte_index * f + field_index.  Plane p occupies columns
    [p*bn, (p+1)*bn) of the result, ready for the fused contraction.

    Planes are split with static ``lax.index_in_dim`` slices: Mosaic has
    no lowering for the ``dynamic_slice`` that integer indexing of a
    value traces to.  Each field is one shift pair — logical for the
    unsigned lower planes, arithmetic (sign-extending, paper Fig. 1b)
    for the top plane — and each plane narrows to int8 before the
    concatenation, which keeps the int32 temporaries to one plane's
    fields: the compiler holds them all in the kernel's scoped VMEM.
    """
    f = fmt.digits_per_byte
    k = fmt.k
    bn = w_u8.shape[-1]
    top_bits = fmt.w_bits - fmt.k * (fmt.planes - 1)
    planes = []
    for p in range(fmt.planes):
        w32 = jax.lax.index_in_dim(w_u8, p, axis=0,
                                   keepdims=False).astype(jnp.int32)
        bits = top_bits if p == fmt.planes - 1 else k
        # Field i holds bits [k*i, k*i + bits): move its top bit to bit
        # 31, then shift back down (arithmetic only for the top plane).
        if p == fmt.planes - 1:
            fields = [(w32 << (32 - k * i - bits)) >> (32 - bits)
                      for i in range(f)]
        else:
            fields = [(w32 >> (k * i)) & ((1 << k) - 1) for i in range(f)]
        # (bkp, f, bn) -> (bk, bn): field index is minor within a byte.
        dig = (fields[0] if f == 1
               else jnp.stack(fields, axis=1).reshape(bk, bn))
        planes.append(dig.astype(jnp.int8))
    # (bk, P*bn): the plane axis folded into N for the dot.
    return jnp.concatenate(planes, axis=-1)


def _shift_add(partial: jax.Array, fmt: PlaneFormat, bn: int) -> jax.Array:
    """(rows, P*bn) per-plane partials -> (rows, bn) sum_p 2^{kp} * plane p.

    Static lane slices of the plane-major columns, the Sum-Together
    adder tree; bit-exact integer arithmetic in any order.
    """
    acc = partial[:, :bn]
    for p in range(1, fmt.planes):
        acc = acc + partial[:, p * bn:(p + 1) * bn] * (1 << (fmt.k * p))
    return acc


def _fused_epilogue(acc, gamma_ref, colsum_ref, epi_refs, out_ref,
                    *, act_zero, epilogue: Optional[EpilogueSpec], out_dtype):
    """VMEM-ref shim over ``epilogue.finish`` — the shared op order."""
    out_ref[...] = _epi.finish(
        acc, gamma_ref[...], colsum_ref[...],
        act_zero=act_zero, spec=epilogue,
        scale=epi_refs["scale"][...] if "scale" in epi_refs else None,
        shift=epi_refs["shift"][...] if "shift" in epi_refs else None,
        residual=(epi_refs["residual"][...] if "residual" in epi_refs
                  else None),
        out_dtype=out_dtype,
    )


def _mpmm_kernel(
    a_ref, w_ref, gamma_ref, colsum_ref, *rest,
    fmt: PlaneFormat, act_zero: int, n_k: int, bk: int, out_dtype,
    variant: str, epilogue: Optional[EpilogueSpec], cache_digits: bool,
):
    """One grid step of the fused mpmm.  Grid order is (j, i, kk)."""
    n_epi = (2 if epilogue is not None and epilogue.bn else 0) + (
        1 if epilogue is not None and epilogue.residual else 0)
    epi_in = rest[:n_epi]
    out_ref = rest[n_epi]
    acc_ref = rest[n_epi + 1]
    dig_ref = rest[n_epi + 2] if cache_digits else None
    epi_refs = {}
    if epilogue is not None and epilogue.bn:
        epi_refs["scale"], epi_refs["shift"] = epi_in[0], epi_in[1]
    if epilogue is not None and epilogue.residual:
        epi_refs["residual"] = epi_in[-1]

    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Decode the packed weight block.  With the cache, slot kk is filled
    # on the first M tile (i == 0) of each j and reused for every later
    # M tile: one decode per (j, kk) weight block.  Without it (VMEM too
    # tight for the strip) the block is decoded in registers per step —
    # no scratch round-trip.
    if cache_digits:
        @pl.when(pl.program_id(1) == 0)
        def _decode():
            dig_ref[kk] = _decode_block(w_ref[...], fmt, bk)
        digits = dig_ref[kk]           # (bk, P*bn) int8
    else:
        digits = _decode_block(w_ref[...], fmt, bk)

    a = a_ref[...]                     # (bm, bk) int8
    # The fused contraction: all P planes in one MXU pass.
    partial = jax.lax.dot_general(
        a, digits, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )                                   # (bm, P*bn) int32
    bm, bn = acc_ref.shape[-2], acc_ref.shape[-1]

    if variant == "st":
        # Sum-Together: shift-add over planes into one accumulator.
        acc_ref[...] += _shift_add(partial, fmt, bn)
    else:
        # Sum-Apart: partial sums stay apart, one accumulator per plane.
        for p in range(fmt.planes):
            acc_ref[p] += partial[:, p * bn:(p + 1) * bn]

    @pl.when(kk == n_k - 1)
    def _epilogue():
        if variant == "st":
            acc = acc_ref[...]
        else:
            acc = jnp.zeros((bm, bn), jnp.int32)
            for p in range(fmt.planes):  # deferred shift-add
                acc = acc + acc_ref[p] * (1 << (fmt.k * p))
        _fused_epilogue(acc, gamma_ref, colsum_ref, epi_refs, out_ref,
                        act_zero=act_zero, epilogue=epilogue,
                        out_dtype=out_dtype)


def mpmm_pallas(
    a_biased: jax.Array,   # int8 (M, K), padded to (bm, bk) multiples
    packed: jax.Array,     # uint8 (P, K//f, N), padded
    gamma: jax.Array,      # f32 (1, N)
    colsum: jax.Array,     # int32 (1, N)
    *,
    fmt: PlaneFormat,
    act_zero: int,
    tile: Tuple[int, int, int],
    variant: str = "st",
    out_dtype=jnp.float32,
    epilogue: Optional[EpilogueSpec] = None,
    scale: Optional[jax.Array] = None,      # f32 (1, N) when epilogue.bn
    shift: Optional[jax.Array] = None,      # f32 (1, N) when epilogue.bn
    residual: Optional[jax.Array] = None,   # (M, N) when epilogue.residual
    cache_digits: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Tiled pallas_call. Caller guarantees divisibility by the tile.

    ``interpret=None`` auto-detects the backend (core/flags
    ``default_interpret``): Mosaic on TPU, interpreter elsewhere.
    ``cache_digits`` keeps the decoded int8 digit strip for the current
    N tile in VMEM (K/bk slots); disable when the strip would not fit.
    """
    m, kdim = a_biased.shape
    p, kp, n = packed.shape
    bm, bk, bn = tile
    f = fmt.digits_per_byte
    assert bk % f == 0, (bk, f)
    bkp = bk // f
    assert m % bm == 0 and kdim % bk == 0 and n % bn == 0, (a_biased.shape, packed.shape, tile)
    assert kp * f == kdim, (kp, f, kdim)
    n_i, n_j, n_k = m // bm, n // bn, kdim // bk
    grid = (n_j, n_i, n_k)  # N outermost (digit-cache reuse), K innermost

    if interpret is None:
        interpret = flags.default_interpret()
    if out_dtype is None:
        out_dtype = jnp.float32
    out_dtype = _epi.resolve_out_dtype(epilogue, out_dtype)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda j, i, kk: (i, kk)),
        pl.BlockSpec((p, bkp, bn), lambda j, i, kk: (0, kk, j)),
        pl.BlockSpec((1, bn), lambda j, i, kk: (0, j)),
        pl.BlockSpec((1, bn), lambda j, i, kk: (0, j)),
    ]
    operands = [a_biased, packed, gamma, colsum]
    if epilogue is not None and epilogue.bn:
        in_specs += [pl.BlockSpec((1, bn), lambda j, i, kk: (0, j))] * 2
        operands += [scale, shift]
    if epilogue is not None and epilogue.residual:
        in_specs.append(pl.BlockSpec((bm, bn), lambda j, i, kk: (i, j)))
        operands.append(residual)

    acc_shape = (bm, bn) if variant == "st" else (p, bm, bn)
    scratch = [pltpu.VMEM(acc_shape, jnp.int32)]
    if cache_digits:
        scratch.append(pltpu.VMEM((n_k, bk, p * bn), jnp.int8))

    return pl.pallas_call(
        functools.partial(
            _mpmm_kernel, fmt=fmt, act_zero=act_zero, n_k=n_k, bk=bk,
            out_dtype=out_dtype, variant=variant, epilogue=epilogue,
            cache_digits=cache_digits,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            # The digit cache makes M steps order-dependent (decode at
            # i == 0, reuse at i > 0), so i must be "arbitrary" while the
            # cache is on — a Megacore split of a "parallel" i would hand
            # one core an i-range with no decode step.  Without the
            # cache, both N and M tiles are freely partitionable.
            dimension_semantics=(
                ("parallel", "arbitrary", "arbitrary") if cache_digits
                else ("parallel", "parallel", "arbitrary")),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*operands)
