"""Bit-plane decomposition and packed HBM storage of quantized weights.

This is the TPU adaptation of the paper's Partial-Product Generator (PPG)
segmentation (Fig. 1b, Section III-A): a w_Q-bit signed weight is split
into ``P = ceil(w_Q / k)`` two's-complement digit planes of the *operand
slice* ``k`` bits each,

    w = sum_{p=0}^{P-2}  plane_p * 2^{k p}   +   plane_{P-1} * 2^{k (P-1)}
        (unsigned digits)                        (signed top digit)

so a matmul against w becomes P shifted matmuls against small-integer
planes — exactly the adder-tree (Sum-Together) or per-plane (Sum-Apart)
consolidation the paper explores, executed on the MXU instead of on LUTs.

Planes are *packed* ``8 // k`` digits per byte along the contraction (K)
axis for HBM storage, so the weight footprint in bytes is w_Q/8 of the
int8 baseline — this is what turns word-length reduction into a
proportionate memory-roofline gain on TPU.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PlaneFormat",
    "num_planes",
    "split_planes",
    "combine_planes",
    "pack_planes",
    "unpack_planes",
    "pack_bits",
    "packed_weight_bytes",
]


def num_planes(w_bits: int, k: int) -> int:
    return int(math.ceil(w_bits / k))


@dataclasses.dataclass(frozen=True)
class PlaneFormat:
    """Storage format of one weight tensor in packed bit-plane form.

    Attributes:
      w_bits: quantized word-length w_Q of the weights (1/2/4/8).
      k:      operand slice in bits (1/2/4/8); k <= w_bits is the useful
              regime (k > w_bits wastes PPG capacity, Section IV-A).
      k_dim:  length of the contraction axis (pre-packing).
      signed: whether the top plane carries the two's-complement sign.
    """

    w_bits: int
    k: int
    k_dim: int
    signed: bool = True

    @property
    def planes(self) -> int:
        return num_planes(self.w_bits, self.k)

    @property
    def digits_per_byte(self) -> int:
        if 8 % self.k != 0:
            raise ValueError(f"operand slice k={self.k} must divide 8")
        return 8 // self.k

    @property
    def packed_k(self) -> int:
        return int(math.ceil(self.k_dim / self.digits_per_byte))


def split_planes(w_int: jax.Array, w_bits: int, k: int) -> jax.Array:
    """Split signed integer codes into k-bit two's-complement digit planes.

    Args:
      w_int: integer weight codes in [-2^{w_bits-1}, 2^{w_bits-1} - 1]
             (any integer dtype), arbitrary shape (..., K, N).
      w_bits: word-length of the codes.
      k: operand-slice width; must divide 8.

    Returns:
      int32 array of shape (P, *w_int.shape) where P = ceil(w_bits / k).
      Lower planes hold unsigned digits in [0, 2^k); the top plane is
      sign-extended to [-2^{k-1}, 2^{k-1}) when w_bits is a multiple of k
      (otherwise the residual top bits, sign-extended).
    """
    p = num_planes(w_bits, k)
    u = jnp.asarray(w_int, jnp.int32) & ((1 << w_bits) - 1)  # two's-complement bits
    planes = []
    for i in range(p):
        digit = (u >> (k * i)) & ((1 << k) - 1)
        if i == p - 1:
            # Top digit carries the sign: occupies bits [k*(p-1), w_bits).
            top_bits = w_bits - k * (p - 1)
            sign_bit = 1 << (top_bits - 1)
            digit = jnp.where(digit >= sign_bit, digit - (1 << top_bits), digit)
        planes.append(digit)
    return jnp.stack(planes, axis=0)


def combine_planes(planes: jax.Array, k: int) -> jax.Array:
    """Inverse of :func:`split_planes`: sum_p plane_p * 2^{k p} (int32)."""
    p = planes.shape[0]
    weights = (2 ** (k * jnp.arange(p, dtype=jnp.int32))).reshape((p,) + (1,) * (planes.ndim - 1))
    return jnp.sum(planes.astype(jnp.int32) * weights, axis=0)


def pack_bits(digits: jax.Array, k: int, axis: int = -2) -> jax.Array:
    """Pack k-bit unsigned digits along ``axis``, 8//k per byte (uint8).

    ``digits`` must be non-negative and < 2^k (top planes are biased by the
    caller before packing). Pads the packed axis with zeros if needed.
    """
    f = 8 // k
    axis = axis % digits.ndim
    n = digits.shape[axis]
    pad = (-n) % f
    if pad:
        pw = [(0, 0)] * digits.ndim
        pw[axis] = (0, pad)
        digits = jnp.pad(digits, pw)
    new_shape = list(digits.shape)
    new_shape[axis] = digits.shape[axis] // f
    new_shape.insert(axis + 1, f)
    d = digits.reshape(new_shape).astype(jnp.uint32)
    shifts = (k * jnp.arange(f, dtype=jnp.uint32)).reshape(
        (1,) * (axis + 1) + (f,) + (1,) * (digits.ndim - axis - 1)
    )
    packed = jnp.sum(d << shifts, axis=axis + 1)
    return packed.astype(jnp.uint8)


def _unpack_bits(packed: jax.Array, k: int, k_dim: int, axis: int = -2) -> jax.Array:
    """Unpack uint8 bytes into k-bit unsigned digits along ``axis``."""
    f = 8 // k
    axis = axis % packed.ndim
    p32 = packed.astype(jnp.uint32)
    parts = [(p32 >> (k * i)) & ((1 << k) - 1) for i in range(f)]
    stacked = jnp.stack(parts, axis=axis + 1)  # (..., packed_k, f, ...)
    new_shape = list(packed.shape)
    new_shape[axis] = packed.shape[axis] * f
    out = stacked.reshape(new_shape)
    slicer = [slice(None)] * out.ndim
    slicer[axis] = slice(0, k_dim)
    return out[tuple(slicer)].astype(jnp.int32)


def pack_planes(w_int: jax.Array, fmt: PlaneFormat, axis: int = -2) -> jax.Array:
    """Quantized codes -> packed uint8 bit-planes (HBM storage format).

    Args:
      w_int: signed codes, shape (..., K, N) with K at ``axis``.
      fmt:   plane format (w_bits, k, K).

    Returns:
      uint8 array of shape (P, ..., ceil(K / (8//k)), N): plane-major so a
      kernel streams one plane at a time. The top plane's digits are stored
      biased (two's-complement k-bit field) and re-signed on unpack.
    """
    planes = split_planes(w_int, fmt.w_bits, fmt.k)  # (P, ..., K, N), top signed
    top_bits = fmt.w_bits - fmt.k * (fmt.planes - 1)
    top = planes[-1] & ((1 << top_bits) - 1)  # store raw two's-complement field
    planes = jnp.concatenate([planes[:-1], top[None]], axis=0)
    return pack_bits(planes, fmt.k, axis=axis % w_int.ndim + 1)


def unpack_planes(packed: jax.Array, fmt: PlaneFormat, axis: int = -2) -> jax.Array:
    """Packed uint8 planes -> int8 digit planes (VMEM compute format).

    Returns int8 of shape (P, ..., K, N); lower planes in [0, 2^k), top
    plane sign-extended. int8 is the MXU-native operand width.
    """
    digits = _unpack_bits(packed, fmt.k, fmt.k_dim, axis=axis % (packed.ndim - 1) + 1)
    if fmt.signed:
        top_bits = fmt.w_bits - fmt.k * (fmt.planes - 1)
        sign_bit = 1 << (top_bits - 1)
        top = digits[-1]
        top = jnp.where(top >= sign_bit, top - (1 << top_bits), top)
        digits = jnp.concatenate([digits[:-1], top[None]], axis=0)
    return digits.astype(jnp.int8)


def packed_weight_bytes(k_dim: int, n_dim: int, w_bits: int, k: int) -> int:
    """HBM bytes of one packed weight tensor (excluding the gamma scale)."""
    fmt = PlaneFormat(w_bits=w_bits, k=k, k_dim=k_dim)
    return fmt.planes * fmt.packed_k * n_dim


def random_codes(rng: np.random.Generator, shape: Tuple[int, ...], w_bits: int) -> np.ndarray:
    """Uniform signed codes for tests/benchmarks."""
    lo, hi = -(2 ** (w_bits - 1)), 2 ** (w_bits - 1) - 1
    return rng.integers(lo, hi + 1, size=shape, dtype=np.int32)
