"""Pallas TPU flash-attention forward kernel (prefill path).

Why this kernel exists (EXPERIMENTS.md §Perf, granite-34b prefill_32k):
the XLA chunked-softmax attention materializes every (Sq, chunk) score
tile at a fusion boundary, so a 32k-token prefill moves O(S^2) bytes of
HBM per layer — it dominated the memory-roofline term of every prefill
cell.  Here scores live only in VMEM: HBM traffic is exactly Q + K + V
reads and O writes, the flash-attention contract.

TPU mapping:
  * grid = (batch, q_heads, Sq / block_q); the KV sweep is a fori_loop
    inside the kernel so the f32 accumulator tile never leaves VMEM.
  * block shapes are multiples of (8, 128) so the MXU sees aligned
    (block_q x head_dim) x (head_dim x block_k) passes.
  * q is pre-scaled; softmax runs online (running max m / sum l) in f32
    exactly like the FPGA paper's partial-sum consolidation runs the
    adder tree at full precision while operands stay narrow.
  * causal + local-window masks are applied as additive biases computed
    from iota inside the kernel (no mask tensors in HBM).

The kernel is MHA: GQA head mapping (q head -> kv head) is resolved by
the caller (ops.py) with a cheap gather on the replicated KV heads, so
the kernel body stays free of division logic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_k: int,
                seq_k: int, causal: bool, window: Optional[int],
                q_offset: int, softmax_scale: float):
    """One (batch, head, q-block) cell: sweep KV blocks with online softmax.

    Refs (VMEM blocks):
      q_ref: (block_q, d)   k_ref/v_ref: (seq_k, d)   o_ref: (block_q, d)
    """
    qb = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * softmax_scale      # (bq, d)
    q_pos = q_offset + qb * block_q + jax.lax.iota(
        jnp.int32, block_q)                                  # absolute rows

    n_kb = seq_k // block_k

    def body(kb, carry):
        acc, m, l = carry
        ks = k_ref[pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        vs = v_ref[pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kv_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)

    if causal:
        # only sweep KV blocks that intersect the causal/window band
        last = (q_offset + (qb + 1) * block_q + block_k - 1) // block_k
        n_sweep = jnp.minimum(last, n_kb)
    else:
        n_sweep = n_kb
    acc, m, l = jax.lax.fori_loop(0, n_sweep, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _fwd_kernel_packed(q_ref, kp_ref, ks_ref, kz_ref, vp_ref, vs_ref, vz_ref,
                       o_ref, *, block_q: int, block_k: int, seq_k: int,
                       causal: bool, window: Optional[int], q_offset: int,
                       softmax_scale: float, k_slice: int, v_slice: int,
                       head_dim: int):
    """Packed-KV cell: decode digit planes in VMEM, contract low-bit codes.

    K and V arrive as uint8 digit planes (the HBM cache layout of
    nn/kvcache.py) with per-(token, head) affine scale/zero.  The affine
    identity  q . (code*s + z) = s * (q . code) + z * sum(q)  lets the
    kernel contract the small-integer digit planes directly and fold the
    grid back in per KV row — the PPG Sum-Together pattern applied to
    attention scores — so dequantized K/V rows never materialize in VMEM.

    Refs (VMEM blocks):
      q_ref: (block_q, d)
      kp_ref/vp_ref: (P, seq_k, packed_d) uint8 digit planes
      ks_ref/kz_ref/vs_ref/vz_ref: (1, seq_k) f32 per-token scale / zero
      o_ref: (block_q, d)
    """
    qb = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * softmax_scale      # (bq, d)
    q_sum = jnp.sum(q, axis=-1)              # multiplies the K zero-point
    q_pos = q_offset + qb * block_q + jax.lax.iota(jnp.int32, block_q)

    n_kb = seq_k // block_k

    def digits_of(planes_u8, slice_bits):
        """(P, bk, packed_d) uint8 bytes -> (P, bk, d) f32 digit planes."""
        f = 8 // slice_bits
        mask = (1 << slice_bits) - 1
        p32 = planes_u8.astype(jnp.int32)
        parts = [(p32 >> (slice_bits * j)) & mask for j in range(f)]
        dig = jnp.stack(parts, axis=-1)                   # (P, bk, pd, f)
        dig = dig.reshape(dig.shape[0], dig.shape[1], -1)[:, :, :head_dim]
        return dig.astype(jnp.float32)

    def body(kb, carry):
        acc, m, l = carry
        kdig = digits_of(
            kp_ref[:, pl.dslice(kb * block_k, block_k), :], k_slice)
        ks = ks_ref[0, pl.dslice(kb * block_k, block_k)]
        kz = kz_ref[0, pl.dslice(kb * block_k, block_k)]
        s_codes = jnp.zeros((block_q, block_k), jnp.float32)
        for p_i in range(kdig.shape[0]):                  # static unroll
            s_codes += float(1 << (k_slice * p_i)) * jax.lax.dot_general(
                q, jax.lax.index_in_dim(kdig, p_i, keepdims=False),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        s = s_codes * ks[None, :] + q_sum[:, None] * kz[None, :]

        kv_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)

        vdig = digits_of(
            vp_ref[:, pl.dslice(kb * block_k, block_k), :], v_slice)
        vs = vs_ref[0, pl.dslice(kb * block_k, block_k)]
        vz = vz_ref[0, pl.dslice(kb * block_k, block_k)]
        # p . (code*s + z): fold the V scale into p, zero-term is rank-1.
        pw = p * vs[None, :]
        pv = jnp.zeros((block_q, head_dim), jnp.float32)
        for p_i in range(vdig.shape[0]):
            pv += float(1 << (v_slice * p_i)) * jax.lax.dot_general(
                pw, jax.lax.index_in_dim(vdig, p_i, keepdims=False),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        pv += jnp.sum(p * vz[None, :], axis=-1)[:, None]
        acc_new = acc * alpha[:, None] + pv
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((block_q, head_dim), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)

    if causal:
        last = (q_offset + (qb + 1) * block_q + block_k - 1) // block_k
        n_sweep = jnp.minimum(last, n_kb)
    else:
        n_sweep = n_kb
    acc, m, l = jax.lax.fori_loop(0, n_sweep, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_fwd_packed(
    q: jax.Array,            # (B, H, Sq, D)   — kernel layout
    kp: jax.Array,           # (B, H, Pk, Sk, packed_dk) uint8
    ks: jax.Array,           # (B, H, Sk) f32
    kz: jax.Array,           # (B, H, Sk) f32
    vp: jax.Array,           # (B, H, Pv, Sk, packed_dv) uint8
    vs: jax.Array,           # (B, H, Sk) f32
    vz: jax.Array,           # (B, H, Sk) f32
    *,
    k_slice: int,
    v_slice: int,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    softmax_scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, d = q.shape
    sk = kp.shape[3]
    pk, pdk = kp.shape[2], kp.shape[4]
    pv_, pdv = vp.shape[2], vp.shape[4]
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)

    kernel = functools.partial(
        _fwd_kernel_packed, block_q=block_q, block_k=block_k, seq_k=sk,
        causal=causal, window=window, q_offset=q_offset, softmax_scale=scale,
        k_slice=k_slice, v_slice=v_slice, head_dim=d)

    # Per-token scales ride as (B, H, 1, Sk) so the block's last two dims
    # equal the array's — Mosaic's (8, 128) block rule.
    ks, kz, vs, vz = (t[:, :, None, :] for t in (ks, kz, vs, vz))
    seq_spec = pl.BlockSpec((None, None, 1, sk),
                            lambda ib, ih, iq: (ib, ih, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b, h, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda ib, ih, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((None, None, pk, sk, pdk),
                         lambda ib, ih, iq: (ib, ih, 0, 0, 0)),
            seq_spec, seq_spec,
            pl.BlockSpec((None, None, pv_, sk, pdv),
                         lambda ib, ih, iq: (ib, ih, 0, 0, 0)),
            seq_spec, seq_spec,
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda ib, ih, iq: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        interpret=interpret,
    )(q, kp, ks, kz, vp, vs, vz)
    return out


def flash_fwd(
    q: jax.Array,            # (B, Sq, H, D)
    k: jax.Array,            # (B, Sk, H, D)  (same head count as q)
    v: jax.Array,            # (B, Sk, H, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    softmax_scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)

    # layout: (B, H, S, D) so the grid can tile the q sequence
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, seq_k=sk,
        causal=causal, window=window, q_offset=q_offset,
        softmax_scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda ib, ih, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((None, None, sk, d),
                         lambda ib, ih, iq: (ib, ih, 0, 0)),
            pl.BlockSpec((None, None, sk, d),
                         lambda ib, ih, iq: (ib, ih, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda ib, ih, iq: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)
