"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached (``jax.experimental.topologies``).

Interpret mode hides what only the TPU compiler (Mosaic) refuses:
unaligned or strided sub-32-bit loads, primitives with no TPU lowering,
block shapes off the (8, 128) tiling, tiles over the kernel's scoped
VMEM.  Each test compiles one kernel at the shape ResNet-18 (batch 8,
224x224, ``examples/plans/resnet18_mixed.json``) or granite-8b really
serves, with ``interpret=False``, and checks that the compiled program
holds the Mosaic custom call.  Nothing runs.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library at a time, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dse
from repro.core.packing import PlaneFormat
from repro.kernels.flashattn import ops as flash_ops
from repro.kernels.mpmm import conv_kernel, kernel, ops
from repro.kernels.mpmm import ref as mpmm_ref
from repro.kernels.mpmm.epilogue import EpilogueSpec
from repro.nn.kvcache import KVFormat

BATCH = 8
EPI = EpilogueSpec(bn=True, relu=True, residual=True)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, shardings, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=shardings)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


def _pad(x, m):
    return x + (-x) % m


@pytest.mark.parametrize("m,kdim,n,w_bits,k,epilogue", [
    (BATCH, 512, 1000, 8, 4, None),                      # FC head
    (BATCH * 56 * 56, 9 * 64, 64, 8, 4, EPI),            # s0b0c1 im2col
    (BATCH * 14 * 14, 9 * 256, 256, 2, 2, EPI),          # s2b0c2 im2col
], ids=["fc-w8k4", "conv3x3-w8k4", "conv3x3-w2k2"])
def test_mpmm_autotuned_tile(one_chip, m, kdim, n, w_bits, k, epilogue):
    # Tile and padding exactly as ops.mpmm derives them.
    t = ops.autotune_tile(m, kdim, n, w_bits=w_bits, k=k)
    fmt0 = PlaneFormat(w_bits=w_bits, k=k, k_dim=kdim)
    f = fmt0.digits_per_byte
    bm, bn = t.bm, t.bn
    bk = max(t.bk, f)
    bk += (-bk) % f
    mp, kp, np_ = _pad(m, bm), _pad(kdim, bk), _pad(n, bn)
    fmt = PlaneFormat(w_bits=w_bits, k=k, k_dim=kp)
    cache = (dse.digit_cache_bytes(kp, dse.TileCandidate(bm, bk, bn), fmt)
             <= dse.DIGIT_CACHE_BUDGET_BYTES)
    shapes = [((mp, kp), jnp.int8), ((fmt.planes, kp // f, np_), jnp.uint8),
              ((1, np_), jnp.float32), ((1, np_), jnp.int32)]
    if epilogue is not None:
        shapes += [((1, np_), jnp.float32)] * 2 + [((mp, np_), jnp.bfloat16)]

    def fn(a, w, g, c, *epi):
        kw = dict(zip(("scale", "shift", "residual"), epi))
        return kernel.mpmm_pallas(
            a, w, g, c, fmt=fmt, act_zero=128, tile=(bm, bk, bn),
            epilogue=epilogue, out_dtype=jnp.bfloat16, cache_digits=cache,
            interpret=False, **kw)

    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("hw,c_in,c_out,stride,w_bits,k", [
    (56, 64, 64, 1, 8, 4),      # layer1 (s0b0c1)
    (28, 128, 256, 2, 2, 2),    # stride-2 downsample (s2b0c1)
    (7, 512, 512, 1, 2, 2),     # layer4 (s3b1c1)
], ids=["layer1-w8k4", "down-s2-w2k2", "layer4-w2k2"])
def test_conv_mpmm(one_chip, hw, c_in, c_out, stride, w_bits, k):
    # bn, padding and digit-cache choice exactly as ops.conv_mpmm.
    conv = dse.ConvShape(batch=BATCH, h=hw, w=hw, c_in=c_in, c_out=c_out,
                         kh=3, kw=3, stride=stride)
    bn = dse.choose_conv_dataflow(conv, w_bits=w_bits, k=k).tile_implicit.bn
    fmt = PlaneFormat(w_bits=w_bits, k=k, k_dim=9 * c_in)
    xp = jax.eval_shape(
        lambda a: mpmm_ref.pad_spatial(a, 3, 3, stride, "SAME", fill=-128),
        jax.ShapeDtypeStruct((BATCH, hw, hw, c_in), jnp.int8))
    ho = (xp.shape[1] - 3) // stride + 1
    wo = (xp.shape[2] - 3) // stride + 1
    n = _pad(c_out, bn)
    cache = 9 * c_in * fmt.planes * bn <= dse.DIGIT_CACHE_BUDGET_BYTES

    def fn(x, w, g, c, s, t, r):
        return conv_kernel.conv_mpmm_pallas(
            x, w, g, c, fmt=fmt, act_zero=128, kh=3, kw=3, stride=stride,
            out_hw=(ho, wo), bn=bn, epilogue=EPI, scale=s, shift=t,
            residual=r, out_dtype=jnp.bfloat16, cache_digits=cache,
            interpret=False)

    _compile(fn, one_chip, (xp.shape, jnp.int8),
             ((fmt.planes, 9 * c_in // fmt.digits_per_byte, n), jnp.uint8),
             ((1, n), jnp.float32), ((1, n), jnp.int32),
             ((1, n), jnp.float32), ((1, n), jnp.float32),
             ((BATCH, ho, wo, n), jnp.bfloat16))


def test_named_layer_keeps_its_kernel_name(one_chip, monkeypatch):
    """A layer served under its plan name runs inside that name's scope,
    and its Mosaic call keeps the kernel's own name (``mpmm.<n>``),
    which the device trace and the benchmark's kernel routing read."""
    import re

    from repro import configs
    from repro.core import flags
    from repro.nn import quantized as Q
    monkeypatch.setattr(flags, "default_interpret", lambda: False)
    api = configs.get("resnet18", reduced=True)
    raw = api.init_params(jax.random.PRNGKey(0))["fc"]
    fc = Q.pack_qlinear(raw, api.policy, layer_class="boundary", name="fc")
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), fc)
    x = jax.ShapeDtypeStruct((BATCH, raw["w"].shape[0]), jnp.float32,
                             sharding=one_chip)
    text = jax.jit(lambda p, a: Q.qlinear_serve_apply(
        p, a, api.policy, layer_class="boundary", impl="pallas",
        name="fc")).lower(shapes, x).compile().as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l
             and re.match(r"\s*(ROOT )?%?\w", l)]
    assert calls
    for line in calls:
        name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line).group(1)
        assert name.split(".")[0] == "mpmm"
        assert "/fc/" in re.search(r'op_name="([^"]*)"', line).group(1)


@pytest.mark.parametrize("bits", [2, 4], ids=["kv2", "kv4"])
def test_flash_fwd_packed_granite_8b(one_chip, bits):
    # granite-8b attention widths: 32 query heads over 8 KV heads, D=128.
    b, s, h, kvh, d = 1, 1024, 32, 8, 128
    fmt = KVFormat(bits=bits, k=bits, d=d)
    leaf = [((fmt.planes, b, s, kvh, fmt.packed_d), jnp.uint8),
            ((b, s, kvh), jnp.bfloat16), ((b, s, kvh), jnp.bfloat16)]

    def fn(q, kp, ks, kz, vp, vs, vz):
        return flash_ops.flash_attention_packed(
            q, {"p": kp, "s": ks, "z": kz}, {"p": vp, "s": vs, "z": vz},
            fmt, fmt, causal=True, interpret=False)

    _compile(fn, one_chip, ((b, s, h, d), jnp.bfloat16), *leaf, *leaf)
