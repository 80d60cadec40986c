"""Quantized linear layers: QAT (train) and packed-plane (serve) modes.

Train mode (paper Section IV-C): LSQ fake-quant of both operands —
activations unsigned 8 bit, weights signed w_Q bit with trained step
sizes — then a bf16 dot.  This is the QAT forward the paper trains for
30 epochs.

Serve mode: the deployed form.  Weights live as packed k-bit digit
planes (uint8, DESIGN.md §2), activations are quantized on the fly to
biased int8 codes, and the product runs through the mpmm kernel — the
precision-scalable BP-ST-1D PE array.  Word-length w_Q can differ per
layer (layer-wise) and gamma_w per output channel (channel-wise) without
touching the kernel, the paper's "no new FPGA image" property.

A qlinear param subtree is identified by the marker key '__q__'; tree
transformations (pack_tree) rewrite those subtrees wholesale.  The
marker carries the layer's class AND its workload layer name, so a
layer-wise ``PrecisionPlan`` resolves per-layer formats anywhere the
subtree travels: every spec/apply/pack entry point below accepts a
``PrecisionPolicy`` OR a ``PrecisionPlan`` plus the layer ``name`` and
funnels both through ``core.plan.resolve_policy`` — the single
resolution point of the layer namespace (DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import packing, quant
from repro.core import plan as plan_lib
from repro.core.packing import PlaneFormat
from repro.core.plan import PolicyOrPlan
from repro.core.precision import PrecisionPolicy
from repro.kernels.mpmm import epilogue as mpmm_epilogue
from repro.kernels.mpmm import ops as mpmm_ops
from repro.kernels.mpmm.epilogue import EpilogueSpec
from repro.nn.param import ParamSpec

__all__ = [
    "qlinear_spec",
    "qlinear_apply",
    "qlinear_serve_spec",
    "qlinear_serve_apply",
    "qconv_spec",
    "qconv_apply",
    "qconv_serve_apply",
    "conv_serve_dataflow",
    "im2col",
    "pack_qlinear",
    "pack_tree",
    "QMARK",
    "EpilogueSpec",
]

QMARK = "__q__"


def _marker(layer_class: str, name: str = "") -> ParamSpec:
    # Zero-size marker carrying the layer class and the workload layer
    # name in its axes metadata slots (markers are stripped before any
    # materialization/sharding, so the slots are free-form).
    return ParamSpec(shape=(0, 0), dtype=jnp.float32,
                     axes=(layer_class, name or None), init="zeros")


def qlinear_spec(
    in_dim: int,
    out_dim: int,
    *,
    axes: Tuple[Optional[str], str] = ("embed", "mlp"),
    layer_class: str = "inner",
    channel_wise: bool = False,
    bias: bool = False,
    lead: Tuple[int, ...] = (),
    lead_axes: Tuple[Optional[str], ...] = (),
    dtype=jnp.float32,
    name: str = "",
) -> Dict[str, ParamSpec]:
    """Spec of one QAT linear: master weight + LSQ step sizes.

    lead/lead_axes: optional leading dims (e.g. ('layers',) for
    scan-over-layers stacking, ('experts',) for MoE banks).
    ``name``: the gemm_workload layer name this linear answers to — it
    rides in the marker so pack/serve resolve the same per-layer format.
    """
    gshape = lead + ((out_dim,) if channel_wise else ())
    gaxes = lead_axes + ((axes[1],) if channel_wise else ())
    return {
        QMARK: _marker(layer_class, name),
        "w": ParamSpec(
            shape=lead + (in_dim, out_dim),
            dtype=dtype,
            axes=lead_axes + axes,
            init="normal",
            fan_in_axes=(-2,),
        ),
        "gw": ParamSpec(shape=gshape, dtype=jnp.float32, axes=gaxes, init="constant",
                        const=0.05),
        "ga": ParamSpec(shape=lead, dtype=jnp.float32, axes=lead_axes, init="constant",
                        const=0.05),
        **(
            {"b": ParamSpec(shape=lead + (out_dim,), dtype=jnp.float32,
                            axes=lead_axes + (axes[1],), init="zeros")}
            if bias
            else {}
        ),
    }


def is_qlinear(sub) -> bool:
    return isinstance(sub, dict) and QMARK in sub


def _layer_class_of(sub: Dict) -> str:
    mark = sub[QMARK]
    axes = mark.axes if isinstance(mark, ParamSpec) else ("inner",)
    return axes[0] or "inner"


def _layer_name_of(sub: Dict) -> str:
    """The workload layer name the marker carries ('' on legacy markers)."""
    mark = sub[QMARK]
    axes = mark.axes if isinstance(mark, ParamSpec) else ()
    return (axes[1] or "") if len(axes) > 1 else ""


def qlinear_apply(
    p: Dict[str, jax.Array],
    x: jax.Array,
    policy: PolicyOrPlan,
    *,
    layer_class: str = "inner",
    quantize_act: bool = True,
    compute_dtype=jnp.bfloat16,
    name: str = "",
) -> jax.Array:
    """QAT forward: fake-quant(act) @ fake-quant(w) (+ b)."""
    policy = plan_lib.resolve_policy(policy, name)
    w, gw, ga = p["w"], p["gw"], p["ga"]
    if policy.quantize:
        w_bits = policy.bits_for(layer_class)
        wspec = quant.weight_spec(w_bits, channel_axis=-1 if gw.ndim > 0 and policy.channel_wise else None)
        w = quant.fake_quant(w.astype(jnp.float32), gw, wspec)
        if quantize_act:
            # activation fake-quant stays in the activation dtype (bf16):
            # 8-bit codes are exact in bf16 and the f32 round-trip was a
            # top byte-mover in the train-step HLO (§Perf).
            aspec = quant.act_spec(policy.a_bits)
            x = quant.fake_quant(x, ga, aspec)
    y = jnp.einsum(
        "...k,kn->...n",
        x.astype(compute_dtype),
        w.astype(compute_dtype),
    )
    if "b" in p:
        y = y + p["b"].astype(compute_dtype)
    return y


# ---------------------------------------------------------------------------
# Serve mode: packed digit planes.
# ---------------------------------------------------------------------------


def qlinear_serve_spec(
    in_dim: int,
    out_dim: int,
    *,
    axes: Tuple[Optional[str], str] = ("embed", "mlp"),
    layer_class: str = "inner",
    policy: PolicyOrPlan = PrecisionPolicy(),
    bias: bool = False,
    lead: Tuple[int, ...] = (),
    lead_axes: Tuple[Optional[str], ...] = (),
    name: str = "",
) -> Dict[str, ParamSpec]:
    """Spec of the deployed (packed) form — shapes for the dry-run.

    ``policy`` may be a layer-wise plan: the spec shapes (plane count,
    packed-K bytes) come from THIS layer's resolved format.
    """
    policy = plan_lib.resolve_policy(policy, name)
    w_bits = policy.bits_for(layer_class) if policy.quantize else 16
    if not policy.quantize:
        # FP baseline deployment: bf16 weights, plain matmul.
        return {
            QMARK: _marker(layer_class, name),
            "w": ParamSpec(shape=lead + (in_dim, out_dim), dtype=jnp.bfloat16,
                           axes=lead_axes + axes, init="normal", fan_in_axes=(-2,)),
            **({"b": ParamSpec(shape=lead + (out_dim,), dtype=jnp.float32,
                               axes=lead_axes + (axes[1],), init="zeros")} if bias else {}),
        }
    # k > w_bits is allowed (PPG partially idle, paper IV-A): storage uses
    # full k-bit digit slots, so the waste shows up in the memory term.
    fmt = PlaneFormat(w_bits=w_bits, k=policy.k, k_dim=in_dim)
    # The packed contraction axis is named after the true input axis so
    # serve rules can row-parallel-shard projections whose OUTPUT is the
    # residual stream (down/o: axes[1] == 'act_embed' maps to None).
    k_axis = f"{axes[0]}_packed" if axes[0] else None
    return {
        QMARK: _marker(layer_class, name),
        "planes": ParamSpec(
            shape=lead + (fmt.planes, fmt.packed_k, out_dim),
            dtype=jnp.uint8,
            axes=lead_axes + ("plane", k_axis, axes[1]),
            init="zeros",
        ),
        "colsum": ParamSpec(shape=lead + (1, out_dim), dtype=jnp.int32,
                            axes=lead_axes + (None, axes[1]), init="zeros"),
        "gamma": ParamSpec(shape=lead + (1, out_dim), dtype=jnp.float32,
                           axes=lead_axes + (None, axes[1]), init="constant", const=1e-3),
        "ga": ParamSpec(shape=lead, dtype=jnp.float32, axes=lead_axes,
                        init="constant", const=0.05),
        **(
            {"b": ParamSpec(shape=lead + (out_dim,), dtype=jnp.float32,
                            axes=lead_axes + (axes[1],), init="zeros")}
            if bias
            else {}
        ),
    }


def _fold_bias(p, epilogue, scale, shift):
    """Fold a layer bias into the epilogue's scale/shift stage.

    A bias must enter BEFORE the epilogue post-ops (the QAT forward adds
    it straight after the matmul), so it becomes part of the folded-BN
    affine instead of a post-kernel add.  Shared by the linear and conv
    serve paths.
    """
    if "b" in p and epilogue is not None:
        b = jnp.asarray(p["b"], jnp.float32).reshape(1, -1)
        if epilogue.bn:
            shift = shift.astype(jnp.float32) + b * scale.astype(jnp.float32)
        else:
            epilogue = dataclasses.replace(epilogue, bn=True)
            scale = jnp.ones_like(b)
            shift = b
    return epilogue, scale, shift


def _layer_scope(fn):
    """Trace ``fn`` under ``jax.named_scope(name)`` when it is given a
    layer ``name``: every op it lowers to then carries the layer's name
    in its ``op_name`` metadata, so a device trace's ops map back to
    the plan's layers.  Kernel names are left as they are."""
    @functools.wraps(fn)
    def scoped(*args, name: str = "", **kw):
        if not name:
            return fn(*args, name=name, **kw)
        with jax.named_scope(name):
            return fn(*args, name=name, **kw)
    return scoped


@_layer_scope
def qlinear_serve_apply(
    p: Dict[str, jax.Array],
    x: jax.Array,
    policy: PolicyOrPlan,
    *,
    layer_class: str = "inner",
    tile: Optional[mpmm_ops.TileShape] = None,
    impl: str = "xla",
    compute_dtype=jnp.bfloat16,
    epilogue: Optional[EpilogueSpec] = None,
    scale: Optional[jax.Array] = None,
    shift: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    act_signed: bool = False,
    name: str = "",
) -> jax.Array:
    """Deployed forward: quantize acts -> mpmm over packed planes.

    The optional fused epilogue runs BN/residual/ReLU inside the matmul
    kernel (epilogue.py); ``tile=None`` autotunes from the DSE model.
    ``act_signed=True`` uses symmetric signed activation codes
    (act_zero = 0) for inputs that straddle zero — a CNN stem fed
    mean-normalized images, where the paper's unsigned codes (Eq. 5,
    meant for post-ReLU activations) would clamp negatives away.
    ``policy`` may be a ``PrecisionPlan``; ``name`` picks this layer's
    entry, matching the format the layer was packed at.
    """
    policy = plan_lib.resolve_policy(policy, name)
    # Validate up front: the bias fold below dereferences scale/shift,
    # and must fail with the designed error, not an AttributeError.
    mpmm_epilogue.validate_operands(epilogue, scale, shift, residual)
    if "w" in p:  # FP baseline
        y = jnp.einsum("...k,kn->...n", x.astype(compute_dtype),
                       p["w"].astype(compute_dtype))
        if "b" in p:
            y = y + p["b"].astype(compute_dtype)
        out_dtype = mpmm_epilogue.resolve_out_dtype(epilogue, compute_dtype)
        return mpmm_epilogue.apply(
            y.astype(jnp.float32), epilogue, scale, shift, residual
        ).astype(out_dtype)
    epilogue, scale, shift = _fold_bias(p, epilogue, scale, shift)
    w_bits = policy.bits_for(layer_class)
    k = policy.k
    kdim = x.shape[-1]
    fmt = PlaneFormat(w_bits=w_bits, k=k, k_dim=kdim)
    a = mpmm_ops.quantize_activations(x, p["ga"], policy.a_bits,
                                      signed=act_signed)
    y = mpmm_ops.mpmm(
        a, p["planes"], p["gamma"], p["colsum"],
        scale, shift, residual,
        fmt=fmt, act_zero=0 if act_signed else 2 ** (policy.a_bits - 1),
        tile=tile, variant=policy.variant, impl=impl,
        out_dtype=compute_dtype, epilogue=epilogue,
    )
    if "b" in p and epilogue is None:
        y = y + p["b"].astype(compute_dtype)
    return y


# ---------------------------------------------------------------------------
# Convolutions as GEMMs (im2col) — the paper's CONV-layer processing.
# ---------------------------------------------------------------------------


def im2col(x: jax.Array, kh: int, kw: int, stride: int, padding: str
           ) -> jax.Array:
    """x (B,H,W,C) -> patches (B,H',W', kh*kw*C) matching HWIO weight layout.

    The patches are a convolution with a one-hot kernel; at the default
    precision the TPU rounds f32 operands to bf16, which changed the
    quantized codes of a float32 stem input.  HIGHEST keeps them exact.
    """
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    # conv_general_dilated_patches yields features ordered (C, kh, kw);
    # reorder to (kh, kw, C) so a reshape of HWIO weights lines up.
    b, ho, wo, f = patches.shape
    c = x.shape[-1]
    patches = patches.reshape(b, ho, wo, c, kh * kw)
    return jnp.swapaxes(patches, -1, -2).reshape(b, ho, wo, kh * kw * c)


def qconv_spec(cin: int, cout: int, k: int, *, layer_class: str = "inner",
               name_axes: Tuple[Optional[str], str] = ("embed", "mlp"),
               channel_wise: bool = False, name: str = "") -> Dict[str, ParamSpec]:
    return qlinear_spec(k * k * cin, cout, axes=name_axes,
                        layer_class=layer_class, channel_wise=channel_wise,
                        name=name)


def qconv_apply(p, x, policy, *, k: int, stride: int = 1, padding="SAME",
                layer_class: str = "inner", quantize_act: bool = True,
                name: str = ""):
    """QAT conv forward: im2col + fake-quant linear."""
    cols = im2col(x, k, k, stride, padding)
    return qlinear_apply({kk: v for kk, v in p.items() if kk != QMARK},
                         cols, policy, layer_class=layer_class,
                         quantize_act=quantize_act, name=name)


def _resolve_impl(impl: str) -> str:
    """'auto' -> the backend mpmm will actually run (pallas on TPU)."""
    if impl == "auto":
        return "pallas" if mpmm_ops._on_tpu() else "xla"
    return impl


def conv_serve_dataflow(x_shape, policy, *, k: int, stride: int,
                        padding: str, layer_class: str, n_out: int,
                        impl: str) -> str:
    """Resolve the per-layer conv dataflow: 'im2col' or 'implicit'.

    The decision runs the extended DSE model (`core.dse.
    choose_conv_dataflow`), whose memory term charges im2col the
    kh·kw/stride² patch-inflation and the implicit dataflow only the raw
    feature map — then gates on kernel feasibility: the pallas
    implicit-GEMM kernel needs C divisible by the packed digits-per-byte
    (a 3-channel stem under k=2 stays on im2col; the XLA direct conv has
    no such constraint).
    """
    b, h, w, cin = x_shape
    w_bits = policy.bits_for(layer_class)
    fmt = PlaneFormat(w_bits=w_bits, k=policy.k, k_dim=k * k * cin)
    resolved = _resolve_impl(impl)
    if resolved == "pallas" and not mpmm_ops.conv_implicit_feasible(cin, fmt):
        return "im2col"
    from repro.core import dse as _dse
    # No layer_class on the ConvShape: the cost model takes w_bits
    # explicitly, and the leaner key lets conv_mpmm's bn lookup hit the
    # same lru_cache entry instead of re-sweeping tiles.
    conv = _dse.ConvShape(batch=b, h=h, w=w, c_in=cin, c_out=n_out,
                          kh=k, kw=k, stride=stride, padding=padding)
    choice = _dse.choose_conv_dataflow(conv, w_bits=w_bits, k=policy.k,
                                       variant=policy.variant,
                                       pin_tile=(resolved == "pallas"))
    return choice.dataflow


@_layer_scope
def qconv_serve_apply(p, x, policy, *, k: int, stride: int = 1,
                      padding="SAME", layer_class: str = "inner",
                      tile: Optional[mpmm_ops.TileShape] = None,
                      impl: str = "xla", compute_dtype=jnp.bfloat16,
                      epilogue: Optional[EpilogueSpec] = None,
                      scale: Optional[jax.Array] = None,
                      shift: Optional[jax.Array] = None,
                      residual: Optional[jax.Array] = None,
                      act_signed: bool = False,
                      dataflow: str = "auto", name: str = ""):
    """Deployed conv forward: packed planes + fused epilogue, per-layer
    dataflow.

    ``dataflow``: 'im2col' materializes the patch matrix and runs the
    matmul path (the pre-PR-2 behavior); 'implicit' runs convolution as
    implicit GEMM (`ops.conv_mpmm`) — patches gathered in VMEM (pallas)
    or a direct ``lax.conv`` on recombined int8 weights (xla), never a
    patch buffer in HBM; 'auto' picks per layer via the DSE cost model
    (patch-reuse term) + kernel feasibility.  Both dataflows are
    bit-exact to each other.  BN (folded to scale/shift), the shortcut
    add, and ReLU all execute in the kernel epilogue either way — the
    FPGA post-processing pipeline.

    ``policy`` may be a ``PrecisionPlan``: ``name`` resolves both the
    (w_bits, k, channel_wise) format and the conv dataflow, with an
    explicit non-'auto' ``dataflow`` argument still winning (DESIGN.md
    §7 resolution order: explicit arg > plan entry > policy default).
    """
    dataflow = plan_lib.resolve_dataflow(policy, name, dataflow)
    policy = plan_lib.resolve_policy(policy, name)
    if "w" in p or not policy.quantize:
        dataflow = "im2col"  # FP baseline serves through the bf16 matmul
    elif dataflow == "auto":
        dataflow = conv_serve_dataflow(
            x.shape, policy, k=k, stride=stride, padding=padding,
            layer_class=layer_class, n_out=p["planes"].shape[-1], impl=impl)
    elif dataflow == "implicit":
        # An explicit 'implicit' still honors kernel feasibility: a layer
        # the pallas conv kernel cannot run (C not a multiple of 8//k)
        # falls back to im2col instead of crashing mid-graph.
        fmt_gate = PlaneFormat(w_bits=policy.bits_for(layer_class),
                               k=policy.k, k_dim=k * k * x.shape[-1])
        if (_resolve_impl(impl) == "pallas"
                and not mpmm_ops.conv_implicit_feasible(x.shape[-1],
                                                        fmt_gate)):
            dataflow = "im2col"
    if dataflow == "im2col":
        cols = im2col(x, k, k, stride, padding)
        return qlinear_serve_apply(
            p, cols, policy, layer_class=layer_class, tile=tile, impl=impl,
            compute_dtype=compute_dtype, epilogue=epilogue, scale=scale,
            shift=shift, residual=residual, act_signed=act_signed)
    assert dataflow == "implicit", dataflow
    mpmm_epilogue.validate_operands(epilogue, scale, shift, residual)
    epilogue, scale, shift = _fold_bias(p, epilogue, scale, shift)
    w_bits = policy.bits_for(layer_class)
    cin = x.shape[-1]
    fmt = PlaneFormat(w_bits=w_bits, k=policy.k, k_dim=k * k * cin)
    a = mpmm_ops.quantize_activations(x, p["ga"], policy.a_bits,
                                      signed=act_signed)
    y = mpmm_ops.conv_mpmm(
        a, p["planes"], p["gamma"], p["colsum"],
        scale, shift, residual,
        fmt=fmt, act_zero=0 if act_signed else 2 ** (policy.a_bits - 1),
        kh=k, kw=k, stride=stride, padding=padding,
        bn=tile.bn if tile is not None else None,
        variant=policy.variant, impl=impl, out_dtype=compute_dtype,
        epilogue=epilogue)
    if "b" in p and epilogue is None:
        y = y + p["b"].astype(compute_dtype)
    return y


def pack_qlinear(
    p: Dict[str, jax.Array],
    policy: PolicyOrPlan,
    layer_class: str = "inner",
    name: str = "",
) -> Dict[str, jax.Array]:
    """Trained QAT params -> deployed packed params (handles lead dims).

    Under a ``PrecisionPlan`` the layer packs at ITS OWN resolved
    format — plane count, packed-K bytes and gamma layout all follow
    the plan entry named by ``name``.
    """
    policy = plan_lib.resolve_policy(policy, name)
    w, gw, ga = p["w"], p["gw"], p["ga"]
    if not policy.quantize:
        out = {"w": w.astype(jnp.bfloat16)}
        if "b" in p:
            out["b"] = p["b"]
        return out
    w_bits = policy.bits_for(layer_class)
    kdim, n = w.shape[-2], w.shape[-1]
    lead_nd = w.ndim - 2
    channel_wise = policy.channel_wise and gw.ndim == lead_nd + 1
    # Broadcast gw against the (possibly lead-stacked) weight explicitly:
    # per-tensor gw has shape `lead` -> lead+(1,1); channel-wise gw has
    # shape lead+(N,) -> lead+(1,N).
    gww = jnp.asarray(gw, jnp.float32)
    g_b = gww[..., None, :] if channel_wise else gww[..., None, None]
    wspec = quant.weight_spec(w_bits, channel_axis=None)
    w_int = quant.quantize_int(w.astype(jnp.float32), g_b, wspec)
    fmt = PlaneFormat(w_bits=w_bits, k=policy.k, k_dim=kdim)
    packed = packing.pack_planes(w_int, fmt, axis=-2)       # (P, ..., Kp, N)
    packed = jnp.moveaxis(packed, 0, -3)                    # (..., P, Kp, N)
    colsum = jnp.sum(w_int, axis=-2, dtype=jnp.int32)[..., None, :]
    gamma_w = jnp.broadcast_to(g_b, w.shape[:-2] + (1, n))
    gamma = gamma_w * jnp.asarray(ga, jnp.float32)[..., None, None]
    out = {"planes": packed, "colsum": colsum, "gamma": gamma,
           "ga": jnp.asarray(ga, jnp.float32)}
    if "b" in p:
        out["b"] = p["b"]
    return out


def pack_tree(params, specs, policy: PolicyOrPlan):
    """Recursively pack every qlinear subtree of a trained param tree.

    `specs` is the matching ParamSpec tree; its markers carry each
    subtree's layer class and workload layer name, so a layer-wise
    ``PrecisionPlan`` packs every layer at its own resolved format —
    the single funnel shared by every model family (no per-family
    pack threading).
    """
    if is_qlinear(specs):
        cls = _layer_class_of(specs)
        sub = {k: v for k, v in params.items() if k != QMARK}
        return pack_qlinear(sub, policy, cls, name=_layer_name_of(specs))
    if isinstance(specs, dict):
        return {
            k: pack_tree(params[k], specs[k], policy)
            for k in specs
            if k != QMARK
        }
    return params
