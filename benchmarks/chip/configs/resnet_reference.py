"""Plain reference of a mixed-precision ResNet served from packed weights.

Independent of the program under test: it imports nothing from it and
takes nothing it made.  It holds, for a ResNet configuration file:

* ``layers(cfg)``: every conv and the classifier as a logical GEMM
  (name, kernel size, stride, input and output sizes);
* ``init_weights(cfg, key)``: the float weights, step sizes and batch
  norm statistics a run serves, drawn from the seed;
* ``forward(cfg, plan, weights, images, act_dtype)``: the served
  network, written from the quantizer's definition (paper Eq. 5).

The served network, layer by layer:

* activation codes ``u = clip(round(x / ga), lo, hi)``: unsigned
  ``[0, 2^a - 1]`` after a ReLU, signed ``[-2^(a-1), 2^(a-1) - 1]`` at the
  stem, which sees raw pixels;
* weight codes ``q = clip(round(w / gw), -2^(b-1), 2^(b-1) - 1)`` at the
  layer's ``w_bits`` from the plan (boundary layers at ``boundary_bits``);
* ``y = conv(u, q) * (gw * ga)``; then the folded batch norm
  ``y * s + t`` with ``s = g / sqrt(var + eps)``, ``t = b - mean * s``;
  the shortcut add and the ReLU, all in float32;
* every layer's output stored as ``act_dtype`` (bfloat16 as configured),
  the rounding an explicit ``reduce_precision``.

The integer conv runs on bfloat16 operands with a float32 accumulator.
Codes (at most 255) and weights (at most 128 in magnitude) are exact in
bfloat16, and the sums stay exact while they are below 2^24, which the
drawn weights and images never approach.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp

EPS = 1e-5


def layers(cfg: Dict) -> List[Dict]:
    """Every GEMM of one image, in forward order.

    Each entry: name, k (kernel size), stride, cin, cout, h_in (input
    height = width), h_out, K = k*k*cin, N = cout, M = h_out^2 (rows per
    image), residual (the epilogue adds a shortcut), layer_class.
    """
    width, img = cfg["width"], cfg["img_size"]
    bottleneck = cfg["block"] == "bottleneck"
    expansion = 4 if bottleneck else 1
    out: List[Dict] = []

    def add(name, k, stride, cin, cout, h_in, residual=False,
            layer_class="inner"):
        h_out = -(-h_in // stride)
        out.append(dict(name=name, k=k, stride=stride, cin=cin, cout=cout,
                        h_in=h_in, h_out=h_out, K=k * k * cin, N=cout,
                        M=h_out * h_out, residual=residual,
                        layer_class=layer_class))
        return h_out

    h = add("stem", 7, 2, 3, width, img, layer_class="boundary")
    h = -(-h // 2)  # 3x3 stride-2 max pool
    cin = width
    for si, n_blocks in enumerate(cfg["stages"]):
        cmid = width * 2 ** si
        cout = cmid * expansion
        for bi in range(n_blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            name = f"s{si}b{bi}"
            if bottleneck:
                add(name + "c1", 1, 1, cin, cmid, h)
                h2 = add(name + "c2", 3, stride, cmid, cmid, h)
                add(name + "c3", 1, 1, cmid, cout, h2, residual=True)
            else:
                h2 = add(name + "c1", 3, stride, cin, cmid, h)
                add(name + "c2", 3, 1, cmid, cmid, h2, residual=True)
            if stride != 1 or cin != cout:
                add(name + "p", 1, stride, cin, cout, h)
            h, cin = h2, cout
    out.append(dict(name="fc", k=1, stride=1, cin=cin, cout=cfg["n_classes"],
                    h_in=1, h_out=1, K=cin, N=cfg["n_classes"], M=1,
                    residual=False, layer_class="boundary"))
    return out


def layer_format(plan: Dict, layer: Dict):
    """(w_bits, k) of one layer under a plan file's contents."""
    if layer["layer_class"] == "boundary":
        return plan["boundary_bits"], plan["default"]["k"]
    entry = dict(plan["default"])
    entry.update(plan["layers"].get(layer["name"], {}))
    return entry["w_bits"], entry["k"]


def _bn_stats(key, c, gain):
    kg, kb, km, kv = jax.random.split(key, 4)
    return {
        "gamma": gain * jax.random.uniform(kg, (c,), jnp.float32, 0.8, 1.2),
        "beta": 0.1 * jax.random.normal(kb, (c,), jnp.float32),
        "mean": 0.1 * jax.random.normal(km, (c,), jnp.float32),
        "var": jax.random.uniform(kv, (c,), jnp.float32, 0.8, 1.2),
    }


def init_weights(cfg: Dict, plan: Dict, key) -> Dict[str, Dict]:
    """{layer name: weights} drawn from ``key`` (jit-friendly).

    He-normal weights; the weight step ``gw`` is LSQ's initial step for
    the layer's w_bits, 2 E|w| / sqrt(2^(b-1) - 1); the activation step
    ``ga`` spans the range the configuration file gives for that kind of
    input; the batch norm after the conv that closes a residual branch
    is scaled by ``residual_gain`` so the trunk grows slowly with depth.
    """
    init = cfg["init"]
    a_bits = plan["a_bits"]
    out = {}
    for i, lay in enumerate(layers(cfg)):
        kw, kbn = jax.random.split(jax.random.fold_in(key, i))
        w = jax.random.normal(kw, (lay["K"], lay["N"]), jnp.float32)
        w = w * math.sqrt(2.0 / lay["K"])
        w_bits, _ = layer_format(plan, lay)
        gw = 2.0 * jnp.mean(jnp.abs(w)) / math.sqrt(max(2 ** (w_bits - 1) - 1, 1))
        if lay["name"] == "stem":
            ga = init["stem_act_range"] / (2 ** (a_bits - 1) - 1)
        else:
            ga = init["act_range"] / (2 ** a_bits - 1)
        ent = {"w": w, "gw": gw, "ga": jnp.asarray(ga, jnp.float32)}
        if lay["name"] != "fc":
            gain = init["residual_gain"] if lay["residual"] else 1.0
            ent["bn"] = _bn_stats(kbn, lay["N"], gain)
        out[lay["name"]] = ent
    return out


def _conv(u, q, k, stride):
    return jax.lax.conv_general_dilated(
        u.astype(jnp.bfloat16), q.astype(jnp.bfloat16), (stride, stride),
        "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)


def _qlayer(lay, ent, plan, x, *, signed, residual=None, relu=True,
            act_dtype=jnp.bfloat16):
    """One quantized conv (or the FC as a 1x1 conv) with its epilogue."""
    a_bits = plan["a_bits"]
    w_bits, _ = layer_format(plan, lay)
    ga, gw = ent["ga"], ent["gw"]
    xs = x.astype(jnp.float32) / ga
    if signed:
        half = 2 ** (a_bits - 1)
        u = jnp.clip(jnp.round(xs), -half, half - 1)
    else:
        u = jnp.clip(jnp.round(xs), 0, 2 ** a_bits - 1)
    qmax = 2 ** (w_bits - 1)
    q = jnp.clip(jnp.round(ent["w"] / gw), -qmax, qmax - 1)
    q = q.reshape(lay["k"], lay["k"], lay["cin"], lay["cout"])
    y = _conv(u, q, lay["k"], lay["stride"]) * (gw * ga)
    if "bn" in ent:
        bn = ent["bn"]
        s = bn["gamma"] * jax.lax.rsqrt(bn["var"] + EPS)
        t = bn["beta"] - bn["mean"] * s
        y = y * s + t
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return _store(y, act_dtype)


def _store(y, dtype):
    """Round to ``dtype`` as an explicit op, which XLA keeps even where
    it may otherwise skip a round trip through a narrower type."""
    fi = jnp.finfo(dtype)
    y = jax.lax.reduce_precision(y, exponent_bits=fi.nexp,
                                 mantissa_bits=fi.nmant)
    return y.astype(dtype)


def forward(cfg: Dict, plan: Dict, weights: Dict, images,
            act_dtype=jnp.bfloat16):
    """(B, H, W, 3) float32 images -> (B, n_classes) float32 logits."""
    lays = {lay["name"]: lay for lay in layers(cfg)}

    def q(name, x, **kw):
        return _qlayer(lays[name], weights[name], plan, x,
                       act_dtype=act_dtype, **kw)

    x = q("stem", images, signed=True)
    x = _store(jax.lax.reduce_window(x.astype(jnp.float32), -jnp.inf,
                                     jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                                     "SAME"), act_dtype)
    bottleneck = cfg["block"] == "bottleneck"
    for si, n_blocks in enumerate(cfg["stages"]):
        for bi in range(n_blocks):
            name = f"s{si}b{bi}"
            sc = q(name + "p", x, signed=False, relu=False) \
                if name + "p" in lays else x
            if bottleneck:
                h = q(name + "c1", x, signed=False)
                h = q(name + "c2", h, signed=False)
                x = q(name + "c3", h, signed=False, residual=sc)
            else:
                h = q(name + "c1", x, signed=False)
                x = q(name + "c2", h, signed=False, residual=sc)
    x = _store(jnp.mean(x.astype(jnp.float32), axis=(1, 2)), act_dtype)
    logits = q("fc", x[:, None, None, :], signed=False, relu=False)
    return logits[:, 0, 0, :].astype(jnp.float32)
