"""The work a served network needs, counted from its configuration, and
the kernel calls of a compiled step matched to the layers they serve.

Operations and bytes are the algorithm's, not the implementation's: a
layer's GEMM is ``2 * M * K * N`` operations however many digit planes
or padded columns the kernel runs, and its bytes are the input feature
map once (int8 codes), the weights at the plan's ``w_bits``, and the
output and the shortcut at bfloat16.  im2col's patch matrix, the plane
fold and padding are waste a kernel may remove; they are not work.
"""
from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional

ACT_BYTES = 1   # int8 activation codes into a layer
OUT_BYTES = 2   # bfloat16 layer outputs and shortcuts

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict:
    """The published peaks of ``device_kind`` (an unknown kind is an
    error, never a default)."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


def layer_work(layer: Dict, w_bits: int, batch: int) -> Dict[str, float]:
    """Operations and bytes of one layer over ``batch`` images."""
    ops = 2.0 * batch * layer["M"] * layer["K"] * layer["N"]
    act_in = batch * layer["h_in"] ** 2 * layer["cin"] * ACT_BYTES
    out = batch * layer["M"] * layer["N"] * OUT_BYTES
    res = out if layer["residual"] else 0
    weights = layer["K"] * layer["N"] * w_bits / 8.0
    return {"ops": ops, "bytes": act_in + out + res + weights}


def least_time(work: Dict[str, float], peak: Dict) -> float:
    """The least time the chip could take: the slower of its compute
    and its memory bound."""
    return max(work["ops"] / peak["int8_ops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])


def ops_per_image(layers: List[Dict]) -> float:
    return sum(2.0 * lay["M"] * lay["K"] * lay["N"] for lay in layers)


# --- kernel calls of a compiled step -----------------------------------------

_CALL = re.compile(r"^\s*%?(?P<name>[\w.\-]+) = (?P<res>\w+\[[\d,]*\])")
_OPS = re.compile(r"operand_layout_constraints=\{(?P<ops>.*?)\}, \w+=")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape(text: str):
    dt, dims = _SHAPE.match(text).groups()
    return dt, tuple(int(d) for d in dims.split(",") if d)


def kernel_calls(hlo_text: str) -> List[Dict]:
    """Every Mosaic call of an optimized HLO module: its instruction
    name (as the device trace names it), kernel, result and operands."""
    calls = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m, o = _CALL.match(line), _OPS.search(line)
        if not m or not o:
            continue
        ops = [_shape(f"{dt}[{dims}]") for dt, dims in _SHAPE.findall(o["ops"])]
        calls.append({"name": m["name"], "kernel": m["name"].split(".")[0],
                      "result": _shape(m["res"]), "operands": ops})
    return calls


def _fits(call: Dict, layer: Dict, w_bits: int, k: int, batch: int
          ) -> Optional[int]:
    """Padded volume if ``call`` can be ``layer``'s kernel, else None."""
    f = 8 // k
    planes = -(-w_bits // k)
    ops = call["operands"]
    # (codes, planes, gamma, colsum[, scale, shift][, shortcut])
    if len(ops) < 4 or (len(ops) == 7) != layer["residual"]:
        return None
    packed = ops[1][1]
    if call["kernel"] == "conv_mpmm":
        x, out = ops[0][1], call["result"][1]
        if len(x) != 4 or len(out) != 4:
            return None
        ok = (x[0] == batch and x[3] == layer["cin"]
              and out[1] == out[2] == layer["h_out"] and out[3] >= layer["N"]
              and packed[0] == planes
              and packed[1] == layer["k"] ** 2
              and packed[2] * f == layer["cin"])
        return out[0] * out[1] * out[2] * out[3] if ok else None
    if call["kernel"] == "mpmm":
        a, out = ops[0][1], call["result"][1]
        if len(a) != 2 or len(out) != 2:
            return None
        m = batch * layer["M"]
        ok = (a[0] >= m and a[1] >= layer["K"] and out[1] >= layer["N"]
              and packed[0] == planes and packed[1] * f == a[1])
        return a[0] * a[1] * out[1] if ok else None
    return None


def route(calls: List[Dict], layers: List[Dict], formats: Dict[str, tuple],
          batch: int) -> Dict[str, str]:
    """{call name: layer name}: each call matched to the layer whose
    shapes and plane format it carries, with the least padding.  Layers
    of equal shape and format are interchangeable, so any such pairing
    counts the same work.  A call or layer left unmatched is an error:
    the count would not be the step's."""
    free = {c["name"]: c for c in calls}
    out = {}
    for lay in sorted(layers, key=lambda l: -l["M"] * l["K"] * l["N"]):
        w_bits, k = formats[lay["name"]]
        best = None
        for c in free.values():
            v = _fits(c, lay, w_bits, k, batch)
            if v is not None and (best is None or v < best[0]):
                best = (v, c["name"])
        if best is None:
            raise ValueError(f"no kernel call of the compiled step serves "
                             f"layer {lay['name']}")
        out[best[1]] = lay["name"]
        del free[best[1]]
    if free:
        raise ValueError(f"kernel calls matched to no layer: {sorted(free)}")
    return out


def kernel_roofline(run, kernel: str) -> Optional[float]:
    """Σ least time of ``kernel``'s calls / Σ their device time, in %.

    Each traced call is counted at the layer the compiled step routes to
    it.  Only a window that runs one compiled step (one bucket) can be
    read so: with several, call names repeat across programs.  A kernel
    with no call in the stretch gives no reading, never 0."""
    t = run.trace
    buckets = run.cell.traffic["buckets"]
    if not t or run.routing is None or len(buckets) != 1:
        return None
    lays = {lay["name"]: lay for lay in run.layers}
    least = spent = 0.0
    for name, a, b in t["ops"]:
        if name.split(".")[0] != kernel:
            continue
        if name not in run.routing:
            return None
        lay = lays[run.routing[name]]
        w_bits, _ = run.formats[lay["name"]]
        least += least_time(layer_work(lay, w_bits, buckets[0]), run.peak)
        spent += b - a
    return 100.0 * least / spent if spent > 0 else None
