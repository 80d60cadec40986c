"""Roofline-term extraction from compiled XLA artifacts (TPU v5e model).

Given a compiled (SPMD-partitioned, per-device) executable:

    compute term    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory term     = HLO_bytes_per_device / HBM_bandwidth_per_chip
    collective term = wire_bytes_per_device / ICI_bandwidth_per_chip

``cost_analysis()`` on a partitioned module reports *per-device* flops and
bytes (verified against hand counts), so no further division by chip count
is applied.  Collective wire bytes are parsed from the compiled HLO text
with ring-algorithm factors:

    all-reduce        2 (n-1)/n x buffer bytes
    all-gather          (n-1)/n x full (output) bytes
    reduce-scatter      (n-1)/n x full (input) bytes
    all-to-all          (n-1)/n x buffer bytes
    collective-permute  1        x buffer bytes

Hardware constants (given): TPU v5e — 197 TFLOP/s bf16 per chip (394
TOPS int8), 819 GB/s HBM, ~50 GB/s/link ICI, ~16 GiB HBM.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

__all__ = [
    "HW",
    "TPU_V5E",
    "CollectiveStats",
    "RooflineReport",
    "collective_wire_bytes",
    "roofline_from_compiled",
    "attribute_measured_time",
]


@dataclasses.dataclass(frozen=True)
class HW:
    name: str
    peak_flops_bf16: float   # FLOP/s per chip
    peak_ops_int8: float     # OP/s per chip
    hbm_bw: float            # bytes/s per chip
    ici_bw: float            # bytes/s per link
    ici_links: int           # usable links per chip (2D torus: 4)
    hbm_bytes: float         # HBM capacity per chip
    vmem_bytes: float        # VMEM capacity per core
    vmem_scoped_bytes: float  # scoped VMEM a Mosaic kernel gets by default

    @property
    def ici_bw_per_chip(self) -> float:
        # Ring collectives drive one link pair per mesh axis concurrently;
        # we budget 2 active links per chip (bidirectional ring).
        return 2.0 * self.ici_bw


TPU_V5E = HW(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    peak_ops_int8=394e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    ici_links=4,
    hbm_bytes=16 * 2**30,
    vmem_bytes=128 * 2**20,
    # The limit the TPU compiler enforces on a pallas_call that sets no
    # vmem_limit_bytes ("Scoped allocation ... limit 16.00M" on v5e).
    vmem_scoped_bytes=16 * 2**20,
)

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# ``f32[128,256]{1,0}`` / ``(f32[8], s32[8])`` shapes in HLO text.
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[\w\[\],{}]+)\s+"
    r"(all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(",
)
_GROUPS_ITOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(shape_text: str) -> int:
    """Total bytes of one (possibly tuple) HLO shape string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_ITOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return default


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    buffer_bytes: Dict[str, int]
    wire_bytes: Dict[str, float]

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())

    @property
    def total_count(self) -> int:
        return sum(self.counts.values())


def collective_wire_bytes(hlo_text: str, default_group: int = 1) -> CollectiveStats:
    """Parse per-device collective traffic out of compiled HLO text."""
    counts: Dict[str, int] = {}
    bufb: Dict[str, int] = {}
    wireb: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_text, op = m.group(1), m.group(2)
        op = op.replace("-start", "")
        b = _shape_bytes(shape_text)
        n = max(_group_size(line, default_group), 1)
        if n == 1 and op != "collective-permute":
            continue  # degenerate group: no wire traffic
        # (collective-permute carries no replica_groups: the buffer always
        # crosses a link once.)
        ring = (n - 1) / n
        if op == "all-reduce":
            wire = 2.0 * ring * b
        elif op == "all-gather":
            wire = ring * b            # output shape is the gathered buffer
        elif op == "reduce-scatter":
            wire = (n - 1) * b         # output is the shard; input = n*b
        elif op == "all-to-all":
            wire = ring * b
        else:  # collective-permute
            wire = float(b)
        counts[op] = counts.get(op, 0) + 1
        bufb[op] = bufb.get(op, 0) + b
        wireb[op] = wireb.get(op, 0.0) + wire
    return CollectiveStats(counts=counts, buffer_bytes=bufb, wire_bytes=wireb)


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: Tuple[Tuple[str, int], ...]
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float            # 6*N*D (or 2*N*tokens for inference)
    collectives: CollectiveStats = None
    argument_bytes: Optional[float] = None
    temp_bytes: Optional[float] = None
    output_bytes: Optional[float] = None

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs x chips): remat/redundancy waste."""
        chips = 1
        for _, s in self.mesh:
            chips *= s
        hlo_total = self.flops_per_device * chips
        return self.model_flops / hlo_total if hlo_total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful model FLOPs per chip-second of the bound: the MFU analogue."""
        chips = 1
        for _, s in self.mesh:
            chips *= s
        if self.bound_s <= 0:
            return 0.0
        achieved = self.model_flops / chips / self.bound_s
        return achieved / TPU_V5E.peak_flops_bf16

    def row(self) -> Dict[str, object]:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": "x".join(str(s) for _, s in self.mesh),
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def attribute_measured_time(
    layers: List[Dict[str, float]],
    measured_s: float,
    hw: HW = TPU_V5E,
) -> Dict[str, object]:
    """Attribute ONE measured device time across per-layer roofline times.

    ``layers`` rows carry the model side (``name``, ``w_bits``,
    ``layer_class``, ``macs``, ``roofline_s``, ``compute_s``,
    ``memory_s``, ``hbm_bytes``); ``measured_s`` is the measured wall
    device time of the whole step.  With a single aggregate measurement
    the only assignment that cannot invent per-layer anomalies is the
    PROPORTIONAL one:

        attributed_s(l) = roofline_s(l) * measured_s / sum roofline_s

    so every layer shares one slowdown factor and per-layer achieved
    TOps/s and HBM bytes/s differ only through layer shape and
    precision, while ``roofline_fraction`` (sum roofline / measured) is
    the single whole-model utilization scalar — the quantity the
    paper's 1.13 TOps/s maps onto.  Pure math: no jax, no planner
    imports (those live in ``runtime.telemetry.layer_attribution``).
    """
    total_roofline = sum(l["roofline_s"] for l in layers)
    if total_roofline <= 0.0 or measured_s <= 0.0:
        return {"measured_s": measured_s, "roofline_s": total_roofline,
                "roofline_fraction": 0.0, "layers": []}
    scale = measured_s / total_roofline
    rows = []
    for l in layers:
        attributed_s = l["roofline_s"] * scale
        flops = 2.0 * l["macs"]
        rows.append({
            "name": l["name"],
            "w_bits": int(l["w_bits"]),
            "layer_class": l.get("layer_class", "inner"),
            "bound": "compute" if l["compute_s"] >= l["memory_s"]
                     else "memory",
            "share": l["roofline_s"] / total_roofline,
            "attributed_s": attributed_s,
            "achieved_tops": flops / attributed_s / 1e12,
            "roofline_tops": flops / l["roofline_s"] / 1e12,
            "achieved_hbm_gbps": l["hbm_bytes"] / attributed_s / 1e9,
            "roofline_hbm_gbps": l["hbm_bytes"] / l["roofline_s"] / 1e9,
        })
    total_macs = sum(l["macs"] for l in layers)
    return {
        "measured_s": measured_s,
        "roofline_s": total_roofline,
        "roofline_fraction": total_roofline / measured_s,
        "achieved_tops": 2.0 * total_macs / measured_s / 1e12,
        "roofline_tops": 2.0 * total_macs / total_roofline / 1e12,
        "peak_int8_tops": hw.peak_ops_int8 / 1e12,
        "layers": rows,
    }


def roofline_from_compiled(
    compiled,
    *,
    arch: str,
    shape: str,
    mesh_axes: Tuple[Tuple[str, int], ...],
    model_flops: float,
    hw: HW = TPU_V5E,
    int8_fraction: float = 0.0,
    hlo_text: Optional[str] = None,
) -> RooflineReport:
    """Build a report from a jax compiled object.

    int8_fraction: share of HLO flops that run on the int8 MXU path (the
    mpmm planes), which executes at 2x the bf16 rate on v5e.
    """
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bts = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    stats = collective_wire_bytes(text)

    eff_peak = hw.peak_flops_bf16 * (1.0 + int8_fraction)  # int8 = 2x bf16
    compute_s = flops / eff_peak
    memory_s = bts / hw.hbm_bw
    collective_s = stats.total_wire_bytes / hw.ici_bw_per_chip

    arg_b = temp_b = out_b = None
    try:
        ma = compiled.memory_analysis()
        arg_b = float(ma.argument_size_in_bytes)
        temp_b = float(ma.temp_size_in_bytes)
        out_b = float(ma.output_size_in_bytes)
    except Exception:  # pragma: no cover - backend without memory stats
        pass

    return RooflineReport(
        arch=arch,
        shape=shape,
        mesh=mesh_axes,
        flops_per_device=flops,
        bytes_per_device=bts,
        wire_bytes_per_device=stats.total_wire_bytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        model_flops=model_flops,
        collectives=stats,
        argument_bytes=arg_b,
        temp_bytes=temp_b,
        output_bytes=out_b,
    )
