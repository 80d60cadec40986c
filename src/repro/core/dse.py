"""Holistic design-space exploration (paper Section III, Fig. 2) on TPU.

The paper's three boxes map onto TPU decisions:

  blue  (PE DSE)        -> kernel variant (ST/SA x slice k): MXU passes
                           P = ceil(w_Q/k), accumulator VMEM, packed bytes.
  red   (PE-array DSE)  -> Pallas tile dims (bm, bk, bn): Eq. 1 N_PE
                           becomes the tile MAC count, Eq. 2 BRAM_NPA
                           becomes the VMEM working set, Eq. 3 U(l)
                           becomes ceil-division tile-quantization waste.
  green (dataflow)      -> per-layer roofline feedback: every candidate is
                           scored by sum_l max(compute_s, memory_s) over
                           the model's GEMM workload; bandwidth-infeasible
                           points are discarded (the paper's roofline
                           check), the throughput-optimal point is chosen.

All candidates are enumerated exhaustively under the hardware constraints
(VMEM capacity, MXU 128-alignment), exactly like the paper's greedy
"explore all possible solutions, then compile the feasible ones".
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro.core.packing import PlaneFormat, num_planes
from repro.core.roofline import HW, TPU_V5E

__all__ = [
    "Gemm",
    "ConvShape",
    "TileCandidate",
    "vmem_working_set",
    "tile_utilization",
    "gemm_time",
    "conv_time",
    "choose_tile",
    "choose_conv_dataflow",
    "dse_sweep",
    "DseChoice",
    "ConvDataflowChoice",
    "autotune_tile",
    "digit_cache_bytes",
    "DIGIT_CACHE_BUDGET_BYTES",
]

# Decoded-digit strips larger than this fall back to per-step decode in
# the kernel (kernel.py cache_digits=False); see DESIGN.md §2.2.  Tiles
# are budgeted against the kernel's scoped VMEM limit less this strip.
DIGIT_CACHE_BUDGET_BYTES = 4 * 2**20


def _default_vmem_budget(hw: HW) -> float:
    return hw.vmem_scoped_bytes - DIGIT_CACHE_BUDGET_BYTES


@dataclasses.dataclass(frozen=True)
class Gemm:
    """One GEMM of the workload: out[M,N] += act[M,K] @ w[K,N], `count` x.

    layer_class 'boundary' layers run at 8 bit regardless of policy
    (paper: first/last layers pinned).
    """

    name: str
    m: int
    k: int
    n: int
    count: int = 1
    layer_class: str = "inner"

    @property
    def macs(self) -> int:
        return self.m * self.k * self.n * self.count


@dataclasses.dataclass(frozen=True)
class ConvShape:
    """One conv layer in NHWC/HWIO form — the dataflow-selection unit.

    The GEMM view (M = B·Ho·Wo, K = kh·kw·C, N = Cout) drives the compute
    term; the conv view (B·H·W·C input bytes) drives the memory term of
    the implicit dataflow, where patches are gathered in VMEM and never
    written back to HBM.
    """

    batch: int
    h: int
    w: int
    c_in: int
    c_out: int
    kh: int
    kw: int
    stride: int = 1
    padding: str = "SAME"
    layer_class: str = "inner"

    def _out(self, size: int, win: int) -> int:
        if self.padding == "SAME":
            return _ceil(size, self.stride)
        return (size - win) // self.stride + 1

    @property
    def ho(self) -> int:
        return self._out(self.h, self.kh)

    @property
    def wo(self) -> int:
        return self._out(self.w, self.kw)

    @property
    def m(self) -> int:
        return self.batch * self.ho * self.wo

    @property
    def k(self) -> int:
        return self.kh * self.kw * self.c_in

    @property
    def patch_reuse(self) -> float:
        """How many times im2col copies each input pixel: kh·kw / stride².

        This is the activation-traffic inflation the implicit dataflow
        avoids — large for 3x3 stride-1 (9x), ~1 for 1x1 or stride-k
        convs, which is exactly why dataflow choice must be per layer
        (Nguyen et al., arXiv:2009.01588)."""
        return (self.kh * self.kw) / float(self.stride ** 2)

    def gemm(self) -> Gemm:
        return Gemm("conv", self.m, self.k, self.c_out,
                    layer_class=self.layer_class)


@dataclasses.dataclass(frozen=True)
class TileCandidate:
    bm: int
    bk: int
    bn: int

    def as_tuple(self) -> Tuple[int, int, int]:
        return (self.bm, self.bk, self.bn)


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def vmem_working_set(
    tile: TileCandidate, fmt: PlaneFormat, variant: str = "st"
) -> int:
    """Eq. 2 analogue: bytes of VMEM live per tile step (double-buffered).

    BRAM_partial-sums -> accumulator tile(s); BRAM_activations -> int8 act
    tile; BRAM_weights -> packed digit-plane tile.  The paper's N/w_Q
    factor appears as the packed-weight byte count (bk * w_Q/8 per column).
    The kernel's own temporaries count too — the int32 digit fields of
    one plane's decode and the (bm, P*bn) int32 dot result — since the
    TPU compiler places them in the same scoped VMEM.
    """
    p = fmt.planes
    f = fmt.digits_per_byte
    act = tile.bm * tile.bk                      # int8
    wgt = p * _ceil(tile.bk, f) * tile.bn        # uint8 packed planes
    dig = p * tile.bk * tile.bn                  # decoded int8 digit slot
    accs = (p if variant == "sa" else 1) * tile.bm * tile.bn * 4
    out = tile.bm * tile.bn * 4
    scales = 2 * tile.bn * 8                     # gamma + colsum blocks
    decode = 2 * tile.bk * tile.bn * 4           # int32 fields + digits
    partial = tile.bm * p * tile.bn * 4          # int32 dot result
    # 2x: double buffering of every pipelined block.
    return 2 * (act + wgt + out + scales) + dig + accs + decode + partial


def tile_utilization(g: Gemm, tile: TileCandidate) -> float:
    """Eq. 3 analogue: ideal MACs / padded MACs (ceil-division waste)."""
    padded = (
        _ceil(g.m, tile.bm) * tile.bm
        * _ceil(g.k, tile.bk) * tile.bk
        * _ceil(g.n, tile.bn) * tile.bn
    )
    return (g.m * g.k * g.n) / padded


def _mxu_efficiency(tile: TileCandidate) -> float:
    """Fraction of the 128x128 MXU (and 8-deep sublanes) a tile feeds."""
    eff_k = tile.bk / (_ceil(tile.bk, 128) * 128)
    eff_n = tile.bn / (_ceil(tile.bn, 128) * 128)
    eff_m = tile.bm / (_ceil(tile.bm, 8) * 8)
    return eff_k * eff_n * eff_m


def gemm_time(
    g: Gemm,
    tile: TileCandidate,
    fmt: PlaneFormat,
    hw: HW = TPU_V5E,
    variant: str = "st",
    a_bits: int = 8,
) -> Tuple[float, float]:
    """(compute_s, memory_s) for one GEMM under this tile/format.

    Compute: P MXU passes over the padded loop nest at int8 peak.
    Memory:  tiled-matmul HBM traffic with the tile's temporal reuse —
    activations re-read per N-tile, packed weights re-read per M-tile
    (the paper's P_actual), outputs written once.
    """
    p = fmt.planes
    gm, gk, gn = _ceil(g.m, tile.bm), _ceil(g.k, tile.bk), _ceil(g.n, tile.bn)
    padded_macs = gm * tile.bm * gk * tile.bk * gn * tile.bn
    compute_s = (
        g.count * 2.0 * padded_macs * p / (hw.peak_ops_int8 * _mxu_efficiency(tile))
    )
    act_bytes = g.m * g.k * 1 * gn               # int8 acts, re-read per bn tile
    wgt_bytes = p * _ceil(g.k, fmt.digits_per_byte) * g.n * gm  # packed, per bm tile
    out_bytes = g.m * g.n * 4
    memory_s = g.count * (act_bytes + wgt_bytes + out_bytes) / hw.hbm_bw
    return compute_s, memory_s


def conv_time(
    conv: ConvShape,
    tile: TileCandidate,
    fmt: PlaneFormat,
    hw: HW = TPU_V5E,
    variant: str = "st",
    dataflow: str = "im2col",
) -> Tuple[float, float]:
    """(compute_s, memory_s) for one conv under a tile and a dataflow.

    Compute is dataflow-invariant (same padded MAC loop nest either way).
    The memory term is where the dataflows differ — the patch-reuse term:

      * ``im2col``: the patch matrix (M, K) = (B·Ho·Wo, kh·kw·C) is
        materialized in HBM (one write), then read back per N tile like
        any GEMM operand.  Activation traffic is inflated by
        ``conv.patch_reuse`` = kh·kw/stride² over the raw feature map.
      * ``implicit``: patch strips are gathered in VMEM from the raw
        (padded) feature map; HBM sees only B·H·W·C bytes per N tile —
        patches never round-trip.

    Weights and outputs cost the same in both dataflows.
    """
    g = conv.gemm()
    compute_s, _ = gemm_time(g, tile, fmt, hw, variant)
    gm, gn = _ceil(g.m, tile.bm), _ceil(g.n, tile.bn)
    if dataflow == "im2col":
        # read input once to form patches + write M*K patch bytes + read
        # them back per N tile (the GEMM operand).
        act_bytes = (conv.batch * conv.h * conv.w * conv.c_in
                     + g.m * g.k * (1 + gn))
    elif dataflow == "implicit":
        # raw feature map (plus halo) per N tile; no patch buffer.
        h_pad = (conv.ho - 1) * conv.stride + conv.kh
        w_pad = (conv.wo - 1) * conv.stride + conv.kw
        act_bytes = conv.batch * h_pad * w_pad * conv.c_in * gn
    else:
        raise ValueError(f"unknown dataflow {dataflow!r}")
    wgt_bytes = fmt.planes * _ceil(g.k, fmt.digits_per_byte) * g.n * gm
    out_bytes = g.m * g.n * 4
    memory_s = (act_bytes + wgt_bytes + out_bytes) / hw.hbm_bw
    return compute_s, memory_s


@dataclasses.dataclass(frozen=True)
class ConvDataflowChoice:
    """Per-layer dataflow decision (green box, extended to convs)."""

    dataflow: str               # 'im2col' | 'implicit'
    tile_im2col: Optional[TileCandidate]
    tile_implicit: Optional[TileCandidate]
    time_im2col_s: float
    time_implicit_s: float

    @property
    def tile(self) -> TileCandidate:
        return (self.tile_implicit if self.dataflow == "implicit"
                else self.tile_im2col)

    @property
    def speedup(self) -> float:
        return self.time_im2col_s / self.time_implicit_s


@functools.lru_cache(maxsize=4096)
def choose_conv_dataflow(
    conv: ConvShape,
    *,
    w_bits: int,
    k: int,
    variant: str = "st",
    hw: HW = TPU_V5E,
    vmem_budget: Optional[float] = None,
    pin_tile: bool = True,
) -> ConvDataflowChoice:
    """Pick im2col vs implicit-GEMM for one conv layer, roofline-scored.

    Both dataflows are scored over tile candidates with ``conv_time``;
    the im2col dataflow sweeps the full (bm, bk, bn) grid (any GEMM tile
    is realizable on the patch matrix).  With ``pin_tile`` (the pallas
    implicit kernel) the implicit dataflow pins bm = Wo (one output row
    per tile) and bk = C (one kernel position per dot) — the
    structure of conv_kernel.py — and sweeps bn; a 3-channel stem is
    correctly penalized for starving the MXU's K lanes.  Without it
    (the XLA direct conv, which tiles internally) implicit sweeps the
    full grid too.  The faster roofline total wins; ties break to
    implicit (no patch buffer to allocate).
    """
    budget = (vmem_budget if vmem_budget is not None
              else _default_vmem_budget(hw))
    fmt = PlaneFormat(w_bits=w_bits, k=k, k_dim=conv.k)
    best: Dict[str, Tuple[float, Optional[TileCandidate]]] = {
        "im2col": (math.inf, None), "implicit": (math.inf, None)}
    implicit_tiles: Iterable[TileCandidate] = (
        [TileCandidate(conv.wo, conv.c_in, bn)
         for bn in (128, 256, 512, 1024)]
        if pin_tile else _tile_grid(hw))
    for tile in _tile_grid(hw):
        if vmem_working_set(tile, fmt, variant) > budget:
            continue
        c, m = conv_time(conv, tile, fmt, hw, variant, dataflow="im2col")
        if max(c, m) < best["im2col"][0]:
            best["im2col"] = (max(c, m), tile)
    for tile in implicit_tiles:
        if vmem_working_set(tile, fmt, variant) > budget:
            continue
        c, m = conv_time(conv, tile, fmt, hw, variant, dataflow="implicit")
        if max(c, m) < best["implicit"][0]:
            best["implicit"] = (max(c, m), tile)
    t_i, tile_i = best["im2col"]
    t_d, tile_d = best["implicit"]
    if tile_i is None and tile_d is None:
        raise ValueError("no feasible conv tile under the VMEM budget")
    flow = "implicit" if (tile_d is not None and t_d <= t_i) else "im2col"
    return ConvDataflowChoice(flow, tile_i, tile_d, t_i, t_d)


def _tile_grid(hw: HW) -> Iterable[TileCandidate]:
    bms = [8, 16, 32, 64, 128, 256, 512]
    bks = [128, 256, 512, 1024, 2048]
    bns = [128, 256, 512, 1024, 2048]
    for bm, bk, bn in itertools.product(bms, bks, bns):
        yield TileCandidate(bm, bk, bn)


@dataclasses.dataclass
class DseChoice:
    """Output of the red+green boxes for one (model, policy) pair."""

    tile: TileCandidate
    k: int
    variant: str
    total_time_s: float
    compute_s: float
    memory_s: float
    mean_utilization: float
    vmem_bytes: int
    n_candidates: int

    def row(self) -> Dict[str, object]:
        return dataclasses.asdict(self) | {"tile": self.tile.as_tuple()}


def choose_tile(
    gemms: Sequence[Gemm],
    *,
    w_bits: int,
    k: int,
    variant: str = "st",
    hw: HW = TPU_V5E,
    vmem_budget: Optional[float] = None,
) -> DseChoice:
    """Red box: pick (bm,bk,bn) minimizing the model's roofline time."""
    budget = (vmem_budget if vmem_budget is not None
              else _default_vmem_budget(hw))
    fmt_inner = PlaneFormat(w_bits=w_bits, k=k, k_dim=1)
    fmt_bound = PlaneFormat(w_bits=8, k=min(k, 8), k_dim=1)
    best: Optional[DseChoice] = None
    n_cand = 0
    for tile in _tile_grid(hw):
        ws = vmem_working_set(tile, fmt_inner, variant)
        if ws > budget:
            continue  # infeasible: does not fit VMEM (the HWC gate, Fig. 2)
        n_cand += 1
        tot_c = tot_m = 0.0
        utils = []
        for g in gemms:
            fmt = fmt_bound if g.layer_class == "boundary" else fmt_inner
            c, m = gemm_time(g, tile, fmt, hw, variant)
            tot_c += c
            tot_m += m
            utils.append(tile_utilization(g, tile))
        total = max(tot_c, tot_m)  # green box: roofline over the whole net
        if best is None or total < best.total_time_s:
            best = DseChoice(
                tile=tile, k=k, variant=variant, total_time_s=total,
                compute_s=tot_c, memory_s=tot_m,
                mean_utilization=sum(utils) / max(len(utils), 1),
                vmem_bytes=ws, n_candidates=0,
            )
    if best is None:
        raise ValueError("no feasible tile under the VMEM budget")
    best.n_candidates = n_cand
    return best


def digit_cache_bytes(k_dim: int, tile: TileCandidate, fmt: PlaneFormat) -> int:
    """VMEM bytes of the full decoded digit strip for one N tile.

    The kernel caches the uint8->int8 decode of every K block of the
    current N tile (kernel.py): ceil(K/bk) slots of (bk, P*bn) int8.
    """
    slots = _ceil(k_dim, tile.bk)
    return slots * tile.bk * fmt.planes * tile.bn


@functools.lru_cache(maxsize=4096)
def autotune_tile(
    m: int,
    k_dim: int,
    n: int,
    *,
    w_bits: int,
    k: int,
    variant: str = "st",
    hw: HW = TPU_V5E,
    vmem_budget: Optional[float] = None,
) -> TileCandidate:
    """Per-layer tile selection from the paper's Eq. 1-3 cost model.

    One GEMM's (M, K, N, w_Q, k) is scored against every tile candidate
    with the same roofline used for whole-model DSE (``choose_tile``);
    the in-process ``lru_cache`` keys on the problem shape so a serve
    graph autotunes each distinct layer shape exactly once.  This
    replaces the fixed 128^3 ``TileShape`` default: asymmetric layer
    dims get asymmetric tiles, exactly the paper's Table II effect.
    """
    return choose_tile(
        [Gemm("layer", m, k_dim, n)],
        w_bits=w_bits, k=k, variant=variant, hw=hw, vmem_budget=vmem_budget,
    ).tile


def dse_sweep(
    gemms: Sequence[Gemm],
    *,
    w_bits: int,
    slices: Sequence[int] = (1, 2, 4, 8),
    variants: Sequence[str] = ("st", "sa"),
    hw: HW = TPU_V5E,
) -> List[DseChoice]:
    """Blue+red+green: sweep operand slice k and consolidation variant.

    Returns choices sorted by total model time (best first) — the Table II
    analogue.  k > w_bits wastes PPG capacity (idle plane bits) exactly as
    in the paper; those points remain in the sweep to show the penalty.
    """
    out = []
    for k, variant in itertools.product(slices, variants):
        try:
            out.append(choose_tile(gemms, w_bits=w_bits, k=k, variant=variant, hw=hw))
        except ValueError:
            continue
    return sorted(out, key=lambda c: c.total_time_s)
