"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds:

  compute    = HLO_FLOPs_per_device / PEAK_FLOPS
  memory     = HLO_bytes_per_device / HBM_BW
  collective = wire_bytes_per_device / ICI_BW

FLOPs/bytes come from ``compiled.cost_analysis()`` (the executable is the
SPMD-partitioned per-device module). Collective bytes are NOT in
cost_analysis: we parse the partitioned HLO and sum per-op wire-byte
estimates using ring-algorithm factors and the parsed replica-group size.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float    # bf16 FLOP/s
    hbm_bw: float   # HBM bytes/s
    ici_bw: float   # interchip bytes/s per link


# Published per-chip peaks, keyed by jax ``Device.device_kind``.
# "TPU v5 lite": Google Cloud TPU documentation, "TPU v5e" — 197 TFLOP/s
# bf16, 819 GB/s HBM, 1,600 Gbit/s interchip interconnect per chip (four
# links of 50 GB/s).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a kind without published peaks is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to roofline.PEAKS with their source") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9_]+\[[^\]]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9_]+)\[([0-9,]*)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _largest_array_bytes(type_str: str) -> int:
    """Bytes of the largest array in a (possibly tuple) type: a combined
    collective carries many arrays at once."""
    return max((_type_bytes(f"{dt}[{dims}]")
                for dt, dims in _SHAPE_RE.findall(type_str)), default=0)


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    return default


# Ring-algorithm wire-byte factors per chip, as multiples of the RESULT size.
def _wire_bytes(op: str, result_bytes: int, g: int) -> float:
    if g <= 1:
        return 0.0
    if op == "all-gather":          # receive everyone else's shard
        return result_bytes * (g - 1) / g
    if op == "reduce-scatter":      # result is the local shard
        return result_bytes * (g - 1)
    if op == "all-reduce":          # RS + AG
        return 2.0 * result_bytes * (g - 1) / g
    if op == "all-to-all":
        return result_bytes * (g - 1) / g
    if op == "collective-permute":
        return float(result_bytes)
    return 0.0


@dataclasses.dataclass
class CollectiveStats:
    wire_bytes: float
    payload_bytes: float
    by_type: Dict[str, float]
    counts: Dict[str, int]
    largest_bytes: float            # largest array a collective returns

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


def parse_collectives(hlo_text: str, total_devices: int) -> CollectiveStats:
    """Collectives of one partitioned module, each counted once: the TPU
    compiler repeats an asynchronous collective inside every fusion that
    continues it, all under the collective's one ``channel_id``."""
    by_type: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    wire = 0.0
    payload = 0.0
    largest = 0.0
    seen = set()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        ch = _CHANNEL_RE.search(line)
        if ch:
            if ch.group(1) in seen:
                continue
            seen.add(ch.group(1))
        type_str, op = m.group(1), m.group(2)
        size = _type_bytes(type_str)
        g = _group_size(line, total_devices)
        w = _wire_bytes(op, size, g)
        wire += w
        payload += size
        largest = max(largest, float(_largest_array_bytes(type_str)))
        by_type[op] = by_type.get(op, 0.0) + w
        counts[op] = counts.get(op, 0) + 1
    return CollectiveStats(wire, payload, by_type, counts, largest)


def roofline_terms(flops: float, bytes_accessed: float, wire_bytes: float,
                   device_kind: str) -> Dict:
    pk = peaks_for(device_kind)
    compute_t = flops / pk.flops
    memory_t = bytes_accessed / pk.hbm_bw
    coll_t = wire_bytes / pk.ici_bw
    terms = {"compute_s": compute_t, "memory_s": memory_t, "collective_s": coll_t}
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    total = max(bound, 1e-30)
    return {
        **terms,
        "dominant": dom.replace("_s", ""),
        "bound_s": bound,
        "roofline_fraction_compute": compute_t / total,
    }


def model_flops(cfg, shape_cell, kind: str) -> float:
    """Analytic useful FLOPs per step: 6·N·D train, 2·N·D forward-only
    (MoE: N_active)."""
    n = cfg.active_param_count()
    if kind == "train":
        tokens = shape_cell.global_batch * shape_cell.seq_len
        return 6.0 * n * tokens
    if kind == "prefill":
        tokens = shape_cell.global_batch * shape_cell.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence (attention reads the cache; the 2·N·D
    # matmul term is the useful-work yardstick)
    return 2.0 * n * shape_cell.global_batch
