"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds:

    compute    = HLO_FLOPs   / (chips × peak_FLOP/s)
    memory     = HLO_bytes   / (chips × HBM_bw)
    collective = coll_bytes  / (chips × link_bw)

HLO_FLOPs / HLO_bytes come from compiled.cost_analysis().  Collective
bytes are NOT in cost_analysis: we parse the optimized HLO text and sum
the operand sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute.

Hardware constants: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI.

NOTE on semantics: with SPMD partitioning, cost_analysis() FLOPs/bytes
are for the per-device partitioned module; collective shapes in the HLO
are likewise per-device buffers.  We therefore divide by ONE chip's
peak, not the whole mesh — the terms are per-device step times, which is
what a roofline compares.
"""

from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # B/s / chip
ICI_BW = 50e9              # B/s / link (per direction)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"(pred|[sufbc]\d+|bf16)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes per collective kind from optimized HLO."""
    out: dict[str, int] = {}
    count: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        if "-done" in line:
            continue  # the -start op already carried the shape
        b = _shape_bytes(shape_str)
        out[kind] = out.get(kind, 0) + b
        count[kind] = count.get(kind, 0) + 1
    out["_ops"] = sum(count.values())
    out["_counts"] = count
    return out


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    coll_bytes: float
    coll_detail: dict
    n_devices: int

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes,
            "coll_detail": {
                k: v for k, v in self.coll_detail.items() if k != "_counts"
            },
            "coll_counts": self.coll_detail.get("_counts", {}),
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "n_devices": self.n_devices,
        }


def extract_roofline(compiled, n_devices: int) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    cb = sum(v for k, v in coll.items() if not k.startswith("_"))
    return Roofline(
        flops=flops, hbm_bytes=hbm, coll_bytes=float(cb),
        coll_detail=coll, n_devices=n_devices,
    )
