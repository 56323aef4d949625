"""Work counted from shapes that every model shares: the least time of a
list of kernel calls, and the collective bytes of a compiled step. A
model's own counts (its FLOPs per epoch, its aggregation's least work) are
in its model module, ``bench/models/<model>.py``.
"""
from __future__ import annotations

import re
from typing import Dict, Sequence, Tuple


def least_seconds(calls: Sequence[Tuple[float, float]], peak_flops: float,
                  hbm_bytes_per_s: float) -> float:
    """Sum over calls of the larger of the two lower bounds."""
    return sum(max(f / peak_flops, b / hbm_bytes_per_s) for f, b in calls)


# ---------------------------------------------------------------------------
# collective bytes of an optimized (post-SPMD) HLO module: the result bytes
# of every communication op, per device; async start/done pairs count once
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVE_OP = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\](?:\{[^}]*\})?))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?[\s(]")


def _shape_bytes(shape: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(shape):
        if dtype in _DTYPE_BYTES:
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for m in _COLLECTIVE_OP.finditer(hlo_text):
        shape, op, suffix = m.groups()
        if suffix == "-done":
            continue
        out[op] = out.get(op, 0) + _shape_bytes(shape)
    out["total"] = sum(out.values())
    return out
