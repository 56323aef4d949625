"""Work counted from shapes: the model's FLOPs per epoch, the least work
of the aggregation kernels, and the collective bytes of a compiled step.

Counts use each partition's real (unpadded) rows and arcs. ``layers`` is
the list of (input width, output width) of the GCN layers.
"""
from __future__ import annotations

import re
from typing import Dict, List, Sequence, Tuple

Widths = Sequence[Tuple[int, int]]


def layer_widths(feature_dim: int, hidden_dim: int, embed_dim: int,
                 num_layers: int) -> List[Tuple[int, int]]:
    dims = [feature_dim] + [hidden_dim] * (num_layers - 1) + [embed_dim]
    return list(zip(dims[:-1], dims[1:]))


def model_flops_per_epoch(nodes: Sequence[int], arcs: Sequence[int],
                          layers: Widths, num_classes: int) -> float:
    """Multiply-adds (x2) one training epoch needs over all partitions:
    the aggregations, the dense products and the head, forward and
    backward. The first layer needs no input gradient (the features are
    not trained) and no edge weight is trained, so neither is counted."""
    total = 0.0
    embed = layers[-1][1]
    for n, e in zip(nodes, arcs):
        for i, (fi, fo) in enumerate(layers):
            total += 2 * e * fi + 2 * n * fi * fo          # forward
            total += 2 * n * fi * fo                       # dW
            if i > 0:
                total += 2 * n * fi * fo + 2 * e * fi      # da, dh
        total += 3 * 2 * n * embed * num_classes           # head f + b
    return total


def aggregation_least_work(nodes: Sequence[int], arcs: Sequence[int],
                           layers: Widths, backward: bool
                           ) -> List[Tuple[float, float]]:
    """(FLOPs, bytes) of each aggregation kernel call one pass makes, summed
    over partitions: the least any implementation must do.

    Forward, per layer: the fused layer reads the input rows once
    (N*F*4 B), the arcs (source, destination, weight: 12 B each) and writes
    the output (N*F_out*4 B); it does 2*E*F for the aggregation and
    2*N*F*F_out for the fused dense product. Backward (``backward``), per
    layer after the first: the transposed aggregation of the input gradient,
    2*E*F, reading and writing N*F*4 B and the arcs."""
    calls = []
    for i, (fi, fo) in enumerate(layers):
        flops = sum(2 * e * fi + 2 * n * fi * fo for n, e in zip(nodes, arcs))
        byts = sum(n * fi * 4 + e * 12 + n * fo * 4
                   for n, e in zip(nodes, arcs))
        calls.append((flops, byts))
        if backward and i > 0:
            calls.append((sum(2 * e * fi for e in arcs),
                          sum(2 * n * fi * 4 + e * 12
                              for n, e in zip(nodes, arcs))))
    return calls


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
