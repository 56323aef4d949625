"""Jit'd public wrappers around the Pallas kernels (padding + dispatch).

The kernels compile through Mosaic on a TPU and run in the Pallas
interpreter on every other backend; the backend alone decides
(:func:`repro.kernels.autotune.interpret_mode`), so no caller can send a TPU
run through the interpreter.

**Padding contract** (the single contract for every aggregation path — the
jnp segment-sum in :mod:`repro.gnn.layers`, the oracle in
:mod:`repro.kernels.ref`, and the Pallas kernels): *padding arcs carry
weight 0 and may point at any in-range row; zero weight is what makes them
no-ops, not where they park.* By convention :mod:`repro.core.assemble`
parks its padding arcs at row ``n_pad - 1``, and the alignment padding
added here repeats the list's last destination (its source is row 0):
both keep a sorted ``edge_dst`` sorted, which is what keeps each node
tile's granule range tight (:mod:`repro.kernels.csr_aggregate`). Both are
no-ops on both paths, which ``tests/test_kernels.py`` pins.

**Strategy dispatch** (DESIGN.md §14): the tiling/strategy choice lives in
a :class:`repro.kernels.autotune.KernelConfig`, resolved per (backend,
shape-bucket) by :func:`repro.kernels.autotune.get_config` and threaded
through these wrappers as a *static* jit argument — never read from module
state inside a jit, so a cache update can never serve a stale compile.
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .autotune import KernelConfig
from .csr_aggregate import (DEFAULT_CONFIG, EDGE_BLOCK, FEAT_TILE, NODE_TILE,
                            csr_aggregate_pallas)
from .flash_decode import flash_decode_pallas
from .fused_layer import LANES, fused_gcn_pallas, fused_gcn_reference


def _pad_to(x: jnp.ndarray, mult: int, axis: int, value=0) -> jnp.ndarray:
    size = x.shape[axis]
    target = ((size + mult - 1) // mult) * mult
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def _padded_rows(n: int, config: KernelConfig) -> int:
    """Rows after the node padding: a multiple of 8, and of the node tile
    past one tile."""
    n_pad = -(-n // 8) * 8
    return -(-n_pad // config.node_tile) * config.node_tile \
        if n_pad > config.node_tile else n_pad


def _pad_graph(h, edge_src, edge_dst, edge_weight, inv_scale,
               config: KernelConfig):
    """Pad (h, arcs, inv) to the config's tile contract. Alignment arcs
    carry weight 0, start at row 0 and repeat the last destination — a
    no-op per the padding contract that keeps a sorted list sorted."""
    n = h.shape[0]
    n_pad = _padded_rows(n, config)
    hp = _pad_to(h, config.feat_tile, 1)
    hp = jnp.pad(hp, ((0, n_pad - n), (0, 0)))
    granule = config.edge_granule
    es = _pad_to(edge_src, granule, 0)
    ed = edge_dst
    if ed.shape[0] % granule:
        ed = jax.lax.pad(ed, ed[-1], ((0, -ed.shape[0] % granule, 0),))
    ew = _pad_to(edge_weight, granule, 0)
    inv = None
    if inv_scale is not None:
        inv = jnp.pad(inv_scale.astype(jnp.float32), (0, n_pad - n),
                      constant_values=1.0)
    return hp, es, ed, ew, inv, n_pad


def streamed_pairs(edge_dst: np.ndarray, n_pad: int,
                   config: KernelConfig) -> Tuple[int, int]:
    """``(streamed, dense)``: the (node tile, edge granule) pairs the
    aggregation kernels stream for the arc list ``edge_dst`` (``[E]``, or
    ``[k, E]`` summed over partitions) of a graph of ``n_pad`` rows, and
    the pairs of a grid over every tile and granule — per feature tile,
    after this module's padding. Host-side numpy, the twin of
    :func:`repro.kernels.csr_aggregate.tile_granule_ranges`."""
    dst = np.asarray(edge_dst, np.int64)
    dst = dst.reshape(-1, dst.shape[-1])
    rows = _padded_rows(n_pad, config)
    nt = min(rows, config.node_tile)
    granule = config.edge_granule
    extra = -dst.shape[1] % granule
    if extra:
        dst = np.concatenate([dst, np.repeat(dst[:, -1:], extra, axis=1)],
                             axis=1)
    granules = dst.reshape(len(dst), -1, granule)
    glo, ghi = granules.min(axis=2), granules.max(axis=2)        # [k, G]
    starts = np.arange(rows // nt)[:, None] * nt                  # [T, 1]
    meets = ((ghi[:, None, :] >= starts)
             & (glo[:, None, :] < starts + nt))                   # [k, T, G]
    g = np.arange(granules.shape[1])
    g0 = np.where(meets, g, granules.shape[1]).min(axis=2)
    g1 = np.where(meets, g, -1).max(axis=2)
    streamed = int(np.maximum(g1 - g0 + 1, 0).sum())
    return streamed, int(meets.size)


@functools.partial(jax.jit, static_argnames=("num_nodes", "config"))
def csr_aggregate(h: jnp.ndarray, edge_src: jnp.ndarray,
                  edge_dst: jnp.ndarray, edge_weight: jnp.ndarray,
                  num_nodes: int,
                  inv_scale: jnp.ndarray | None = None,
                  config: KernelConfig | None = None) -> jnp.ndarray:
    """Weighted neighbor-sum via the Pallas kernel, with automatic padding.

    Semantics match :func:`repro.kernels.ref.csr_aggregate_ref` exactly;
    with ``inv_scale`` given, each output row is additionally multiplied by
    it inside the kernel epilogue (pass ``1/max(in_degree, 1)`` to get the
    GCN weighted *mean* as one fused kernel call).

    Differentiable w.r.t. ``h`` and ``edge_weight``: the kernel carries a
    custom VJP whose transpose pass runs the same kernel over the reversed
    arc list — the src-sorted permutation it needs is precomputed here (and
    dead-code-eliminated by XLA on non-differentiated calls). ``inv_scale``
    and the arc lists are graph structure: zero cotangent by design.

    ``config`` picks the tuned tile sizes/stream factor (default: the
    untuned point); its *strategy* field is ignored here — this wrapper is
    always the Pallas aggregation (strategy dispatch happens one level up,
    in :func:`repro.gnn.layers.aggregate_mean` / :func:`fused_gcn_layer`).
    """
    if config is None:
        config = DEFAULT_CONFIG
    n, f = h.shape
    hp, es, ed, ew, inv, n_pad = _pad_graph(
        h, edge_src, edge_dst, edge_weight, inv_scale, config)
    perm = jnp.argsort(es)           # bwd-only; DCE'd on forward-only calls
    out = csr_aggregate_pallas(hp, es, ed, ew, num_nodes=n_pad,
                               inv_scale=inv, src_perm=perm, config=config)
    return out[:n, :f].astype(h.dtype)


@functools.partial(jax.jit, static_argnames=("activate", "config"))
def fused_gcn_layer(h: jnp.ndarray, edge_src: jnp.ndarray,
                    edge_dst: jnp.ndarray, edge_weight: jnp.ndarray,
                    in_degree: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                    activate: bool = True,
                    config: KernelConfig | None = None) -> jnp.ndarray:
    """One fused GNN layer: ``act(mean-aggregate(h) @ w + b)``.

    THE kernel-path entry point for the training modes (DESIGN.md §14):
    dispatches on ``config.strategy`` —

    - ``"pallas_fused"``: one ``pallas_call`` for the whole layer
      (:func:`repro.kernels.fused_layer.fused_gcn_pallas`), padding
      handled here;
    - ``"pallas"``: the aggregation kernel with tuned tiles + an XLA
      dense epilogue;
    - ``"xla"``: the jnp composition under this jit (the right answer
      wherever Pallas would run in interpret mode).

    Differentiable w.r.t. ``h``, ``edge_weight``, ``w``, ``b`` on every
    strategy; parity across strategies is pinned in
    ``tests/test_fused_layer.py``.
    """
    if config is None:
        config = DEFAULT_CONFIG
    inv = 1.0 / jnp.maximum(in_degree.astype(jnp.float32), 1.0)
    if config.strategy == "xla":
        return fused_gcn_reference(h, edge_src, edge_dst, edge_weight, inv,
                                   w, b, activate=activate)
    if config.strategy == "pallas":
        agg = csr_aggregate(h, edge_src, edge_dst, edge_weight,
                            num_nodes=h.shape[0], inv_scale=inv,
                            config=config)
        # f32 like the kernel's own products (one bf16 pass on a TPU
        # otherwise)
        z = (jnp.dot(agg.astype(jnp.float32), w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
             + b.astype(jnp.float32)[None, :])
        # jax.nn.relu for the gradient-at-zero convention (see fused_layer)
        out = jax.nn.relu(z) if activate else z
        return out.astype(h.dtype)
    # pallas_fused: pad to the full contract (incl. FO lanes), one call.
    n, f = h.shape
    fo = w.shape[1]
    hp, es, ed, ew, invp, n_pad = _pad_graph(
        h, edge_src, edge_dst, edge_weight, inv, config)
    wp = _pad_to(jnp.pad(w, ((0, hp.shape[1] - f), (0, 0))), LANES, 1)
    bp = _pad_to(b, LANES, 0)
    perm = jnp.argsort(es)
    out = fused_gcn_pallas(hp, es, ed, ew, num_nodes=n_pad, wmat=wp, b=bp,
                           activate=activate, inv_scale=invp,
                           src_perm=perm, config=config)
    return out[:n, :fo].astype(h.dtype)


@jax.jit
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 length: jnp.ndarray) -> jnp.ndarray:
    """Single-token GQA decode attention. q: [H, D]; k/v: [S, Hkv, D]."""
    return flash_decode_pallas(q, k, v, length)
