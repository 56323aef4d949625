"""GCN and GraphSAGE layers on padded edge-list subgraphs.

Aggregation is gather + segment-sum over the destination-sorted arc list of a
:class:`repro.core.assemble.PartitionBatch` row — exactly the access pattern
the Pallas kernel in :mod:`repro.kernels.csr_aggregate` implements for TPU;
here we default to the jnp path and switch to the kernel via ``use_kernel``.

Under ``use_kernel=True`` the layer entry points resolve a
:class:`repro.kernels.autotune.KernelConfig` for the call's shape (backend +
shape-bucket, DESIGN.md §14) and route the WHOLE layer through
:func:`repro.kernels.ops.fused_gcn_layer` — on TPU that is the fused
aggregate+dense+bias+relu kernel; on interpret-mode backends the autotuner
resolves to the XLA strategy of the same math. Resolution happens at trace
time and the config is a static jit argument, so retuning triggers a
recompile instead of serving a stale kernel.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp


def _kernel_config(n: int, e: int, f: int):
    from repro.kernels.autotune import get_config
    return get_config(n, e, f)


def aggregate_mean(h: jnp.ndarray, edge_src: jnp.ndarray,
                   edge_dst: jnp.ndarray, edge_weight: jnp.ndarray,
                   in_degree: jnp.ndarray, use_kernel: bool = False
                   ) -> jnp.ndarray:
    """Weighted mean over in-neighbors.  h: [N, F] -> [N, F].

    Padding arcs carry weight 0 and may point at any in-range row (the
    single contract — see :mod:`repro.kernels.ops`): the zero weight is
    what makes them no-ops on both paths. Both paths are differentiable
    w.r.t. ``h`` and ``edge_weight``; the kernel path fuses the degree
    normalization into the Pallas epilogue, so it is one kernel call.

    With ``use_kernel=True`` the autotuned config decides: the Pallas
    strategies run the tuned-tile aggregation kernel; the ``"xla"``
    strategy (interpret-mode backends) falls through to the jnp path —
    same math, no emulator.
    """
    if use_kernel:
        cfg = _kernel_config(h.shape[0], edge_src.shape[0], h.shape[1])
        if cfg.uses_pallas:
            from repro.kernels.ops import csr_aggregate
            inv = 1.0 / jnp.maximum(in_degree, 1.0)
            return csr_aggregate(h, edge_src, edge_dst, edge_weight,
                                 num_nodes=h.shape[0], inv_scale=inv,
                                 config=cfg)
    with jax.named_scope("aggregation"):
        msgs = h[edge_src] * edge_weight[:, None]
        summed = jax.ops.segment_sum(msgs, edge_dst,
                                     num_segments=h.shape[0])
        return summed / jnp.maximum(in_degree[:, None], 1.0)


def gcn_layer(params: Dict[str, jnp.ndarray], h: jnp.ndarray,
              edge_src, edge_dst, edge_weight, in_degree,
              activate: bool = True, use_kernel: bool = False) -> jnp.ndarray:
    """Paper eq. (1): h_v = sigma( mean_{u in N(v)} W h_u ).

    Transform-then-aggregate commuted to aggregate-then-transform (they are
    identical for a linear W and cheaper when F_in >= F_out). The kernel
    path runs the whole layer through the fused dispatcher (one pallas_call
    on TPU — aggregate, dense, bias, and relu never leave VMEM).
    """
    if use_kernel:
        from repro.kernels.ops import fused_gcn_layer
        cfg = _kernel_config(h.shape[0], edge_src.shape[0], h.shape[1])
        return fused_gcn_layer(h, edge_src, edge_dst, edge_weight, in_degree,
                               params["w"], params["b"], activate=activate,
                               config=cfg)
    agg = aggregate_mean(h, edge_src, edge_dst, edge_weight, in_degree,
                         use_kernel)
    out = agg @ params["w"] + params["b"]
    return jax.nn.relu(out) if activate else out


def sage_layer(params: Dict[str, jnp.ndarray], h: jnp.ndarray,
               edge_src, edge_dst, edge_weight, in_degree,
               activate: bool = True, use_kernel: bool = False) -> jnp.ndarray:
    """Paper eq. (2): h_v = sigma( W . concat(h_v, AGG(h_u)) ) with mean AGG.

    Implemented as h @ W_self + agg @ W_neigh (== concat form, fused). The
    kernel path computes the neighbor half via the fused dispatcher
    (activation deferred until after the self term joins)."""
    if use_kernel:
        from repro.kernels.ops import fused_gcn_layer
        cfg = _kernel_config(h.shape[0], edge_src.shape[0], h.shape[1])
        neigh = fused_gcn_layer(h, edge_src, edge_dst, edge_weight,
                                in_degree, params["w_neigh"],
                                jnp.zeros_like(params["b"]),
                                activate=False, config=cfg)
        out = h @ params["w_self"] + neigh + params["b"]
        return jax.nn.relu(out) if activate else out
    agg = aggregate_mean(h, edge_src, edge_dst, edge_weight, in_degree,
                         use_kernel)
    out = h @ params["w_self"] + agg @ params["w_neigh"] + params["b"]
    return jax.nn.relu(out) if activate else out


def init_gcn_layer(key, f_in: int, f_out: int) -> Dict[str, jnp.ndarray]:
    scale = jnp.sqrt(2.0 / f_in)
    return {"w": jax.random.normal(key, (f_in, f_out), jnp.float32) * scale,
            "b": jnp.zeros((f_out,), jnp.float32)}


def init_sage_layer(key, f_in: int, f_out: int) -> Dict[str, jnp.ndarray]:
    k1, k2 = jax.random.split(key)
    scale = jnp.sqrt(2.0 / f_in)
    return {"w_self": jax.random.normal(k1, (f_in, f_out), jnp.float32) * scale,
            "w_neigh": jax.random.normal(k2, (f_in, f_out), jnp.float32) * scale,
            "b": jnp.zeros((f_out,), jnp.float32)}
