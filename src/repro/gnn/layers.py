"""GCN, GraphSAGE and GAT layers on padded edge-list subgraphs.

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

The GAT layer's arc weights are learned: it scores and normalises its arcs
in XLA and sums over them through the same aggregation kernel, its heads
folded into the kernel's partition axis (DESIGN.md §17).

Every layer shares one signature, ``layer(params, h, edge_src, edge_dst,
edge_weight, in_degree, activate, use_kernel)``, and every init
``init(key, f_in, f_out, heads, concat)``; :func:`layer_for` maps a
``GNNConfig.kind`` to the pair.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Tuple

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


def init_gcn_layer(key, f_in: int, f_out: int, heads: int = 1,
                   concat: bool = True) -> Dict[str, jnp.ndarray]:
    """He-normal ``w`` and a zero bias. ``heads`` and ``concat`` are GAT's:
    a one-head layer has nothing to concatenate."""
    scale = jnp.sqrt(2.0 / f_in)
    return {"w": jax.random.normal(key, (f_in, f_out), jnp.float32) * scale,
            "b": jnp.zeros((f_out,), jnp.float32)}


def init_sage_layer(key, f_in: int, f_out: int, heads: int = 1,
                    concat: bool = True) -> Dict[str, jnp.ndarray]:
    """As :func:`init_gcn_layer`, for the self and the neighbour weights."""
    k1, k2 = jax.random.split(key)
    scale = jnp.sqrt(2.0 / f_in)
    return {"w_self": jax.random.normal(k1, (f_in, f_out), jnp.float32) * scale,
            "w_neigh": jax.random.normal(k2, (f_in, f_out), jnp.float32) * scale,
            "b": jnp.zeros((f_out,), jnp.float32)}


# ---------------------------------------------------------------------------
# GAT (Veličković et al., arXiv:1710.10903, eq. (1)-(6))
# ---------------------------------------------------------------------------
def _arc_sum(z: jnp.ndarray, alpha: jnp.ndarray, edge_src, edge_dst,
             use_kernel: bool) -> jnp.ndarray:
    """``out[n, h] = sum_{e -> n} alpha[e, h] * z[src[e], h]``: z [N, H, D],
    alpha [E, H] -> [N, H, D].

    On the kernel path each head is one :func:`repro.kernels.ops.
    csr_aggregate` call with ``alpha[:, h]`` as its arc weights and no
    scale; the ``vmap`` over heads folds into the kernel's partition axis,
    and its VJP gives the cotangents of ``z`` (the transposed pass) and of
    ``alpha`` (``edge_dot``)."""
    n, _, d = z.shape
    if use_kernel:
        cfg = _kernel_config(n, edge_src.shape[0], d)
        if cfg.uses_pallas:
            from repro.kernels.ops import csr_aggregate
            one_head = lambda zh, ah: csr_aggregate(
                zh, edge_src, edge_dst, ah, num_nodes=n, config=cfg)
            return jax.vmap(one_head, in_axes=1, out_axes=1)(z, alpha)
    with jax.named_scope("aggregation"):
        return jax.ops.segment_sum(z[edge_src] * alpha[..., None], edge_dst,
                                   num_segments=n)


def gat_layer(params: Dict[str, jnp.ndarray], h: jnp.ndarray,
              edge_src, edge_dst, edge_weight, in_degree,
              activate: bool = True, use_kernel: bool = False) -> jnp.ndarray:
    """Multi-head graph attention over a destination-sorted arc list.

    ``params``: ``w`` [F_in, H*D], ``a_l``/``a_r`` [H, D] (H and D are read
    from their shape) and ``b``. With ``z = h @ w`` as [N, H, D], head h
    scores arc u -> v as ``LeakyReLU_0.2(<z_u, a_l> + <z_v, a_r>)`` and the
    self arc v -> v alike; a softmax over each row's in-arcs and itself
    weighs the sum of the sources' rows. A hidden layer (``activate``)
    concatenates its heads, adds ``b`` [H*D] and applies ELU; the last
    averages them and adds ``b`` [D].

    Arc weights are not multiplied in: a weight-0 arc is padding (the
    single contract, :mod:`repro.kernels.ops`) and scores -inf, so its
    attention is 0. The self arc keeps every row's normaliser at 1 or more,
    so a row with no in-arcs, or a padding row, reads its own ``z``.
    ``in_degree`` is unused. Scores and the softmax run in XLA, in float32,
    under ``jax.named_scope("attention")``; on the kernel path the dense
    products run at ``Precision.HIGHEST``, as the kernels do."""
    del in_degree
    heads, d = params["a_l"].shape
    n = h.shape[0]
    precision = jax.lax.Precision.HIGHEST if use_kernel else None
    z = jnp.dot(h, params["w"], precision=precision).reshape(n, heads, d)
    with jax.named_scope("attention"):
        el = jnp.einsum("nhd,hd->nh", z, params["a_l"], precision=precision)
        er = jnp.einsum("nhd,hd->nh", z, params["a_r"], precision=precision)
        real = (edge_weight != 0)[:, None]
        s = jnp.where(real, jax.nn.leaky_relu(el[edge_src] + er[edge_dst], 0.2),
                      -jnp.inf)                                   # [E, H]
        s_self = jax.nn.leaky_relu(el + er, 0.2)                           # [N, H]
        m = jax.lax.stop_gradient(jnp.maximum(
            jax.ops.segment_max(s, edge_dst, num_segments=n), s_self))
        p = jnp.exp(s - m[edge_dst])
        p_self = jnp.exp(s_self - m)
        norm = jax.ops.segment_sum(p, edge_dst, num_segments=n) + p_self
        alpha = p / norm[edge_dst]
        alpha_self = p_self / norm
    out = (_arc_sum(z, alpha, edge_src, edge_dst, use_kernel)
           + alpha_self[..., None] * z)
    if activate:
        return jax.nn.elu(out.reshape(n, heads * d) + params["b"])
    return jnp.mean(out, axis=1) + params["b"]


def _glorot(key, shape, fan_in: int, fan_out: int) -> jnp.ndarray:
    limit = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def init_gat_layer(key, f_in: int, f_out: int, heads: int = 1,
                   concat: bool = True) -> Dict[str, jnp.ndarray]:
    """Glorot-uniform ``w`` [f_in, heads*f_out] and attention vectors
    ``a_l``/``a_r`` [heads, f_out] (each head's ``a`` = [a_l; a_r] is the
    paper's single-layer map from 2*f_out to 1), and a zero bias, [heads *
    f_out] where the layer concatenates its heads, else [f_out]."""
    kw, kl, kr = jax.random.split(key, 3)
    return {"w": _glorot(kw, (f_in, heads * f_out), f_in, heads * f_out),
            "a_l": _glorot(kl, (heads, f_out), 2 * f_out, 1),
            "a_r": _glorot(kr, (heads, f_out), 2 * f_out, 1),
            "b": jnp.zeros((heads * f_out if concat else f_out,),
                           jnp.float32)}


_LAYERS: Dict[str, Tuple[Callable, Callable]] = {
    "gcn": (gcn_layer, init_gcn_layer),
    "sage": (sage_layer, init_sage_layer),
    "gat": (gat_layer, init_gat_layer),
}


def layer_for(kind: str) -> Tuple[Callable, Callable]:
    """``(layer, init)`` of a ``GNNConfig.kind``; an unknown kind raises."""
    if kind not in _LAYERS:
        raise ValueError(f"unknown GNN kind {kind!r}; have {sorted(_LAYERS)}")
    return _LAYERS[kind]
