"""Pallas TPU kernel: one fused GNN layer — aggregate + dense + bias + relu
in a single ``pallas_call``, with a custom VJP so it is a real training path.

Why fuse (DESIGN.md §14): the aggregation kernel computes the aggregate,
writes it to HBM, and XLA then reads it back for the dense transform — one
full [N, F] round trip plus a second kernel launch per layer. This kernel keeps
the aggregate tile in VMEM and runs the dense epilogue on it while it is
still resident, following the fused-epilogue idiom of
``kernels/flash_decode.py`` (accumulator scratch + ``pl.when`` init/finish
over the feature-tile grid dimension):

    grid = (partitions p, node tiles i, feature tiles ft); ft fastest
    per (i, ft):   agg[i, ft] = Σ_{g in [g0[i], g1[i]]} onehot-matmul(granule g)
                   agg[i, ft] *= inv[i]                  # mean epilogue
                   zacc[i]   += agg[i, ft] @ W[ft, :]    # dense, FT-chunked
    at last (ft):  out[i] = relu(zacc[i] + b)            # bias + act

``zacc`` ([NT, FO] f32 scratch) persists across grid steps (Pallas scratch
semantics), so the dense transform is accumulated feature-tile by
feature-tile without the aggregate ever leaving VMEM. The aggregate is
*also* written out — the backward pass needs it for dW, and XLA
dead-code-eliminates the store on forward-only calls. The XLA row gather
before the call, the per-tile granule range streamed through two-slot
VMEM buffers, the degenerate-block skip and the partition axis that
``vmap`` folds into are shared with :mod:`repro.kernels.csr_aggregate`; a
tile whose range is empty (no in-arcs) still runs the epilogue, so its
rows read ``act(b)``. The weight block travels as ``[FT, FO]``, the bias
as a ``[1, FO]`` row.

Backward: with A the weighted adjacency, ``agg = diag(inv)·A·h``,
``z = agg@W + b``, ``out = act(z)``:

    gz  = g ⊙ 1[out > 0]          (relu; identity otherwise)
    db  = Σ_rows gz
    dW  = aggᵀ @ gz               (XLA matmul over the saved aggregate)
    da  = gz @ Wᵀ
    dh  = Aᵀ·diag(inv)·da         — the transpose-aggregation kernel
    dw[e] = inv[dst[e]]·<da[dst[e]], h[src[e]]>  — the edge-dot kernel

i.e. the reverse pass reuses the aggregation kernels (`_aggregate`,
`_edge_dot`) with the same KernelConfig, so tuned tiles apply to both
directions.

:func:`fused_gcn_reference` is the jnp composition of the same math — the
parity oracle in tests AND the ``"xla"`` strategy the autotuner picks on
backends where Pallas would run in interpret mode.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import KernelConfig, interpret_mode
from .csr_aggregate import (DEFAULT_CONFIG, ShapeContractError, _aggregate,
                            _edge_dot, _node_tile, check_shape_contract,
                            partition_batched, stream_inputs, stream_specs,
                            stream_tile_granules)

LANES = 128


def fused_gcn_reference(h, edge_src, edge_dst, edge_weight, inv_scale,
                        w, b, activate: bool = True) -> jnp.ndarray:
    """jnp composition of the fused layer: oracle + the "xla" strategy."""
    n = h.shape[0]
    with jax.named_scope("aggregation"):
        msgs = (jnp.take(h, edge_src, axis=0).astype(jnp.float32)
                * edge_weight.astype(jnp.float32)[:, None])
        agg = jax.ops.segment_sum(msgs, edge_dst, num_segments=n)
        agg = agg * inv_scale.astype(jnp.float32)[:, None]
    z = agg @ w.astype(jnp.float32) + b.astype(jnp.float32)[None, :]
    # jax.nn.relu, NOT jnp.maximum: their values agree but their gradients
    # at z == 0 differ (relu' = 0 vs maximum's 0.5 tie split) — and z == 0
    # is exact for zero-degree rows under zero-initialized biases. The
    # kernel VJP's (out > 0) mask follows the relu convention.
    out = jax.nn.relu(z) if activate else z
    return out.astype(h.dtype)


def _fused_kernel(g0_ref, g1_ref, lo_ref, hi_ref, dst_hbm, w_hbm, rows_hbm,
                  inv_ref, wmat_ref, b_ref, agg_ref, out_ref, dst_buf, w_buf,
                  rows_buf, sems, zacc_ref, *, edge_block: int, stream: int,
                  activate: bool):
    ftid = pl.program_id(2)
    agg_ref[...] = jnp.zeros_like(agg_ref)
    stream_tile_granules(g0_ref, g1_ref, lo_ref, hi_ref, dst_hbm, w_hbm,
                         rows_hbm, agg_ref, dst_buf, w_buf, rows_buf, sems,
                         edge_block=edge_block, stream=stream)

    # fused epilogue: normalization, then the dense transform on the still-
    # resident aggregate tile (zacc accumulates over feature tiles), then
    # bias + activation once the last feature tile lands.
    agg_ref[...] = agg_ref[...] * inv_ref[...]           # [NT, 1] column

    @pl.when(ftid == 0)
    def _zacc_init():
        zacc_ref[...] = jnp.zeros_like(zacc_ref)

    zacc_ref[...] += jax.lax.dot(
        agg_ref[...], wmat_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(ftid == pl.num_programs(2) - 1)
    def _finish():
        z = zacc_ref[...] + b_ref[...].astype(jnp.float32)   # [1, FO] row
        out_ref[...] = jnp.maximum(z, 0.0) if activate else z


def _fused_call(g0, g1, lo, hi, dst, w, rows, inv, wmat, b, *,
                activate: bool, interpret: bool, config: KernelConfig):
    """The fused-layer ``pallas_call`` over a leading partition axis:
    returns ``[agg [P, N, F], out [P, N, FO]]``."""
    parts, n, _ = inv.shape
    f, fo = rows.shape[-1], wmat.shape[-1]
    nt = _node_tile(n, config.node_tile)
    ft_sz = min(config.feat_tile, f)
    in_specs, scratch = stream_specs((g0, g1, lo, hi), dst, w, rows, config)
    return pl.pallas_call(
        functools.partial(_fused_kernel, edge_block=config.edge_block,
                          stream=config.stream, activate=activate),
        grid=(parts, n // nt, f // ft_sz),
        in_specs=[
            *in_specs,
            pl.BlockSpec((None, nt, 1), lambda p, i, ft: (p, i, 0)),
            pl.BlockSpec((None, ft_sz, fo), lambda p, i, ft: (p, ft, 0)),
            pl.BlockSpec((None, 1, fo), lambda p, i, ft: (p, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, nt, ft_sz), lambda p, i, ft: (p, i, ft)),
            pl.BlockSpec((None, nt, fo), lambda p, i, ft: (p, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((parts, n, f), jnp.float32),
            jax.ShapeDtypeStruct((parts, n, fo), jnp.float32),
        ],
        scratch_shapes=[*scratch, pltpu.VMEM((nt, fo), jnp.float32)],
        interpret=interpret,
        name="gcn_fused_layer",
    )(g0, g1, lo, hi, dst, w, rows, inv, wmat, b)


def _fused_forward(h, edge_src, edge_dst, edge_weight, inv_scale, wmat, b,
                   *, activate: bool, interpret: bool, config: KernelConfig):
    """Aligned-domain fused layer: returns (out [N, FO], agg [N, F])."""
    n = h.shape[0]
    fo = wmat.shape[1]
    call = partition_batched(functools.partial(
        _fused_call, activate=activate, interpret=interpret, config=config))
    with jax.named_scope("aggregation"):
        agg, out = call(
            *stream_inputs(h, edge_src, edge_dst, edge_weight, config),
            inv_scale.reshape(n, 1), wmat, b.reshape(1, fo))
    return out, agg


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fused_diff(interpret, activate, config, h, edge_src, edge_dst,
                edge_weight, inv_scale, wmat, b, src_perm):
    del src_perm                     # bwd-only (see csr_aggregate)
    out, _ = _fused_forward(h, edge_src, edge_dst, edge_weight, inv_scale,
                            wmat, b, activate=activate, interpret=interpret,
                            config=config)
    return out


def _fused_diff_fwd(interpret, activate, config, h, edge_src, edge_dst,
                    edge_weight, inv_scale, wmat, b, src_perm):
    out, agg = _fused_forward(h, edge_src, edge_dst, edge_weight, inv_scale,
                              wmat, b, activate=activate,
                              interpret=interpret, config=config)
    return out, (h, edge_src, edge_dst, edge_weight, inv_scale, wmat,
                 src_perm, agg, out)


def _fused_diff_bwd(interpret, activate, config, res, g):
    h, src, dst, w, inv, wmat, perm, agg, out = res
    gz = g.astype(jnp.float32)
    if activate:
        gz = gz * (out > 0.0)
    db = jnp.sum(gz, axis=0)
    # f32 products like the forward kernel's (a TPU's default is one bf16
    # pass, ~1e-3 off the f32 reference gradients)
    mm = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    dwmat = mm(agg.T, gz)                                # [F, FO]
    da = mm(gz, wmat.astype(jnp.float32).T)              # [N, F]
    # dh: transpose aggregation over the reversed src-sorted arc list,
    # normalization folded into the reverse weights (same kernel and cfg).
    rev_w = jnp.take(w.astype(jnp.float32) * jnp.take(inv, dst), perm)
    dh = _aggregate(da, jnp.take(dst, perm), jnp.take(src, perm), rev_w,
                    None, interpret=interpret, config=config).astype(h.dtype)
    da_scaled = da * inv.astype(jnp.float32)[:, None]
    with jax.named_scope("aggregation"):
        dw = _edge_dot(jnp.take(h.astype(jnp.float32), src, axis=0),
                       jnp.take(da_scaled, dst, axis=0),
                       interpret=interpret, config=config).astype(w.dtype)
    zero_int = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return (dh, zero_int(src), zero_int(dst), dw, jnp.zeros_like(inv),
            dwmat.astype(wmat.dtype), db, zero_int(perm))


_fused_diff.defvjp(_fused_diff_fwd, _fused_diff_bwd)


@functools.partial(jax.jit, static_argnames=("num_nodes", "activate",
                                             "config"))
def fused_gcn_pallas(h: jnp.ndarray, edge_src: jnp.ndarray,
                     edge_dst: jnp.ndarray, edge_weight: jnp.ndarray,
                     num_nodes: int, wmat: jnp.ndarray, b: jnp.ndarray,
                     activate: bool = True,
                     inv_scale: jnp.ndarray | None = None,
                     src_perm: jnp.ndarray | None = None,
                     config: KernelConfig | None = None) -> jnp.ndarray:
    """Aligned-domain fused GNN layer (one pallas_call; see module doc).

    ``out = act((inv_scale ⊙ Σ_e w[e]·h[src[e]]→dst[e]) @ wmat + b)``.
    Differentiable w.r.t. ``h``, ``edge_weight``, ``wmat``, ``b``. Shape
    contract: the csr_aggregate contract plus FO % 128 == 0 (lane multiple
    of the resident output tile); :func:`repro.kernels.ops.fused_gcn_layer`
    applies the padding automatically.
    """
    if config is None:
        config = DEFAULT_CONFIG
    n, f = h.shape
    e = edge_src.shape[0]
    fo = wmat.shape[1]
    check_shape_contract(n, f, e, num_nodes, config)
    if fo % LANES != 0:
        raise ShapeContractError(
            [f"FO={fo} not a multiple of {LANES} (output lane tile)"],
            (n, f, e), (n, f, e))
    if inv_scale is None:
        inv_scale = jnp.ones((n,), jnp.float32)
    if src_perm is None:
        src_perm = jnp.argsort(edge_src)
    return _fused_diff(interpret_mode(), activate, config, h, edge_src,
                       edge_dst, edge_weight, inv_scale.astype(jnp.float32),
                       wmat, b, src_perm)
