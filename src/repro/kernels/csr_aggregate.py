"""Pallas TPU kernel: weighted neighbor aggregation (edge-list SpMM),
node-tiled, streamed, and differentiable.

The GNN hot-spot: ``out[d] += w[e] * h[src[e]]`` over a weight-0-padded
arc list. GPU implementations use shared-memory atomics; TPU has no scatter
hardware, so we ADAPT (see DESIGN.md §3): the scatter becomes a **one-hot
matmul** that feeds the MXU —

    G   = h[src]                                      # XLA gather [E, F]
    for each node tile N_t, feature tile F_t, edge block E_b:
        S   = onehot(dst[E_b] - N_t.start) * w[E_b]   # scatter  [NT, EB]
        out[N_t, F_t] += S @ G[E_b, F_t]              # MXU      [NT, FT]
    after the last edge block:
        out[N_t, F_t] *= inv_scale[N_t, None]         # fused epilogue

The row gather runs in XLA before the ``pallas_call``: Mosaic cannot lower
an arbitrary row gather inside a kernel, so the kernel streams
``[granule, FT]`` blocks of the pre-gathered rows like any other operand.
Every operand block is 2-D and lane-dense: the arc arrays travel as
``[1, E]`` rows and ``inv_scale`` as an ``[N, 1]`` column, because Mosaic
and XLA tile 1-D arrays differently.

Blocking: the grid is (node tiles × feature tiles × edge granules). The
tile sizes come from a :class:`repro.kernels.autotune.KernelConfig`
(the module constants are the untuned default point). Two refinements
(DESIGN.md §14):

* **Degenerate-tile fast path.** ``edge_dst`` arrives sorted (the assemble
  layout), so most edge blocks touch one or two node tiles. The wrapper
  precomputes each block's dst range ``[lo, hi]`` (two small int32 arrays
  in SMEM) and the kernel wraps the one-hot matmul in
  ``pl.when(block ∩ tile ≠ ∅)`` — a skipped block costs a scalar compare
  instead of an [NT, EB] × [EB, FT] MXU pass. Weight-0 padding arcs can
  only *widen* a block's range, never corrupt a result.

* **Streamed edge granules.** The edge BlockSpecs load
  ``stream × edge_block`` arcs per grid step (one larger DMA that Pallas
  pipelines against compute across grid steps), and the kernel unrolls
  over the ``stream`` sub-blocks, each with its own skip guard.

The VMEM working set per step (:func:`repro.kernels.autotune.vmem_bytes`)
is the double-buffered ``[granule, FT]`` row block, the arc rows, the
``[NT, FT]`` output tile and the ``[NT, EB]`` one-hot: nothing in it
scales with N. The output block index is independent of the edge-granule
grid dimension, so Pallas keeps it resident and we accumulate across
granules (init at granule 0, scale epilogue at the last). Accumulation is
f32, and the matmuls run at ``Precision.HIGHEST`` so a TPU result matches
the f32 segment-sum reference instead of a one-pass bf16 product.

Differentiation (DESIGN.md §11): ``csr_aggregate_pallas`` carries a
``jax.custom_vjp``. With A the [N, N] weighted adjacency the forward is
``out = diag(inv_scale) · A · h``, so

* the h-cotangent is ``Aᵀ · diag(inv_scale) · g`` — the *same* kernel run
  over the reversed arc list ``(dst, src)`` with weights
  ``w[e]·inv_scale[dst[e]]`` and no epilogue, re-sorted by the new
  destination (= original source) via a precomputed permutation;
* the edge-weight cotangent is the per-edge row dot
  ``dw[e] = inv_scale[dst[e]] · <g[dst[e]], h[src[e]]>`` — a small
  companion kernel (``_edge_dot_kernel``) that fuses the multiply-reduce
  over feature tiles so the [E, F] products never hit HBM; its output is
  one lane-dense ``[1, E]`` row;
* ``inv_scale`` (the fused degree normalization) and the arc lists are
  graph *structure*, not trainable data: their cotangents are defined as
  zero (``float0`` for the int arrays).

Names on the device (DESIGN.md §16): the kernels are ``csr_aggregate``
(forward and transposed aggregation) and ``edge_dot``; each runs with its
XLA row gathers under ``jax.named_scope("aggregation")``, the scope the jnp
path's gather and segment-sum share, so that a profile finds every
path's aggregation ops by it.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import KernelConfig, interpret_mode

# The untuned tile point — the default KernelConfig; the autotuner
# supersedes it per (backend, shape-bucket).
NODE_TILE = 512
EDGE_BLOCK = 256
FEAT_TILE = 128

DEFAULT_CONFIG = KernelConfig(strategy="pallas", node_tile=NODE_TILE,
                              edge_block=EDGE_BLOCK, feat_tile=FEAT_TILE,
                              stream=1)


class ShapeContractError(ValueError):
    """A kernel input violates the F/E/N divisibility contract.

    Carries which constraint failed and the nearest valid padded shape, so
    the caller (usually a human who bypassed :mod:`repro.kernels.ops`)
    knows exactly what to pad to."""

    def __init__(self, failures, got, valid):
        self.failures = tuple(failures)
        self.got = got
        self.valid = valid
        super().__init__(
            "kernel shape contract violated: "
            + "; ".join(failures)
            + f". Got (N={got[0]}, F={got[1]}, E={got[2]}); nearest valid "
              f"padded shape is (N={valid[0]}, F={valid[1]}, E={valid[2]}). "
              "repro.kernels.ops.csr_aggregate applies this padding "
              "automatically (weight-0 arcs, see its padding contract).")


def check_shape_contract(n: int, f: int, e: int, num_nodes: int,
                         config: KernelConfig) -> None:
    """Raise :class:`ShapeContractError` naming every violated constraint."""
    ft, granule, nt = config.feat_tile, config.edge_granule, config.node_tile
    failures = []
    if n != num_nodes:
        failures.append(f"N={n} != num_nodes={num_nodes} (pad h first)")
    if f % ft != 0:
        failures.append(f"F={f} not a multiple of feat_tile={ft}")
    if e % granule != 0:
        failures.append(
            f"E={e} not a multiple of edge_block*stream="
            f"{config.edge_block}*{config.stream}={granule}")
    if n > nt:
        if n % nt != 0:
            failures.append(
                f"N={n} > node_tile={nt} but not a multiple of it")
    elif n % 8 == 0:
        pass
    else:
        failures.append(f"N={n} <= node_tile={nt} but not a multiple of 8")
    if failures:
        n_valid = (((n + nt - 1) // nt) * nt if n > nt
                   else ((n + 7) // 8) * 8)
        f_valid = ((f + ft - 1) // ft) * ft
        e_valid = ((e + granule - 1) // granule) * granule
        raise ShapeContractError(failures, (n, f, e),
                                 (n_valid, f_valid, e_valid))


def edge_block_ranges(edge_dst: jnp.ndarray, edge_block: int):
    """Per-edge-block dst range [lo, hi] feeding the degenerate-tile fast
    path. Computed on the padded arc list; weight-0 padding arcs only widen
    a range — the skip is conservative.

    Block ``b``'s range sits at ``[b // 128, b % 128]`` of two int32
    ``[blocks / 128, 128]`` arrays (zero-padded; the kernel never reads
    the padding). Under ``vmap`` the SMEM operand gains a leading
    partition axis, and Mosaic only accepts its per-partition block when
    the last two dims are whole — a flat ``[blocks]`` row is not."""
    blocks = edge_dst.astype(jnp.int32).reshape(-1, edge_block)
    pad = (0, -blocks.shape[0] % 128)
    return (jnp.pad(jnp.min(blocks, axis=1), pad).reshape(-1, 128),
            jnp.pad(jnp.max(blocks, axis=1), pad).reshape(-1, 128))


def accumulate_edge_granule(lo_ref, hi_ref, dst_ref, w_ref, rows_ref,
                            acc_ref, *, edge_block: int, stream: int,
                            granule_idx):
    """``acc_ref[NT, FT] += onehot(dst) · w @ rows`` over one edge granule.

    ``rows_ref`` holds the granule's pre-gathered source rows
    ``[EB·S, FT]``; ``dst_ref``/``w_ref`` its ``[1, EB·S]`` arc rows. Each
    of the ``stream`` sub-blocks is skipped when its dst range misses this
    node tile. Shared by the aggregation and the fused-layer kernels."""
    nt = acc_ref.shape[0]
    tile_lo = pl.program_id(0) * nt
    for s in range(stream):                  # unrolled sub-blocks
        blk = granule_idx * stream + s
        lo = lo_ref[blk // 128, blk % 128]
        hi = hi_ref[blk // 128, blk % 128]

        @pl.when(jnp.logical_and(hi >= tile_lo, lo < tile_lo + nt))
        def _compute(s=s):
            cols = slice(s * edge_block, (s + 1) * edge_block)
            dst = dst_ref[:, cols]                         # [1, EB]
            w = w_ref[:, cols].astype(jnp.float32)         # [1, EB]
            rows = rows_ref[cols, :].astype(jnp.float32)   # [EB, FT]
            # masked one-hot scatter for THIS node tile:
            # S[i, e] = w[e] * (dst[e] == tile_start + i)  -> [NT, EB]
            ids = (jax.lax.broadcasted_iota(jnp.int32, (nt, edge_block), 0)
                   + tile_lo)
            scatter = jnp.where(ids == dst, w, 0.0)
            acc_ref[...] += jax.lax.dot(
                scatter, rows, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)


def _agg_kernel(lo_ref, hi_ref, dst_ref, w_ref, inv_ref, rows_ref, out_ref,
                *, edge_block: int, stream: int):
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    accumulate_edge_granule(lo_ref, hi_ref, dst_ref, w_ref, rows_ref,
                            out_ref, edge_block=edge_block, stream=stream,
                            granule_idx=sb)

    @pl.when(sb == pl.num_programs(2) - 1)
    def _epilogue():
        out_ref[...] = out_ref[...] * inv_ref[...]       # [NT, 1] column


def _edge_dot_kernel(a_ref, b_ref, out_ref):
    ft = pl.program_id(1)

    @pl.when(ft == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    prod = a_ref[...].astype(jnp.float32) * b_ref[...].astype(jnp.float32)
    # reduce over features along sublanes so the [1, EB] result is a
    # lane-dense row (an [EB] vector has no layout Mosaic and XLA share)
    out_ref[...] += jnp.sum(prod.T, axis=0, keepdims=True)


def _node_tile(n: int, node_tile: int) -> int:
    return n if n <= node_tile else node_tile


def edge_row_specs(granule: int):
    """BlockSpecs of the ``[1, E]`` dst and weight rows, one granule per
    step of the grid's last (edge) dimension."""
    spec = pl.BlockSpec((1, granule), lambda i, ft, sb: (0, sb))
    return [spec, spec]


def _aggregate(h, edge_src, edge_dst, edge_weight, inv_scale, *,
               interpret: bool, config: KernelConfig) -> jnp.ndarray:
    """Aligned-domain forward: one pallas_call, f32 accumulate + epilogue."""
    n, f = h.shape
    e = edge_src.shape[0]
    nt = _node_tile(n, config.node_tile)
    eb, ft_sz, stream = config.edge_block, config.feat_tile, config.stream
    ft_sz = min(ft_sz, f)
    granule = eb * stream
    grid = (n // nt, f // ft_sz, e // granule)
    with jax.named_scope("aggregation"):
        lo, hi = edge_block_ranges(edge_dst, eb)
        rows = jnp.take(h, edge_src, axis=0)     # XLA gather: [E, F]
        out = pl.pallas_call(
            functools.partial(_agg_kernel, edge_block=eb, stream=stream),
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),    # lo
                pl.BlockSpec(memory_space=pltpu.SMEM),    # hi
                *edge_row_specs(granule),                 # dst, w
                pl.BlockSpec((nt, 1), lambda i, ft, sb: (i, 0)),
                pl.BlockSpec((granule, ft_sz), lambda i, ft, sb: (sb, ft)),
            ],
            out_specs=pl.BlockSpec((nt, ft_sz), lambda i, ft, sb: (i, ft)),
            out_shape=jax.ShapeDtypeStruct((n, f), jnp.float32),
            interpret=interpret,
            name="csr_aggregate",
        )(lo, hi, edge_dst.reshape(1, e), edge_weight.reshape(1, e),
          inv_scale.reshape(n, 1), rows)
    return out.astype(h.dtype)


def _edge_dot(a, b, *, interpret: bool, config: KernelConfig) -> jnp.ndarray:
    """Per-edge row dot <a[e, :], b[e, :]> -> [E], f32, feature-tiled.
    Callers gather the rows and put both under the aggregation scope."""
    e, f = a.shape
    eb, ft_sz = config.edge_block, min(config.feat_tile, f)
    grid = (e // eb, f // ft_sz)
    out = pl.pallas_call(
        _edge_dot_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((eb, ft_sz), lambda i, ft: (i, ft)),
            pl.BlockSpec((eb, ft_sz), lambda i, ft: (i, ft)),
        ],
        out_specs=pl.BlockSpec((1, eb), lambda i, ft: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, e), jnp.float32),
        interpret=interpret,
        name="edge_dot",
    )(a, b)
    return out.reshape(e)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _aggregate_diff(interpret, config, h, edge_src, edge_dst, edge_weight,
                    inv_scale, src_perm):
    # src_perm is only consumed by the backward pass; in the primal it is an
    # unused parameter, so XLA dead-code-eliminates the argsort that feeds it
    # whenever the call is not differentiated.
    del src_perm
    return _aggregate(h, edge_src, edge_dst, edge_weight, inv_scale,
                      interpret=interpret, config=config)


def _aggregate_diff_fwd(interpret, config, h, edge_src, edge_dst,
                        edge_weight, inv_scale, src_perm):
    out = _aggregate(h, edge_src, edge_dst, edge_weight, inv_scale,
                     interpret=interpret, config=config)
    return out, (h, edge_src, edge_dst, edge_weight, inv_scale, src_perm)


def _aggregate_diff_bwd(interpret, config, res, g):
    h, src, dst, w, inv, perm = res
    g32 = g.astype(jnp.float32)
    ones = jnp.ones((h.shape[0],), jnp.float32)
    # h-cotangent: transpose aggregation — the same kernel over the reversed
    # (src-sorted) arc list, normalization folded into the reverse weights.
    rev_w = jnp.take(w.astype(jnp.float32) * jnp.take(inv, dst), perm)
    dh = _aggregate(g32, jnp.take(dst, perm), jnp.take(src, perm), rev_w,
                    ones, interpret=interpret, config=config).astype(h.dtype)
    # w-cotangent: per-edge row dot of h[src] with the scaled cotangent rows.
    g_scaled = g32 * inv.astype(jnp.float32)[:, None]
    with jax.named_scope("aggregation"):
        dw = _edge_dot(jnp.take(h.astype(jnp.float32), src, axis=0),
                       jnp.take(g_scaled, dst, axis=0),
                       interpret=interpret, config=config).astype(w.dtype)
    zero_int = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    # inv_scale is graph structure (degree normalization): zero by design.
    return (dh, zero_int(src), zero_int(dst), dw, jnp.zeros_like(inv),
            zero_int(perm))


_aggregate_diff.defvjp(_aggregate_diff_fwd, _aggregate_diff_bwd)


@functools.partial(jax.jit, static_argnames=("num_nodes", "config"))
def csr_aggregate_pallas(h: jnp.ndarray, edge_src: jnp.ndarray,
                         edge_dst: jnp.ndarray, edge_weight: jnp.ndarray,
                         num_nodes: int,
                         inv_scale: jnp.ndarray | None = None,
                         src_perm: jnp.ndarray | None = None,
                         config: KernelConfig | None = None
                         ) -> jnp.ndarray:
    """Pallas path. h: [N, F] -> [N, F] (f32 accumulate, cast back).

    Differentiable w.r.t. ``h`` and ``edge_weight`` (custom VJP, see module
    docstring). ``inv_scale`` ([N], default all-ones) is multiplied into
    each output row by the kernel epilogue — pass ``1/max(degree, 1)`` to
    fuse mean normalization into the same kernel call; it is treated as
    graph structure (zero cotangent). ``src_perm`` (default
    ``argsort(edge_src)``, dead-code-eliminated unless differentiated)
    orders the reversed arc list for the transpose pass of the VJP.
    ``config`` (default: the untuned tile point) selects the tuned tile
    sizes and stream factor — resolve one with
    :func:`repro.kernels.autotune.get_config`.

    Inputs are padded by :func:`repro.kernels.ops.csr_aggregate`; this
    function requires F % feat_tile == 0, E % (edge_block*stream) == 0, and
    N % 8 == 0 when N <= node_tile else N % node_tile == 0 — violations
    raise :class:`ShapeContractError` naming the failed constraint and the
    nearest valid padded shape.
    """
    if config is None:
        config = DEFAULT_CONFIG
    n, f = h.shape
    e = edge_src.shape[0]
    check_shape_contract(n, f, e, num_nodes, config)
    if inv_scale is None:
        inv_scale = jnp.ones((n,), jnp.float32)
    if src_perm is None:
        src_perm = jnp.argsort(edge_src)
    return _aggregate_diff(interpret_mode(), config, h, edge_src, edge_dst,
                           edge_weight, inv_scale.astype(jnp.float32),
                           src_perm)
