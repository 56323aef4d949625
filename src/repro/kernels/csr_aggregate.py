"""Pallas TPU kernel: weighted neighbor aggregation (edge-list SpMM),
node-tiled, streamed, and differentiable.

The GNN hot-spot: ``out[d] += w[e] * h[src[e]]`` over a weight-0-padded
arc list. GPU implementations use shared-memory atomics; TPU has no scatter
hardware, so we ADAPT (see DESIGN.md §3): the scatter becomes a **one-hot
matmul** that feeds the MXU —

    G   = h[src]                                      # XLA gather [E, F]
    for each node tile N_t, feature tile F_t:
        for each edge block E_b whose dst range meets N_t:
            S   = onehot(dst[E_b] - N_t.start) * w[E_b]   # scatter [NT, EB]
            out[N_t, F_t] += S @ G[E_b, F_t]              # MXU     [NT, FT]
        out[N_t, F_t] *= inv_scale[N_t, None]         # fused epilogue

The row gather runs in XLA before the ``pallas_call``: Mosaic cannot lower
an arbitrary row gather inside a kernel. The pre-gathered rows and the
``[1, E]`` dst and weight rows stay in HBM (``memory_space=pl.ANY``); the
``[NT, 1]`` ``inv_scale`` column is a blocked operand.

Blocking: the grid is (partitions × node tiles × feature tiles). The tile
sizes come from a :class:`repro.kernels.autotune.KernelConfig` (the module
constants are the untuned default point). Edges are read in granules of
``stream × edge_block`` arcs (DESIGN.md §14):

* **Per-tile granule range.** ``edge_dst`` arrives sorted (the assemble
  layout; :mod:`repro.kernels.ops` keeps it sorted through its alignment
  padding), so a granule meets one or two node tiles. The wrapper computes,
  per node tile, the first and last granule whose dst range ``[lo, hi]``
  meets it (:func:`tile_granule_ranges`, a ``[T, G]`` compare in XLA), and
  each grid step streams exactly that range: a ``fori_loop`` that
  double-buffers ``[granule, FT]`` row blocks and the granule's arc rows
  with ``pltpu.make_async_copy`` (start granule ``g+1``, then compute
  ``g``). On a sorted list the ranges visit at most ``G + T − 1`` (tile,
  granule) pairs, against ``G · T`` for a grid over every pair; on an
  unsorted list a range widens up to every granule, so the result never
  depends on the order. A tile whose range is empty runs only its init and
  epilogue. :func:`repro.kernels.ops.streamed_pairs` counts the pairs on
  the host.

* **Degenerate-block skip.** Inside a granule, each of the ``stream``
  sub-blocks wraps its one-hot matmul in ``pl.when(block ∩ tile ≠ ∅)``
  over the per-block dst range (:func:`edge_block_ranges`, in SMEM) — a
  granule at a range's edge, or inside a widened range, skips the MXU pass
  of the blocks that miss the tile. Weight-0 padding arcs can only
  *widen* a range, never corrupt a result.

The VMEM working set per step (:func:`repro.kernels.autotune.vmem_bytes`)
is the two ``[granule, FT]`` row buffers and two arc-row buffers, the
``[NT, FT]`` output tile and the ``[NT, EB]`` one-hot: nothing in it
scales with N or E. Accumulation is f32 into the resident output tile,
and the matmuls run at ``Precision.HIGHEST`` so a TPU result matches the
f32 segment-sum reference instead of a one-pass bf16 product.

The kernels take the partition axis as their leading grid dimension:
Mosaic refuses ``vmap``'s batching of an ``ANY`` operand, so
:func:`partition_batched` folds ``vmap`` into that axis instead (one
partition outside ``vmap``, one per device under ``shard_map``).

Differentiation (DESIGN.md §11): ``csr_aggregate_pallas`` carries a
``jax.custom_vjp``. With A the [N, N] weighted adjacency the forward is
``out = diag(inv_scale) · A · h``, so

* the h-cotangent is ``Aᵀ · diag(inv_scale) · g`` — the *same* kernel run
  over the reversed arc list ``(dst, src)`` with weights
  ``w[e]·inv_scale[dst[e]]`` and no epilogue, re-sorted by the new
  destination (= original source) via a precomputed permutation, so its
  granule ranges are tight too;
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


def _smem_rows(x: jnp.ndarray) -> jnp.ndarray:
    """A flat int32 vector as a zero-padded ``[len / 128, 128]`` array:
    entry ``j`` sits at ``[j // 128, j % 128]``. Under ``vmap`` an SMEM
    operand gains a leading partition axis, and Mosaic only accepts its
    per-partition block when the last two dims are whole — a flat row is
    not."""
    return jnp.pad(x, (0, -x.shape[0] % 128)).reshape(-1, 128)


def edge_block_ranges(edge_dst: jnp.ndarray, edge_block: int):
    """Per-edge-block dst range [lo, hi] feeding the degenerate-block skip
    and :func:`tile_granule_ranges`. Computed on the padded arc list;
    weight-0 padding arcs only widen a range — the skip is conservative.
    Two int32 ``[blocks / 128, 128]`` SMEM arrays (the kernel never reads
    the padding)."""
    blocks = edge_dst.astype(jnp.int32).reshape(-1, edge_block)
    return (_smem_rows(jnp.min(blocks, axis=1)),
            _smem_rows(jnp.max(blocks, axis=1)))


def tile_granule_ranges(lo, hi, n: int, e: int, config: KernelConfig):
    """First and last granule ``[g0[i], g1[i]]`` whose dst range meets node
    tile ``i``: the range the kernel streams for that tile. ``lo``/``hi``
    are the block ranges of :func:`edge_block_ranges` over ``e`` arcs; a
    granule's range is that of its ``stream`` blocks. A tile no granule
    meets gets ``g0 = G``, ``g1 = -1`` (an empty loop).

    Tight for a sorted list; for an unsorted one the range spans every
    granule from the first to the last that meets the tile. Two int32
    ``[tiles / 128, 128]`` SMEM arrays, like :func:`edge_block_ranges`."""
    nt = _node_tile(n, config.node_tile)
    num_granules = e // config.edge_granule
    blocks = num_granules * config.stream
    glo = lo.reshape(-1)[:blocks].reshape(num_granules, -1).min(axis=1)
    ghi = hi.reshape(-1)[:blocks].reshape(num_granules, -1).max(axis=1)
    starts = jnp.arange(n // nt, dtype=jnp.int32)[:, None] * nt
    meets = (ghi[None, :] >= starts) & (glo[None, :] < starts + nt)  # [T, G]
    g = jnp.arange(num_granules, dtype=jnp.int32)
    g0 = jnp.min(jnp.where(meets, g, num_granules), axis=1)
    g1 = jnp.max(jnp.where(meets, g, -1), axis=1)
    return _smem_rows(g0), _smem_rows(g1)


def accumulate_edge_granule(lo_ref, hi_ref, dst_ref, w_ref, rows_ref,
                            acc_ref, *, edge_block: int, stream: int,
                            granule_idx, tile_lo):
    """``acc_ref[NT, FT] += onehot(dst) · w @ rows`` over one edge granule.

    ``rows_ref`` holds the granule's pre-gathered source rows
    ``[EB·S, FT]``; ``dst_ref``/``w_ref`` its ``[1, EB·S]`` arc rows. Each
    of the ``stream`` sub-blocks is skipped when its dst range misses the
    node tile that starts at row ``tile_lo``."""
    nt = acc_ref.shape[0]
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


def stream_tile_granules(g0_ref, g1_ref, lo_ref, hi_ref, dst_hbm, w_hbm,
                         rows_hbm, acc_ref, dst_buf, w_buf, rows_buf, sems,
                         *, edge_block: int, stream: int):
    """Accumulate this grid step's node tile over its granule range.

    The step is ``(partition p, node tile i, feature tile ft)``. The arc
    rows ``[P, 1, E]`` and pre-gathered source rows ``[P, E, F]`` stay in
    HBM; granule ``g`` of the range ``[g0[i], g1[i]]`` is copied into slot
    ``(g - g0) % 2`` of the two-slot VMEM buffers, the copy of ``g + 1``
    starting before ``g`` is computed. Shared by the aggregation and the
    fused-layer kernels."""
    p, i, ft = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    granule = edge_block * stream
    ft_sz = rows_buf.shape[-1]
    first = g0_ref[i // 128, i % 128]
    last = g1_ref[i // 128, i % 128]

    def copies(g, slot):
        arcs = pl.ds(pl.multiple_of(g * granule, granule), granule)
        feats = pl.ds(pl.multiple_of(ft * ft_sz, ft_sz), ft_sz)
        return (pltpu.make_async_copy(dst_hbm.at[p, :, arcs],
                                      dst_buf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(w_hbm.at[p, :, arcs],
                                      w_buf.at[slot], sems.at[1, slot]),
                pltpu.make_async_copy(rows_hbm.at[p, arcs, feats],
                                      rows_buf.at[slot], sems.at[2, slot]))

    @pl.when(first <= last)
    def _prime():
        for c in copies(first, 0):
            c.start()

    def body(g, carry):
        slot = (g - first) % 2

        @pl.when(g < last)
        def _prefetch():
            for c in copies(g + 1, 1 - slot):
                c.start()

        for c in copies(g, slot):
            c.wait()
        accumulate_edge_granule(lo_ref, hi_ref, dst_buf.at[slot],
                                w_buf.at[slot], rows_buf.at[slot], acc_ref,
                                edge_block=edge_block, stream=stream,
                                granule_idx=g, tile_lo=i * acc_ref.shape[0])
        return carry

    jax.lax.fori_loop(first, last + 1, body, 0)


def stream_specs(ranges, dst, w, rows, config: KernelConfig):
    """``in_specs`` of the streamed operands, in kernel order — the SMEM
    ranges (``g0, g1, lo, hi``), then the HBM arc rows ``[P, 1, E]`` and
    source rows ``[P, E, F]`` — and the ``scratch_shapes`` that
    :func:`stream_tile_granules` streams them through: two-slot VMEM
    buffers and their DMA semaphores."""
    granule = config.edge_granule
    ft_sz = min(config.feat_tile, rows.shape[-1])
    smem = [pl.BlockSpec((None,) + r.shape[1:], lambda p, i, ft: (p, 0, 0),
                         memory_space=pltpu.SMEM) for r in ranges]
    hbm = [pl.BlockSpec(memory_space=pl.ANY)] * 3
    scratch = [pltpu.VMEM((2, 1, granule), dst.dtype),
               pltpu.VMEM((2, 1, granule), w.dtype),
               pltpu.VMEM((2, granule, ft_sz), rows.dtype),
               pltpu.SemaphoreType.DMA((3, 2))]
    return smem + hbm, scratch


def stream_inputs(h, edge_src, edge_dst, edge_weight, config: KernelConfig):
    """The streamed operands of one partition, under the aggregation
    scope: the four range arrays, the ``[1, E]`` arc rows and the XLA row
    gather ``h[src]``."""
    n, e = h.shape[0], edge_dst.shape[0]
    lo, hi = edge_block_ranges(edge_dst, config.edge_block)
    return (*tile_granule_ranges(lo, hi, n, e, config), lo, hi,
            edge_dst.reshape(1, e), edge_weight.reshape(1, e),
            jnp.take(h, edge_src, axis=0))              # XLA gather: [E, F]


def partition_batched(call):
    """``call(*operands)`` maps arrays with a leading partition axis to
    arrays with one. Returns the function of ONE partition (the axis
    added and taken off again) whose ``vmap`` folds the mapped axis into
    the partition axis, so that the kernel's grid takes it.

    Mosaic refuses Pallas's own batching of a kernel with ``ANY``
    operands; this rule replaces it. Unmapped operands are broadcast."""
    @jax.custom_batching.custom_vmap
    def batched(*args):
        return call(*args)

    @batched.def_vmap
    def _fold(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        out = batched(*[a.reshape((-1,) + a.shape[2:]) for a in args])
        out = jax.tree.map(
            lambda o: o.reshape((axis_size, -1) + o.shape[1:]), out)
        return out, jax.tree.map(lambda _: True, out)

    def one(*args):
        return jax.tree.map(lambda o: o[0],
                            batched(*[a[None] for a in args]))
    return one


def _agg_kernel(g0_ref, g1_ref, lo_ref, hi_ref, dst_hbm, w_hbm, rows_hbm,
                *refs, edge_block: int, stream: int):
    *inv_ref, out_ref, dst_buf, w_buf, rows_buf, sems = refs
    out_ref[...] = jnp.zeros_like(out_ref)
    stream_tile_granules(g0_ref, g1_ref, lo_ref, hi_ref, dst_hbm, w_hbm,
                         rows_hbm, out_ref, dst_buf, w_buf, rows_buf, sems,
                         edge_block=edge_block, stream=stream)
    if inv_ref:                                          # scale epilogue
        out_ref[...] = out_ref[...] * inv_ref[0][...]    # [NT, 1] column


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


def _agg_call(g0, g1, lo, hi, dst, w, rows, *inv, n: int, interpret: bool,
              config: KernelConfig):
    """The aggregation ``pallas_call`` over a leading partition axis;
    ``inv`` is the ``[P, N, 1]`` scale column, or absent for none."""
    parts, _, f = rows.shape
    nt = _node_tile(n, config.node_tile)
    ft_sz = min(config.feat_tile, f)
    in_specs, scratch = stream_specs((g0, g1, lo, hi), dst, w, rows, config)
    if inv:
        in_specs.append(pl.BlockSpec((None, nt, 1),
                                     lambda p, i, ft: (p, i, 0)))
    return pl.pallas_call(
        functools.partial(_agg_kernel, edge_block=config.edge_block,
                          stream=config.stream),
        grid=(parts, n // nt, f // ft_sz),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, nt, ft_sz),
                               lambda p, i, ft: (p, i, ft)),
        out_shape=jax.ShapeDtypeStruct((parts, n, f), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
        name="csr_aggregate",
    )(g0, g1, lo, hi, dst, w, rows, *inv)


def _aggregate(h, edge_src, edge_dst, edge_weight, inv_scale, *,
               interpret: bool, config: KernelConfig) -> jnp.ndarray:
    """Aligned-domain forward: one pallas_call, f32 accumulate, then each
    row times ``inv_scale`` (``None``: no epilogue, as in the transposed
    pass)."""
    n = h.shape[0]
    call = partition_batched(functools.partial(
        _agg_call, n=n, interpret=interpret, config=config))
    inv = () if inv_scale is None else (inv_scale.reshape(n, 1),)
    with jax.named_scope("aggregation"):
        out = call(*stream_inputs(h, edge_src, edge_dst, edge_weight, config),
                   *inv)
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
    # h-cotangent: transpose aggregation — the same kernel over the reversed
    # (src-sorted) arc list, normalization folded into the reverse weights.
    rev_w = jnp.take(w.astype(jnp.float32) * jnp.take(inv, dst), perm)
    dh = _aggregate(g32, jnp.take(dst, perm), jnp.take(src, perm), rev_w,
                    None, interpret=interpret, config=config).astype(h.dtype)
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
